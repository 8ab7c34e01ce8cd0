"""Host milliseconds per tree under ``train.refprofile_sample`` and
``train.refprofile_margins``: the sampled rows' take from the host
table, their upload and look-up, and the bin-space forest's margins of
them, to the host."""

from benchmark.lib import spans


def read(run):
    return spans.phase_ms_per_tree(run, ("train.refprofile_sample",
                                         "train.refprofile_margins"))
