"""Host milliseconds per tree under ``train.cat_bitsets``: the bin
bitsets of a fit's categorical splits turned into LightGBM's bitsets over
raw category values (inside ``train.finalize``)."""

from benchmark.lib import spans


def read(run):
    return spans.phase_ms_per_tree(run, ("train.cat_bitsets",))
