"""Host milliseconds per tree under ``train.host_trees`` and
``train.booster``: the fetched trees made into HostTrees (thresholds,
and ``train.cat_bitsets`` inside it) and the Booster built from them,
the two parts of ``train.finalize``."""

from benchmark.lib import spans


def read(run):
    return spans.phase_ms_per_tree(run, ("train.host_trees", "train.booster"))
