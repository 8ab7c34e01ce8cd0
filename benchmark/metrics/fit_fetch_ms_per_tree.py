"""Host milliseconds per tree under ``train.fetch_trees`` and
``train.finalize``: the grown trees to the host, and the Booster built
from them."""

from benchmark.lib import spans


def read(run):
    return spans.phase_ms_per_tree(run, ("train.fetch_trees",
                                         "train.finalize"))
