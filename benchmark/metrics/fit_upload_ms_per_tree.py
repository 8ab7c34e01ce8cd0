"""Host milliseconds per tree under the program's ``train.upload`` span:
the binned table, labels, weights and scores put on the device(s) by
every ``engine.train`` call."""

from benchmark.lib import spans


def read(run):
    return spans.phase_ms_per_tree(run, ("train.upload",))
