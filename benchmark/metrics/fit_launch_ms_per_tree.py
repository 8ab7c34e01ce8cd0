"""Host milliseconds per tree under ``train.build_step`` and
``train.launch``: building the chunk program and the call that traces,
compiles or looks it up, and enqueues it."""

from benchmark.lib import spans


def read(run):
    return spans.phase_ms_per_tree(run, ("train.build_step", "train.launch"))
