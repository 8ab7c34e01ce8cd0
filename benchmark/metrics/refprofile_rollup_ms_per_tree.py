"""Host milliseconds per tree under ``train.refprofile_rollup``:
``build_reference_profile``, the counts rolled up into sketches feature
by feature, and the margin sketch."""

from benchmark.lib import spans


def read(run):
    return spans.phase_ms_per_tree(run, ("train.refprofile_rollup",))
