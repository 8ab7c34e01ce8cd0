"""Host milliseconds per tree under ``train.reference_profile``: the
drift-monitoring sketch over the binned table that every fit is charged."""

from benchmark.lib import spans


def read(run):
    return spans.phase_ms_per_tree(run, ("train.reference_profile",))
