"""Host milliseconds per tree under ``train.refprofile_counts``: the
reference profile's count pass from its dispatch to the counts on the
host, the wait for the device included (on the host path, the column
passes)."""

from benchmark.lib import spans


def read(run):
    return spans.phase_ms_per_tree(run, ("train.refprofile_counts",))
