"""Host milliseconds per tree under ``train.monitor``: the chunk
boundary's gauges, the ``train_loss`` fetch and the journal line, which
is telemetry's own cost inside the fit."""

from benchmark.lib import spans


def read(run):
    return spans.phase_ms_per_tree(run, ("train.monitor",))
