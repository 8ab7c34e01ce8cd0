"""Host milliseconds per tree under ``train.device_wait``: the enqueued
chunk's device work and its drain (the table's transfer is
``train.upload_wait``'s): the program's own view of ``tree_device_ms``,
whole on four chips where a trace's plane may come back short."""

from benchmark.lib import spans


def read(run):
    return spans.phase_ms_per_tree(run, ("train.device_wait",))
