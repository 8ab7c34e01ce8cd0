"""Host milliseconds per tree under ``train.upload_wait``: after a fit's
first chunk is enqueued, the wait for the binned table, labels and
weights (a ranker's layout) that ``jnp.asarray`` started to send and did
not wait for.  The device does nothing else while it is open."""

from benchmark.lib import spans


def read(run):
    return spans.phase_ms_per_tree(run, ("train.upload_wait",))
