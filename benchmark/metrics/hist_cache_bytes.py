"""Bytes of a device's per-leaf histogram cache in the window's fits,
from the ``train.fit`` spans' ``hist_cache_bytes`` (the same in every
fit; in feature space whatever the table)."""

from benchmark.metrics.efb_bundle_columns import same_in_every_fit


def read(run):
    return same_in_every_fit(run, "hist_cache_bytes")
