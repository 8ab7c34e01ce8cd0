"""Milliseconds per tree that no phase names: the ``train.fit`` spans'
self time, plus the window's seconds outside any ``train.fit``.  A phase
that nobody instrumented shows here."""

from benchmark.lib import spans


def read(run):
    return spans.unattributed_ms_per_tree(run)
