"""Bytes a tree's collectives carry, from the ``train.fit`` spans'
``collective_bytes`` (the grower's schedule times the trees grown)."""

from benchmark.lib import spans


def read(run):
    return spans.root_attr_per_tree(run, "collective_bytes")
