"""Collectives a tree issues, from the ``train.fit`` spans'
``collective_count`` (the grower's schedule times the trees grown)."""

from benchmark.lib import spans


def read(run):
    return spans.root_attr_per_tree(run, "collective_count")
