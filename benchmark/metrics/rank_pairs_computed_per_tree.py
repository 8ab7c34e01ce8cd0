"""Pair slots a tree's lambdarank gradient computes: the sum over the
query layout's size classes of chunks x queries x length^2, from the
``train.fit`` spans' ``rank_pairs_computed``."""

from benchmark.lib import spans


def read(run):
    return spans.root_attr_per_tree(run, "rank_pairs_computed")
