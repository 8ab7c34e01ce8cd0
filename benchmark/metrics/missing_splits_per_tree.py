"""Splits a tree makes whose node held rows in the split feature's
missing bin (rows its default direction routed), from the ``train.fit``
spans' ``missing_splits``: a fit on a table without missing values has
none to read."""

from benchmark.lib import spans


def read(run):
    return spans.root_attr_per_tree(run, "missing_splits")
