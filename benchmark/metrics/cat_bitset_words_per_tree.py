"""u32 words of raw-value bitsets a tree's categorical splits export,
from the ``train.fit`` spans' ``cat_bitset_words``."""

from benchmark.lib import spans


def read(run):
    return spans.root_attr_per_tree(run, "cat_bitset_words")
