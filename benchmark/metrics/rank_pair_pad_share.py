"""Share of the computed pair slots that no query's own pairs fill, in
percent: 100 x (1 - useful / computed), from the ``train.fit`` spans'
``rank_pairs_useful`` (the sum over queries of their squared sizes) and
``rank_pairs_computed``.  None where a fit carries neither."""

from benchmark.lib import spans


def read(run):
    useful = spans.root_attr_per_tree(run, "rank_pairs_useful")
    computed = spans.root_attr_per_tree(run, "rank_pairs_computed")
    if useful is None or not computed:
        return None
    return 100.0 * (1.0 - useful / computed)
