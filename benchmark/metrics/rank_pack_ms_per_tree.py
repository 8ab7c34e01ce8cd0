"""Host milliseconds per tree under ``train.rank_pack``: the query
layout's upload inside an ``engine.train`` call."""

from benchmark.lib import spans


def read(run):
    return spans.phase_ms_per_tree(run, ("train.rank_pack",))
