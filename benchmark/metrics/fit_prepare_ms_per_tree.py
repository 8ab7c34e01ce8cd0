"""Host milliseconds per tree under ``train.prepare`` and
``train.fit_attrs``: what a fit does before it hands over to the upload
(weights, the objective's passes over the labels, the histogram
schedule, argument checks, the budget guard) and the attrs it computes
of itself at its end."""

from benchmark.lib import spans


def read(run):
    return spans.phase_ms_per_tree(run, ("train.prepare", "train.fit_attrs"))
