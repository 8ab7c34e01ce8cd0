"""``fit_tree_mfu`` for a cell whose fits are a ranker's: the least time
the cell's chips could take for the histogram work that the grown trees
required (benchmark/lib/work.py; the pair pass of the gradients is not
in it: the share is of the whole step, whatever else the step does),
over the window's seconds per tree, in percent.  The trees are parsed by
``reference/gbdt.py`` from the text the driver keeps under
``state["rank_model_text"]``."""

from benchmark.lib import work
from benchmark.reference import gbdt


def read(run):
    text = run.state.get("rank_model_text")
    if not text or not run.work.get("trees"):
        return None
    trees = gbdt.parse_model(text)
    ops, moved = work.histogram_work(
        trees, run.state["features"], run.state["num_bins"])
    least, _ = work.least_seconds(ops, moved, run.peak, run.chips)
    per_tree = run.work["window_s"] / run.work["trees"]
    return 100.0 * (least / len(trees)) / per_tree
