"""``fit_tree_mfu`` for a multiclass cell: the least time the cell's chips
could take for the histogram work that the grown class trees required
(benchmark/lib/work.py; every class tree is a tree of its own, grown
from its class's gradients), over the window's seconds per tree, in
percent.  The trees are parsed by ``reference/gbdt_multi.py`` (which
reads the text's class count) from the text the driver keeps under
``state["multi_model_text"]``."""

from benchmark.lib import work
from benchmark.reference import gbdt_multi


def read(run):
    text = run.state.get("multi_model_text")
    if not text or not run.work.get("trees"):
        return None
    _, trees = gbdt_multi.parse_model(text)
    ops, moved = work.histogram_work(
        trees, run.state["features"], run.state["num_bins"])
    least, _ = work.least_seconds(ops, moved, run.peak, run.chips)
    per_tree = run.work["window_s"] / run.work["trees"]
    return 100.0 * (least / len(trees)) / per_tree
