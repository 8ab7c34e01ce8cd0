"""``fit_tree_mfu`` for a cell whose table holds missing values: the
least time the cell's chips could take for the histogram work the grown
trees required (benchmark/lib/work.py), over the window's seconds per
tree, in percent.  The trees are parsed by ``reference/gbdt_missing.py``
from the text the driver keeps under ``state["missing_model_text"]``."""

from benchmark.lib import work
from benchmark.reference import gbdt_missing


def read(run):
    text = run.state.get("missing_model_text")
    if not text or not run.work.get("trees"):
        return None
    trees = gbdt_missing.parse_model(text)
    ops, moved = work.histogram_work(
        trees, run.state["features"], run.state["num_bins"])
    least, _ = work.least_seconds(ops, moved, run.peak, run.chips)
    per_tree = run.work["window_s"] / run.work["trees"]
    return 100.0 * (least / len(trees)) / per_tree
