"""``fit_tree_mfu`` for a cell whose table is one-hot coded: the least
time the cell's chips could take for the histogram work that the grown
trees required, counted over the LEAST bundle columns and the bins the
columns use (benchmark/lib/work_sparse.py: from the configuration's own
columns, not the program's bundle count), over the window's seconds per
tree, in percent.  The trees are parsed from the text the driver keeps
under ``state["sparse_model_text"]``."""

from benchmark.lib import work, work_sparse
from benchmark.reference import gbdt


def read(run):
    text = run.state.get("sparse_model_text")
    if not text or not run.work.get("trees"):
        return None
    trees = gbdt.parse_model(text)
    ops, moved = work_sparse.histogram_work(
        trees, run.state["onehot_blocks"], run.state["numeric_columns"],
        run.state["num_bins"] - 1)
    least, _ = work.least_seconds(ops, moved, run.peak, run.chips)
    per_tree = run.work["window_s"] / run.work["trees"]
    return 100.0 * (least / len(trees)) / per_tree
