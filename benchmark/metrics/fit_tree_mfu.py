"""The whole step's share of the chip's peak: the least time the cell's
chips could take for the histogram work that the grown trees required
(benchmark/lib/work.py, counted from the returned trees and the shapes),
over the window's seconds per tree, in percent."""

from benchmark.lib import work
from benchmark.reference import gbdt


def read(run):
    text = run.state.get("model_text")
    if not text or not run.work.get("trees"):
        return None
    trees = gbdt.parse_model(text)
    ops, moved = work.histogram_work(
        trees, run.state["features"], run.state["num_bins"])
    least, _ = work.least_seconds(ops, moved, run.peak, run.chips)
    per_tree = run.work["window_s"] / run.work["trees"]
    return 100.0 * (least / len(trees)) / per_tree
