"""Operations and bytes that the histogram work of a grown tree requires,
counted from the tree and the shapes, whatever implements it.

A leaf-wise histogram learner has to histogram the root's rows and, at
every split, the rows of the smaller child (the larger child's histogram
is its parent's less its sibling's).  For each such row and each feature
it adds the row's gradient, hessian and count into one bin: 3 additions,
and at the least it reads the row's bin (1 byte a feature) and the row's
gradient pair, and writes each node's histogram once.
"""

BYTES_PER_BIN = 1           # uint8 bins, max_bin <= 255
CHANNELS = 3                # gradient, hessian, count
HIST_CELL_BYTES = 4 * CHANNELS


def rows_histogrammed(tree):
    """Root rows plus the smaller child's rows at every split; ``tree`` as
    benchmark/reference/gbdt.parse_model gives it."""
    if tree["num_leaves"] <= 1:
        return 0

    def count(c):
        return int(tree["leaf_count"][~c] if c < 0
                   else tree["internal_count"][c])

    total = int(tree["internal_count"][0])
    for lc, rc in zip(tree["left"], tree["right"]):
        total += min(count(lc), count(rc))
    return total


def histogram_work(trees, features, num_bins):
    """``(ops, bytes)`` for all of ``trees``."""
    rows = sum(rows_histogrammed(t) for t in trees)
    nodes = sum(2 * t["num_leaves"] - 1 for t in trees)
    ops = rows * features * CHANNELS
    moved = (rows * features * BYTES_PER_BIN          # the bins
             + rows * 4 * (CHANNELS - 1)              # gradient, hessian
             + nodes * features * num_bins * HIST_CELL_BYTES)
    return ops, moved


def least_seconds(ops, moved, peak, chips=1):
    """The larger of ops over the peak rate and bytes over the peak
    bandwidth, over ``chips``; and which of the two bounds it."""
    t_ops = ops / peak["flops_per_s"]
    t_mem = moved / peak["hbm_bytes_per_s"]
    bound = "memory" if t_mem >= t_ops else "compute"
    return max(t_ops, t_mem) / chips, bound
