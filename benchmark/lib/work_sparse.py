"""Operations and bytes that the histogram work of a grown tree requires
on a table of one-hot coded columns, counted from the tree and the
configuration's own columns, whatever implements it.

``lib/work.py`` counts one bin read a row and FEATURE and one histogram
cell a feature and bin.  On a one-hot table that overstates what has to
be read about a hundredfold: a block of ``k`` exclusive 0/1 columns is
one categorical value a row, and the least any implementation reads is
one byte a row for every 255 non-default bins it has to tell apart.  So
the work is counted over the LEAST bundle columns,
``ceil(sum_j (bins_j - 1) / 255)`` with ``bins_j`` the bins column ``j``
uses (2 for a 0/1 column, up to 255 for a numeric one), and a node's
histogram over the bins the columns use, ``sum_j bins_j`` cells: from
the configuration, never from the program's own bundle count.
"""

from benchmark.lib import work


def least_columns(onehot_blocks, numeric_columns, max_bin):
    """``(columns, cells)``: the least byte columns a row needs, and the
    histogram cells of a node, for ``numeric_columns`` columns of up to
    ``max_bin`` bins and one-hot blocks of the given sizes."""
    onehot = sum(int(k) for k in onehot_blocks)
    non_default = numeric_columns * (max_bin - 1) + onehot
    cells = numeric_columns * max_bin + 2 * onehot
    return -(-non_default // 255), cells


def histogram_work(trees, onehot_blocks, numeric_columns, max_bin):
    """``(ops, bytes)`` for all of ``trees`` (``work.histogram_work``'s
    arithmetic on the least columns and the used cells)."""
    columns, cells = least_columns(onehot_blocks, numeric_columns, max_bin)
    rows = sum(work.rows_histogrammed(t) for t in trees)
    nodes = sum(2 * t["num_leaves"] - 1 for t in trees)
    ops = rows * columns * work.CHANNELS
    moved = (rows * columns * work.BYTES_PER_BIN
             + rows * 4 * (work.CHANNELS - 1)
             + nodes * cells * work.HIST_CELL_BYTES)
    return ops, moved
