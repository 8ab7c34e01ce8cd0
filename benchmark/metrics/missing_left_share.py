"""Of the window's splits whose node held missing rows of the split
feature, the share that sent them LEFT, in percent, from the
``train.fit`` spans' ``missing_left_splits`` over ``missing_splits``.
None where the fits carry neither, or made no such split."""

from benchmark.lib import spans


def read(run):
    win = spans.window_of(run)
    if win is None:
        return None
    roots = win[0]
    held = [r["attrs"].get("missing_splits") for r in roots]
    left = [r["attrs"].get("missing_left_splits") for r in roots]
    if None in held or None in left or not sum(held):
        return None
    return 100.0 * sum(left) / sum(held)
