"""Bundle columns of the table the window's fits ran on, from the
``train.fit`` spans' ``efb_bundles`` (the same in every fit)."""

from benchmark.lib import spans


def same_in_every_fit(run, key):
    """An attribute every root of the window carries with one value, or
    None (no such span, a root without it, two values)."""
    win = spans.window_of(run)
    if win is None:
        return None
    values = {r["attrs"].get(key) for r in win[0]}
    return values.pop() if len(values) == 1 and None not in values else None


def read(run):
    return same_in_every_fit(run, "efb_bundles")
