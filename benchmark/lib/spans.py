"""The program's own spans, reduced to per-tree numbers.

``mmlspark_tpu.core.profiler.Profiler.region`` keeps every phase of a fit
in memory as ``{"id", "name", "start", "end", "parent", "fit", "attrs"}``
on the host's monotonic clock, whether or not anyone traces; the driver
hands the profiler over as ``run.state["profiler"]``.  A fit's root is
its ``train.fit`` span; the window's fits are the last
``run.work["fits"]`` roots (the warm-up fit came before them).

Every function returns ``None`` where there is nothing to read: a
program without ``Profiler.spans`` (the parent of the PR that added
it), a window with no fit, a phase the fits never entered.  The
arithmetic is on plain lists and dicts
(benchmark/tests/test_span_readers.py checks it on a hand-made list).
"""

from benchmark.lib.trace import merge

ROOT = "train.fit"


def program_spans(run):
    """The profiler's closed spans, oldest first, or None."""
    spans = getattr(run.state.get("profiler"), "spans", None)
    return spans() if callable(spans) else None


def window_fits(spans, fits):
    """``(roots, inside)``: the last ``fits`` root spans, and all their
    descendants; None where the list holds fewer roots than that."""
    roots = [s for s in spans if s["name"] == ROOT]
    if not fits or len(roots) < fits:
        return None
    roots = roots[-fits:]
    by_parent = {}
    for s in spans:
        by_parent.setdefault(s["parent"], []).append(s)
    inside, todo = [], list(roots)
    while todo:
        children = by_parent.get(todo.pop()["id"], [])
        inside.extend(children)
        todo.extend(children)
    return roots, inside


def window_of(run):
    """``(roots, inside, trees)`` for a run, or None."""
    spans = program_spans(run)
    trees = run.work.get("trees")
    if spans is None or not trees:
        return None
    found = window_fits(spans, run.work.get("fits"))
    if found is None:
        return None
    return found[0], found[1], trees


def phase_ms_per_tree(run, names):
    """Milliseconds per tree inside the window's fits under the spans
    called ``names``; None where the fits hold no such span."""
    win = window_of(run)
    if win is None:
        return None
    _, inside, trees = win
    took = [s["end"] - s["start"] for s in inside if s["name"] in names]
    if not took:
        return None
    return sum(took) * 1e3 / trees


def self_seconds(span, spans):
    """``span``'s duration less the part its direct children cover."""
    lo, hi = span["start"], span["end"]
    covered = merge([max(s["start"], lo), min(s["end"], hi)]
                    for s in spans if s["parent"] == span["id"])
    return (hi - lo) - sum(b - a for a, b in covered)


def unattributed_ms_per_tree(run):
    """What no phase names: the roots' self time, plus the window's
    seconds outside any root (the driver's loop between two fits)."""
    win = window_of(run)
    if win is None:
        return None
    roots, inside, trees = win
    own = sum(self_seconds(r, inside) for r in roots)
    between = run.work["window_s"] - sum(r["end"] - r["start"] for r in roots)
    return (own + max(between, 0.0)) * 1e3 / trees


def root_attr_per_tree(run, key):
    """A counter the roots carry as an attribute, summed over the
    window's fits, per tree; None where a root lacks it."""
    win = window_of(run)
    if win is None:
        return None
    roots, _, trees = win
    values = [r["attrs"].get(key) for r in roots]
    if any(v is None for v in values):
        return None
    return sum(values) / trees
