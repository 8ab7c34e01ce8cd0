"""Device-level tracing — the framework's profiling subsystem.

The reference's observability story is the Spark UI plus the ``Timer``
pipeline stage (SURVEY.md §5.1); the TPU-native equivalent is a
``jax.profiler`` trace (Perfetto/TensorBoard-readable, captures every XLA
op with device timestamps).  This module makes that a first-class,
in-package capability rather than a side tool:

* :func:`trace` — context manager; wrap any region to capture a device
  trace into a directory.
* :func:`summarize_trace` — parse the written ``.xplane.pb`` (no
  TensorBoard needed) into device self time by the program's named
  scopes; ``tools/profile_boost_step.py`` prints it.
* :func:`idle_by_span` — the same trace's idle device seconds, each
  charged to the ``Profiler.region`` the host had open then.
* :func:`compiled_instructions` / :func:`compiled_copies` — the
  instructions of a compiled program over a byte threshold (its ``copy``
  instructions), read from its text: what a loop's carry costs when it
  is not updated in place, which temporaries a kernel's formulation
  makes, known before anything runs.
* ``LightGBMBase.setProfileTraceDir(dir)`` — traces the whole ``fit``
  (engine hooks through :func:`maybe_trace`).

Serving adds a second, host-side need: per-stage wall-clock counters for
the scoring hot path (queue wait / decode / score / reply), cheap enough
to stay on in production.  :class:`LatencyStats` is a thread-safe
streaming accumulator over a FIXED log-bucketed histogram (ISSUE 8):
counts per logarithmic latency bucket instead of the old 4096-sample
ring, so two workers' snapshots MERGE exactly (bucket counts sum;
percentiles recompute from the summed buckets) — averaging or
max-ing per-worker p99s, the only option a sample ring allowed, is not
a percentile of the combined population.  :class:`StageStats` groups
named stages plus a rows counter so ``ScoringEngine.stats()`` can
report rows/s and p50/p99 without a profiler attached.
"""

from __future__ import annotations

import glob
import math
import os
import re
import threading
import time
from bisect import bisect_left
from collections import defaultdict
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

# -- log-bucket ladder -------------------------------------------------------

#: multiplicative bucket growth: 2**0.25 bounds the relative error of a
#: bucket-midpoint percentile estimate to ~±9% — tight enough for an SLO
#: readout, coarse enough that a stage's occupied buckets stay few
HIST_GROWTH = 2.0 ** 0.25
#: lowest bucket upper bound (10 µs); the top finite bound is
#: ``HIST_GROWTH**(HIST_BUCKETS-1)`` above it (~300 s) — everything
#: slower lands in the +Inf overflow bucket
HIST_FLOOR = 1e-5
HIST_BUCKETS = 100

#: upper (``le``) bounds of the finite buckets, ascending
BUCKET_BOUNDS: Tuple[float, ...] = tuple(
    HIST_FLOOR * HIST_GROWTH ** i for i in range(HIST_BUCKETS))
#: stable string keys for the bucket bounds — the wire/snapshot
#: representation (identical across processes because the ladder is a
#: module constant, never computed from data)
LE_STRS: Tuple[str, ...] = tuple(
    format(b, ".6g") for b in BUCKET_BOUNDS) + ("+Inf",)
_LE_INDEX = {s: i for i, s in enumerate(LE_STRS)}


def bucket_index(seconds: float) -> int:
    """Index into ``LE_STRS`` of the bucket holding ``seconds`` (the
    first bound >= the value; the last index is the +Inf overflow)."""
    return bisect_left(BUCKET_BOUNDS, seconds)


def _bucket_mid(i: int) -> float:
    """Representative value (geometric midpoint) for bucket ``i`` —
    the percentile estimate returned for ranks landing in it."""
    if i >= HIST_BUCKETS:                       # +Inf overflow
        return BUCKET_BOUNDS[-1] * math.sqrt(HIST_GROWTH)
    return BUCKET_BOUNDS[i] / math.sqrt(HIST_GROWTH)


def percentile_from_buckets(buckets: Dict[str, int], q: float) -> float:
    """q-th percentile (0-100), in seconds, of a sparse ``{le: count}``
    bucket dict (the ``snapshot()["buckets"]`` shape).  Deterministic in
    the bucket counts alone, so summing two sources' buckets and calling
    this is EXACTLY the percentile of the combined population at the
    ladder's resolution — the property ``merge_snapshots`` relies on."""
    total = 0
    per_idx: List[Tuple[int, int]] = []
    for le, c in buckets.items():
        i = _LE_INDEX.get(le)
        if i is None or not c:
            continue
        per_idx.append((i, int(c)))
        total += int(c)
    if total <= 0:
        return 0.0
    rank = max(1, math.ceil(q / 100.0 * total))
    cum = 0
    for i, c in sorted(per_idx):
        cum += c
        if cum >= rank:
            return _bucket_mid(i)
    return _bucket_mid(per_idx[-1][0] if per_idx else 0)


class LatencyStats:
    """Thread-safe streaming latency accumulator over the fixed
    log-bucket ladder.

    Keeps exact count/total plus one integer per occupied bucket —
    O(1) per record, bounded memory, and (unlike the sample ring it
    replaced) MERGEABLE: ``snapshot()["buckets"]`` from any number of
    workers can be key-wise summed and the percentiles recomputed
    exactly for the combined population.

    Two views coexist: the CUMULATIVE buckets (the exposition's
    ``_bucket`` rows and the merge representation — Prometheus
    consumers ``rate()`` them for any window they like), and a
    RECENT-WINDOW pair of bucket epochs rotated every
    ``window_s`` seconds that the ``p50_ms``/``p99_ms`` snapshot keys
    are estimated from — a latency SLO watches *current* tail latency,
    and a lifetime-cumulative estimate would dilute a regression under
    millions of historical fast samples (the property the old sample
    ring had, kept).  ``capacity`` is accepted and ignored for
    backward compatibility with the ring-buffer signature.
    """

    #: half-window for the recent-percentile epochs: estimates span
    #: the last 1-2 windows' samples
    WINDOW_S = 60.0

    __slots__ = ("_lock", "_count", "_total", "_buckets", "_recent",
                 "_prev", "_epoch_t")

    def __init__(self, capacity: int = 4096):
        del capacity                    # ring-era knob, no longer used
        self._lock = threading.Lock()
        self._count = 0
        self._total = 0.0
        self._buckets = [0] * len(LE_STRS)
        self._recent = [0] * len(LE_STRS)
        self._prev = [0] * len(LE_STRS)
        self._epoch_t = time.monotonic()

    def _roll_locked(self) -> None:
        elapsed = time.monotonic() - self._epoch_t
        if elapsed < self.WINDOW_S:
            return
        if elapsed >= 2 * self.WINDOW_S:
            # a traffic gap longer than the whole window: BOTH epochs
            # are stale — shifting would present the pre-gap epoch as
            # "recent" for another window
            self._prev = [0] * len(LE_STRS)
        else:
            self._prev = self._recent
        self._recent = [0] * len(LE_STRS)
        self._epoch_t = time.monotonic()

    def record(self, seconds: float) -> None:
        i = bucket_index(seconds)
        with self._lock:
            self._roll_locked()
            self._count += 1
            self._total += seconds
            self._buckets[i] += 1
            self._recent[i] += 1

    @property
    def count(self) -> int:
        return self._count

    def _window_counts_locked(self):
        """Recent-window bucket counts (last 1-2 epochs), falling back
        to the cumulative buckets when the window is empty (e.g. right
        after a rotation with no fresh traffic) so percentiles degrade
        to the lifetime estimate instead of reading 0."""
        self._roll_locked()
        window = [a + b for a, b in zip(self._recent, self._prev)]
        return window if any(window) else list(self._buckets)

    def percentile(self, q: float) -> float:
        """q-th percentile (0-100) over the recent window, in seconds
        (bucket-midpoint estimate, ~±9% relative; same estimator as
        ``snapshot()`` — both delegate to
        :func:`percentile_from_buckets`)."""
        with self._lock:
            counts = self._window_counts_locked()
        return percentile_from_buckets(
            {LE_STRS[i]: c for i, c in enumerate(counts) if c}, q)

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            count, total = self._count, self._total
            counts = list(self._buckets)
            window = self._window_counts_locked()
        sparse = {LE_STRS[i]: c for i, c in enumerate(counts) if c}
        wsparse = {LE_STRS[i]: c for i, c in enumerate(window) if c}
        return {
            "count": count,
            "total_s": round(total, 6),
            "mean_ms": round(total / count * 1e3, 4) if count else 0.0,
            "p50_ms": round(
                percentile_from_buckets(wsparse, 50) * 1e3, 4),
            "p99_ms": round(
                percentile_from_buckets(wsparse, 99) * 1e3, 4),
            "buckets": sparse,
        }


class StageStats:
    """Named :class:`LatencyStats` per pipeline stage + a rows counter.

    The scoring engine instruments every hop (queue wait, decode, score,
    reply, end-to-end) through one of these; ``snapshot()`` is the
    JSON-able stats surface ``ScoringEngine.stats()`` exposes and
    ``tools/bench_serving.py`` records into its artifact.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._stages: Dict[str, LatencyStats] = {}
        self._counters: Dict[str, int] = {}
        self._gauges: Dict[str, float] = {}
        self._rows = 0
        self._t_first: Optional[float] = None
        self._t_last = 0.0

    def timer(self, stage: str) -> LatencyStats:
        with self._lock:
            stats = self._stages.get(stage)
            if stats is None:
                stats = self._stages[stage] = LatencyStats()
            return stats

    def adopt(self, stage: str, stats: LatencyStats) -> None:
        """Expose an EXISTING :class:`LatencyStats` under ``stage`` —
        the histogram object is SHARED, not copied, so records made by
        its original owner show up here with zero extra hot-path work
        (the profiler's alias mechanism, ISSUE 12).  Replaces any
        previous timer of that name."""
        with self._lock:
            self._stages[stage] = stats

    @contextmanager
    def time(self, stage: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.timer(stage).record(time.perf_counter() - t0)

    def incr(self, name: str, n: int = 1) -> None:
        """Bump a named event counter (``n=0`` pre-registers the name so
        a snapshot shows an explicit zero instead of a missing key —
        the resilience counters ``shed``/``expired``/``salvaged``/
        ``restarted`` are seeded this way by the scoring engine, so
        "no degradation happened" is observable, not ambiguous)."""
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def counter(self, name: str) -> int:
        with self._lock:
            return self._counters.get(name, 0)

    def set_gauge(self, name: str, value: float) -> None:
        """Record a point-in-time level (last-write-wins) — e.g. the
        elastic watchdog's worst peer heartbeat age, where "how stale
        NOW" matters and a count or latency distribution would not."""
        with self._lock:
            self._gauges[name] = float(value)

    def gauge(self, name: str, default: float = 0.0) -> float:
        with self._lock:
            return self._gauges.get(name, default)

    def add_rows(self, n: int) -> None:
        now = time.perf_counter()
        with self._lock:
            if self._t_first is None:
                self._t_first = now
            self._t_last = now
            self._rows += n

    @property
    def rows(self) -> int:
        return self._rows

    def _rows_per_s_locked(self) -> float:
        if self._t_first is None or self._t_last <= self._t_first:
            return 0.0
        return self._rows / (self._t_last - self._t_first)

    def rows_per_s(self) -> float:
        with self._lock:
            return self._rows_per_s_locked()

    def snapshot(self) -> Dict[str, object]:
        # one lock acquisition for the WHOLE top-level read: reading
        # self._rows and calling rows_per_s() after release could pair a
        # newer row count with an older window (or vice versa), so a
        # concurrent add_rows() made rows and rows_per_s mutually
        # inconsistent in one snapshot
        with self._lock:
            stages = dict(self._stages)
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            rows = self._rows
            rows_per_s = self._rows_per_s_locked()
        return {
            "rows": rows,
            "rows_per_s": round(rows_per_s, 2),
            "counters": counters,
            "gauges": gauges,
            "stages": {name: s.snapshot() for name, s in stages.items()},
        }


@contextmanager
def trace(out_dir: str):
    """Capture a ``jax.profiler`` trace of the wrapped region."""
    import jax
    os.makedirs(out_dir, exist_ok=True)
    with jax.profiler.trace(out_dir):
        yield


@contextmanager
def maybe_trace(out_dir: Optional[str]):
    """:func:`trace` when ``out_dir`` is set; no-op otherwise (the shape
    engine code wants: one `with` either way)."""
    if not out_dir:
        yield
        return
    with trace(out_dir):
        yield


#: components of an op's ``op_name`` path that jax's transforms and
#: control flow put there; what is left is what ``jax.named_scope`` named
_STRUCTURAL = re.compile(
    r"^(?:\w+\(.*\)|while|body|cond|scan|closed_call|core_call|"
    r"shard_map|branch_\d+_fun|.*<locals>.*|.*->.*)$")
#: the stat of an "XLA Ops" event's METADATA that carries the op's
#: ``op_name`` path on the TPU (jax 0.9 / libtpu 0.0.34; PERF.md §3)
_SCOPE_STAT = "tf_op"


def scope_of(op_name: str) -> str:
    """The named scopes of an ``op_name`` path, outermost first:
    ``jit(f)/while/body/closed_call/root_hist/reduce/psum:`` →
    ``root_hist/reduce``; an op under no scope is named by its program
    (``jit(f)``)."""
    parts = op_name.rstrip(":").split("/")
    named = [p for p in parts[:-1] if not _STRUCTURAL.match(p)]
    return "/".join(named) if named else parts[0]


def _varint(buf, i):
    """``(value, next index)`` of the protobuf varint at ``buf[i]``."""
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf):
    """``(field, value)`` of one protobuf message: varints as ints,
    everything else as memoryviews (not copied, so skipping a plane's
    lines costs nothing)."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield field, val
            continue
        if wire == 2:
            size, i = _varint(buf, i)
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield field, buf[i:i + size]
        i += size


def _op_name_paths(path: str) -> Dict[str, Dict[str, str]]:
    """``{plane: {event name: op_name path}}``: the ``tf_op`` stat that
    an xplane keeps on its EVENT METADATA.  ``jax.profiler.ProfileData``
    hands out an event's own stats only, and the TPU's "XLA Ops" events
    carry the path on the metadata, so this reads the XSpace wire format
    directly (XPlane: name=2, event_metadata=4, stat_metadata=5;
    XEventMetadata: name=2, stats=5; XStat: metadata_id=1, str_value=5,
    ref_value=7), skipping every line."""
    with open(path, "rb") as fh:
        space = fh.read()
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(space):
        if f != 1:
            continue
        name, stat_names, events = "", {}, []
        for pf, val in _fields(plane):
            if pf == 2:
                name = bytes(val).decode()
            elif pf in (4, 5):      # map entries: key=1, value=2
                entry = dict(_fields(val))
                if 2 not in entry:
                    continue
                if pf == 4:
                    events.append(entry[2])
                else:
                    stat_names[entry.get(1)] = bytes(
                        dict(_fields(entry[2])).get(2, b"")).decode()
        wanted = {k for k, v in stat_names.items() if v == _SCOPE_STAT}
        found = {}
        for event in events:
            ev_name, value = "", None
            for ef, ev in _fields(event):
                if ef == 2:
                    ev_name = bytes(ev).decode()
                elif ef == 5:
                    st = dict(_fields(ev))
                    if st.get(1) in wanted:
                        value = (bytes(st[5]).decode() if 5 in st
                                 else stat_names.get(st.get(7)))
            if value:
                found[ev_name] = value
        if found:
            out[name] = found
    return out


def _self_times(events) -> Dict[str, float]:
    """Seconds by name over ``(name, start, duration)`` events of one
    line, each event's time less the time of the events nested inside it
    (a ``while`` spans its body's ops)."""
    stack: List[list] = []      # [name, end, self]
    totals: Dict[str, float] = defaultdict(float)

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            totals[name] += max(own, 0.0)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return totals


def _newest_xplane(out_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``out_dir``, by mtime: the
    profiler names exports by timestamp strings whose lexicographic
    order diverges from chronology across hosts/sessions (and a re-run
    into the same dir must win)."""
    paths = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    return max(paths, key=lambda p: (os.path.getmtime(p), p))


def summarize_trace(out_dir: str, top: int = 25
                    ) -> List[Tuple[float, str]]:
    """Device self time by named scope, from the newest ``.xplane.pb``
    under ``out_dir``.  Returns ``[(total_ms, name), ...]`` sorted
    descending, with one trailing ``(total_device_ms,
    "total_device_ms")`` summary row (the device-busy time summed over
    the trace's devices); empty when no trace file exists.

    Events come from ``jax.profiler.ProfileData``: the "XLA Ops" line of
    every ``/device:`` plane.  An event's time is its SELF time (a
    ``while`` and its body are not counted twice), and its name is
    :func:`scope_of` its ``op_name`` path, which the program's
    ``jax.named_scope`` calls feed: ``root_hist``, ``partition``,
    ``root_hist/reduce``...  An op with no path (the ``while`` itself, a
    parameter copy) keeps its HLO name without the number.  A trace with
    no device plane (the CPU backend) is summarized from the host
    threads' HLO-op events, by op."""
    newest = _newest_xplane(out_dir)
    if newest is None:
        return []
    from jax.profiler import ProfileData
    data = ProfileData.from_file(newest)
    op_names = _op_name_paths(newest)
    device_lines, host_lines = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            scopes = op_names.get(plane.name, {})
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                device_lines.append([
                    (scope_of(scopes[e.name]) if e.name in scopes
                     else _hlo_base(e.name),
                     e.start_ns / 1e9, e.duration_ns / 1e9)
                    for e in line.events])
        elif not device_lines:
            for line in plane.lines:
                host_lines.append([
                    (_hlo_base(e.name), e.start_ns / 1e9,
                     e.duration_ns / 1e9) for e in line.events
                    if any(k == "hlo_op" for k, _ in e.stats)])
    agg: Dict[str, float] = defaultdict(float)
    for events in device_lines or host_lines:
        for name, secs in _self_times(events).items():
            agg[name] += secs
    rows = sorted(((s * 1e3, name) for name, s in agg.items()),
                  reverse=True)
    total_ms = round(sum(ms for ms, _ in rows), 3)
    return rows[:top] + [(total_ms, "total_device_ms")]


#: the root of a fit's regions (``gbdt/engine.train``), and where an
#: idle stretch goes that lies between two of them
FIT_SPAN = "train.fit"
BETWEEN_FITS = "between fits"
#: names of the profiler's regions among a trace's host annotations
_REGION_PREFIXES = ("train.", "bin.")


def _busy_union(intervals) -> List[List[float]]:
    """Sorted, disjoint ``[start, end]`` covering the same points.  A
    trace's line comes in start order and is merged as it streams (a
    plane may hold millions of events); what comes out of order is
    sorted in at the end."""
    out: List[List[float]] = []
    late = []
    for a, b in intervals:
        if b <= a:
            continue
        if out and a < out[-1][0]:
            late.append((a, b))
        elif out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    if not late:
        return out
    return _busy_union(sorted(late + [tuple(i) for i in out]))


def charge_idle(events, annotations) -> List[Tuple[float, str]]:
    """Idle seconds of one device by the host span open at the time, on
    plain lists (``tests/test_fit_spans.py`` checks it on a hand-made
    one): ``events`` the device's ``(start, end)`` op intervals,
    ``annotations`` the host's ``(name, start, end)`` regions, same
    clock.  From the first :data:`FIT_SPAN`'s start to the last one's
    end, every stretch in which no event runs is cut at the regions'
    boundaries and each piece charged to the innermost region open then
    (the latest to start; the fit itself where no child is open,
    :data:`BETWEEN_FITS` outside any).  ``[(seconds, name), ...]``,
    largest first; empty without a fit."""
    fits = [a for a in annotations if a[0] == FIT_SPAN]
    if not fits:
        return []
    lo = min(a[1] for a in fits)
    hi = max(a[2] for a in fits)
    # the host's timeline as disjoint (start, end, innermost name)
    edges = sorted({lo, hi, *(t for a in annotations for t in a[1:]
                              if lo < t < hi)})
    opened = sorted((a for a in annotations if a[2] > lo and a[1] < hi),
                    key=lambda a: a[1])
    timeline, stack, nxt = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while nxt < len(opened) and opened[nxt][1] <= a:
            stack.append(opened[nxt])
            nxt += 1
        stack = [r for r in stack if r[2] > a]
        timeline.append((a, b, stack[-1][0] if stack else BETWEEN_FITS))
    # the device's busy union, walked once beside the timeline
    busy = _busy_union(e for e in events if e[1] > lo and e[0] < hi)
    totals: Dict[str, float] = defaultdict(float)
    k = 0
    for a, b, name in timeline:
        idle = b - a
        while k < len(busy) and busy[k][1] <= a:
            k += 1
        j = k
        while j < len(busy) and busy[j][0] < b:
            idle -= min(busy[j][1], b) - max(busy[j][0], a)
            j += 1
        totals[name] += idle
    return sorted(((secs, name) for name, secs in totals.items()
                   if secs > 0), reverse=True)


def idle_by_span(out_dir: str) -> List[Tuple[float, str]]:
    """Where the device waited for the host, by the program's own spans,
    from the newest ``.xplane.pb`` under ``out_dir``: the host planes'
    ``TraceAnnotation`` events that are the profiler's regions
    (``Profiler.region``: ``train.*``, ``bin.*``) and the "XLA Ops"
    events of the busiest device (the CPU backend has no device plane:
    its host threads' HLO-op events stand in), reduced by
    :func:`charge_idle`.  ``[(idle_ms, span name), ...]`` largest first,
    with one trailing ``(total_idle_ms, "total_idle_ms")`` row: the fits'
    seconds (and what lies between them) less that device's busy union.
    Empty when there is no trace or no ``train.fit`` in it."""
    newest = _newest_xplane(out_dir)
    if newest is None:
        return []
    from jax.profiler import ProfileData
    devices: Dict[str, list] = {}
    host_ops, annotations = [], []
    planes = list(ProfileData.from_file(newest).planes)
    on_host = not any(p.name.startswith("/device:") for p in planes)
    for plane in planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = _busy_union(
                        (e.start_ns / 1e9,
                         (e.start_ns + e.duration_ns) / 1e9)
                        for e in line.events)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(_REGION_PREFIXES):
                    annotations.append((e.name, e.start_ns / 1e9,
                                        (e.start_ns + e.duration_ns) / 1e9))
                elif on_host and e.duration_ns > 0 and any(
                        k == "hlo_op" for k, _ in e.stats):
                    host_ops.append((e.start_ns / 1e9,
                                     (e.start_ns + e.duration_ns) / 1e9))
    # the busiest device: a plane that came back short is not it
    busiest = host_ops if on_host else max(
        devices.values(), key=lambda u: sum(b - a for a, b in u),
        default=[])
    rows = [(secs * 1e3, name)
            for secs, name in charge_idle(busiest, annotations)]
    if not rows:
        return []
    return rows + [(round(sum(ms for ms, _ in rows), 3), "total_idle_ms")]


def trace_tables(out_dir: str) -> str:
    """:func:`summarize_trace` and :func:`idle_by_span` of the newest
    trace under ``out_dir`` as text, one ``milliseconds  name`` row a
    line: what ``LightGBMBase._fit`` logs where ``profileTraceDir`` is
    set."""
    out = []
    for title, rows in (
            ("device self time by named scope", summarize_trace(out_dir)),
            ("idle device time by the host's span", idle_by_span(out_dir))):
        out.append(f"{title} (ms):")
        out.extend(f"  {ms:12.3f}  {name[:100]}" for ms, name in rows)
    return "\n".join(out)


def _hlo_base(name: str) -> str:
    """``%copy.470 = f32[...] copy(...)`` → ``copy``: an HLO op's name
    without its number, for ops that no scope names."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(?:\.\d+|\.clone)+$", "", head) or head


#: bytes per element of the HLO element types an instruction may carry
_HLO_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                 "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
                 "s32": 4, "u32": 4, "f32": 4,
                 "s64": 8, "u64": 8, "f64": 8}
#: ``%copy.470 = f32[255,2000,256,3]{3,2,1,0:T(8,128)} copy(...)``: name,
#: element type, dimensions, opcode.  An asynchronous ``copy-start``
#: yields a tuple whose first element is the destination, so the first
#: shape after ``=`` is the one that is written
_HLO_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\(?(\w+)\[([\d,]*)\][^=]*?"
    r"\s([a-z][\w\-]*)\(")


def compiled_instructions(compiled, min_bytes: int = 0,
                          opcodes: Optional[Tuple[str, ...]] = None
                          ) -> List[Tuple[str, Tuple[int, ...], int]]:
    """The instructions of a compiled program whose result holds at least
    ``min_bytes``: ``[(name, shape, bytes), ...]``, largest first;
    ``opcodes`` keeps only those HLO opcodes.

    ``compiled`` is what ``jax.jit(f).lower(...).compile()`` returns (or
    its ``as_text()``), for the backend that will run it.  Fused
    computations are scanned too, so an array that lives only inside a
    fusion (never in HBM) is listed like one that is written out: ask
    ``memory_analysis()`` for what the program holds, and this for what
    it computes (PERF.md Findings, PR 28: an ``f32[8192,2000,16,3]``
    broadcast per chunk of the histogram build).  Parameters and
    ``get-tuple-element`` name an array, they do not make one, and are
    left out."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    out = []
    for line in text.splitlines():
        m = _HLO_LINE.match(line)
        if m is None or m.group(2) not in _HLO_ITEMSIZE:
            continue
        op = m.group(4)
        if op in ("parameter", "get-tuple-element") or (
                opcodes is not None and op not in opcodes):
            continue
        shape = tuple(int(d) for d in m.group(3).split(",") if d)
        nbytes = math.prod(shape) * _HLO_ITEMSIZE[m.group(2)]
        if nbytes >= min_bytes:
            out.append((m.group(1), shape, nbytes))
    return sorted(out, key=lambda r: -r[2])


def compiled_copies(compiled, min_bytes: int = 0
                    ) -> List[Tuple[str, Tuple[int, ...], int]]:
    """The ``copy`` instructions of a compiled program that move at least
    ``min_bytes`` (:func:`compiled_instructions`): a loop's carry that
    XLA cannot update in place shows here as a copy of the carry's shape
    in the loop's body, before anything runs (PERF.md Findings, PR 26:
    two ``f32[255,2000,256,3]`` copies a split were 23% of a fit).  The
    CPU's compiler places copies differently; ask the compiler of the
    device you mean (``jax.experimental.topologies`` describes a TPU that
    is not attached)."""
    return compiled_instructions(compiled, min_bytes,
                                 opcodes=("copy", "copy-start"))
