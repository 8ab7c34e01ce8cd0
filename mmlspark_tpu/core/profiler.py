"""Continuous performance profiler — always-on cost attribution
(ISSUE 12).

The observability stack so far (telemetry, traces, SLOs, flight
recorder) can say *that* a request or a fit was slow, but not *why*:
there was no compile/dispatch attribution, no host-path phase profile,
and no automated detection when a change regresses the committed bench
numbers.  This module is the attribution half (the regression half is
``tools/perf_sentinel.py``); three sources, all cheap enough to stay on
in production:

* **Phase attribution** — the known hot paths feed
  :meth:`Profiler.record_phase` with durations they already measured
  (the scoring engine's form/decode/score/reply, the transport's
  encode/decode/wire-write, the GBDT engine's boost-chunk host glue,
  the fleet's fan-out/wait/reduce).  Phases accumulate into one
  :class:`~mmlspark_tpu.core.profiling.StageStats` — the same
  log-bucket histograms the rest of telemetry uses, so snapshots merge
  cross-process with :func:`~mmlspark_tpu.core.telemetry.
  merge_snapshots` and ``tools/perf_report.py`` can recompute exact
  percentiles over a whole topology.
* **JAX events** — a ``jax.monitoring`` duration listener accumulates
  per-event compile counts and cumulative seconds
  (``backend_compile``, ``jaxpr_trace``, ...), and a process-monotonic
  :meth:`compile_seq` lets any dispatch site classify its own calls as
  cache HIT vs MISS without touching jit internals: read the sequence
  before and after the call — if it moved, this dispatch compiled
  (:meth:`count_dispatch` keeps the hit/miss ledger per site; the
  host-dispatch / materialization-wait split of a call is its site's
  own phases or regions).  Device/HBM watermarks are sampled from
  ``device.memory_stats()`` where the backend exposes it (TPU/GPU;
  CPU returns none): in use and reserved, now and at their peaks.
* **Sampling** — :meth:`start_sampler`, opt-in, off by default:
  collapsed thread stacks for a flamegraph.

Exposition: the ``mmlspark_tpu_profile_*`` families join every
``/metrics`` scrape through the registry's exposition-provider hook
(see docs/observability.md §Profiling); :meth:`snapshot` is the
JSON-able block embedded in flight records and bench artifacts and
consumed by ``tools/perf_report.py``.

Overhead contract: with the profiler DISABLED every hook is one
attribute check; ENABLED, a phase record is a dict lookup plus one
log-bucket histogram insert (no allocation, no syscall).  The tier-1
overhead test pins the enabled-vs-disabled p50 delta of a closed-loop
scoring burst under 3%.
"""

from __future__ import annotations

import itertools
import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from typing import Any, Dict, List, Optional, Tuple

from .profiling import LatencyStats, StageStats
from .telemetry import (PREFIX, _fmt, _labels, current_fit_span,
                        get_journal, get_registry)

__all__ = ["Profiler", "get_profiler", "install_jax_hooks",
           "PROFILER_ENV"]

#: set to ``"0"`` to disable the always-on profiler process-wide (the
#: overhead A/B in tools/perf_sentinel.py and the tier-1 overhead test
#: flip Profiler.configure instead — same switch, no env round-trip)
PROFILER_ENV = "MMLSPARK_TPU_PROFILER"

#: jax.monitoring event key substring that marks an actual backend
#: compilation (a cache MISS somewhere in the process)
_COMPILE_EVENT = "backend_compile"


def _jax_backend_initialized(jax, prof: "Profiler") -> bool:
    """True only when the process ALREADY initialized a jax backend —
    never a trigger for that initialization.  Peeks the xla_bridge
    backend cache; on API drift, falls back to evidence the process
    compiled something (the monitoring listener saw an event)."""
    try:
        from jax._src import xla_bridge
        return bool(xla_bridge._backends)
    except Exception:  # noqa: BLE001 - private API moved
        return prof._compile_seq > 0 or bool(prof._jax_events)


def _short_event(name: str) -> str:
    """``/jax/core/compile/backend_compile_duration`` →
    ``backend_compile`` — the label value the exposition carries."""
    short = name.rsplit("/", 1)[-1]
    if short.endswith("_duration"):
        short = short[: -len("_duration")]
    return short


class Profiler:
    """Process-wide performance attribution.  One instance per process
    (:func:`get_profiler`); every hook is safe from any thread."""

    #: journal profile spans only when they exceed this (keeps the
    #: bounded journal ring from flooding with per-request spans);
    #: callers may force with ``journal=True``
    SPAN_JOURNAL_MS = 50.0
    #: regions kept in memory (:meth:`spans`): a fit of one chunk records
    #: 22, so the ring holds the last 460 fits or so
    SPAN_RING = 10240
    #: newest regions carried by :meth:`snapshot`
    SPAN_SNAPSHOT_TAIL = 64

    def __init__(self, enabled: Optional[bool] = None):
        if enabled is None:
            enabled = os.environ.get(PROFILER_ENV, "1") != "0"
        self.enabled = bool(enabled)
        #: phase timers — StageStats so the snapshot merges like every
        #: other telemetry source
        self.stats = StageStats()
        self._timers: Dict[str, LatencyStats] = {}
        self._lock = threading.Lock()
        #: closed regions, oldest first (bounded; see :meth:`region`)
        self._spans: deque = deque(maxlen=self.SPAN_RING)
        self._span_ids = itertools.count(1)
        #: per-thread stack of open region ids: a region's parent is
        #: the region that encloses it on the same thread
        self._open = threading.local()
        #: jax.monitoring accumulation: short event name -> [n, total_s]
        self._jax_events: Dict[str, List[float]] = {}
        self._compile_seq = 0
        #: per-site dispatch ledger: site -> {"hits": n, "misses": n}
        self._dispatch: Dict[str, Dict[str, int]] = {}
        #: (device, kind) -> bytes, refreshed by sample_memory()
        self._mem: Dict[Tuple[str, str], float] = {}
        self._mem_t = 0.0
        # sampler state
        self._sampler_stop = threading.Event()
        self._sampler_thread: Optional[threading.Thread] = None
        self._samples = 0
        self._stacks: Dict[str, int] = {}
        self._stacks_cap = 4096

    # ---- configuration ----

    def configure(self, enabled: Optional[bool] = None) -> "Profiler":
        if enabled is not None:
            self.enabled = bool(enabled)
        return self

    # ---- phase attribution ----

    def timer(self, phase: str) -> LatencyStats:
        """Resolve the phase's histogram ONCE — per-frame/per-batch
        call sites cache the returned object and record directly
        (``if prof.enabled: t.record(dt)``), skipping the dict lookup
        and call overhead of :meth:`record_phase` on every hit."""
        t = self._timers.get(phase)
        if t is None:
            with self._lock:
                t = self._timers.setdefault(phase,
                                            self.stats.timer(phase))
        return t

    def alias(self, phase: str, timer: LatencyStats) -> None:
        """Expose an EXISTING histogram (one a hot path already
        records into — the scoring engine's stage timers, the
        transport's codec timers) under ``phase`` in the profile view.
        This is the zero-overhead attribution path: the phase shows up
        in ``mmlspark_tpu_profile_phase_seconds`` and the snapshot
        without a single extra record on the hot path.  Replaces any
        previous alias — the newest engine instance wins, matching the
        registry's namespace semantics."""
        with self._lock:
            self._timers[phase] = timer
            self.stats.adopt(phase, timer)

    def record_phase(self, phase: str, seconds: float) -> None:
        """Accumulate an already-measured duration under ``phase``.
        The hot paths call this with timings they measured anyway, so
        an enabled profiler adds one histogram insert per call and a
        disabled one adds a single attribute check."""
        if not self.enabled:
            return
        self.timer(phase).record(seconds)

    @contextmanager
    def region(self, name: str, **attrs):
        """A span around the wrapped block: one measurement, three
        faces.

        * an in-memory record ``{"id", "name", "start", "end",
          "parent", "fit", "attrs"}`` on ``time.perf_counter()``'s
          clock, appended to a bounded ring when the block ends
          (:meth:`spans`).  ``parent`` is the id of the region that
          encloses this one on the same thread (None at the top),
          ``fit`` is :func:`~mmlspark_tpu.core.telemetry.
          current_fit_span` at entry, the identifier every span of one
          fit shares.  The ``with`` target is the record's ``attrs``
          dict, so a caller adds what it learns inside the block
          (``sp["bytes"] = ...``).  A region's self time is its
          duration less the part its children cover.
        * a ``jax.profiler.TraceAnnotation(name)`` for the block's
          duration when jax is already imported (this module never
          imports it), so the same span lies in the profiler's
          ``.xplane.pb`` beside the device events, on their clock,
          whenever anyone traces; with no trace running the annotation
          is one atomic load.
        * the phase histogram, as :meth:`record_phase` feeds it, so
          ``/metrics``, ``tools/perf_report.py`` and flight records see
          the phase with no exporter of their own.

        Disabled: one attribute check, no annotation, no record."""
        if not self.enabled:
            yield attrs
            return
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        jprof = getattr(sys.modules.get("jax"), "profiler", None)
        note = (jprof.TraceAnnotation(name) if jprof is not None
                else nullcontext())
        rec = {"id": next(self._span_ids), "name": name,
               "parent": stack[-1] if stack else None,
               "fit": current_fit_span(), "attrs": attrs,
               "start": time.perf_counter(), "end": None}
        stack.append(rec["id"])
        try:
            with note:
                yield attrs
        finally:
            rec["end"] = time.perf_counter()
            stack.pop()
            with self._lock:
                self._spans.append(rec)
            self.record_phase(name, rec["end"] - rec["start"])

    #: the older name: a region is the scoped timer ``phase`` was
    phase = region

    def spans(self) -> List[dict]:
        """A copy of the ring of closed regions, oldest first."""
        with self._lock:
            return list(self._spans)

    def jax_seconds(self, event: str) -> float:
        """Cumulative seconds the ``jax.monitoring`` listener has seen
        for ``event`` (short name: ``jaxpr_trace``, ``backend_compile``);
        a region reads it before and after to learn how much of itself
        was tracing or compiling."""
        with self._lock:
            ent = self._jax_events.get(event)
            return float(ent[1]) if ent else 0.0

    def span(self, name: str, seconds: float, journal: bool = False,
             record: bool = True, **ids) -> None:
        """Record a phase AND journal a ``profile_span`` event (with
        the current fit span and any caller ids — trace ids ride
        ``tid=``) when the span is slow enough to matter or the caller
        forces it.  This is what puts per-hop costs on the
        ``tools/trace_report.py`` timelines.  ``record=False`` journals
        only — for call sites whose phase is an ALIASED timer they
        already recorded into (a second record would double-count)."""
        if not self.enabled:
            return
        if record:
            self.record_phase(name, seconds)
        dur_ms = seconds * 1e3
        if journal or dur_ms >= self.SPAN_JOURNAL_MS:
            get_journal().emit("profile_span", phase=name,
                               dur_ms=round(dur_ms, 3),
                               fit=current_fit_span(), **ids)

    # ---- JAX events ----

    def _on_jax_duration(self, name: str, secs: float, **kw) -> None:
        """jax.monitoring duration listener (installed once per
        process by :func:`install_jax_hooks`)."""
        if not self.enabled:
            return
        short = _short_event(name)
        with self._lock:
            ent = self._jax_events.setdefault(short, [0, 0.0])
            ent[0] += 1
            ent[1] += float(secs)
            if _COMPILE_EVENT in short:
                self._compile_seq += 1

    def compile_seq(self) -> int:
        """Process-monotonic compile counter: bumped once per backend
        compilation.  Bracket any jitted call with it to classify the
        dispatch as cache hit (unchanged) or miss (moved)."""
        return self._compile_seq

    def count_dispatch(self, site: str, misses: int = 0) -> None:
        """Ledger-only dispatch accounting (the cheapest hook: one
        lock).  ``misses`` is the :meth:`compile_seq` delta over the
        bracketed call — 0 means the dispatch rode the compile cache.
        ONE dispatch contributes ONE ledger entry (hit or miss), no
        matter how many backend compiles its jaxpr triggered — the raw
        compile count lives in the ``jax_events`` family.  Caveat: the
        sequence is process-global, so a dispatch whose window overlaps
        ANOTHER site's compile (e.g. a refit while serving) is
        conservatively counted as a miss for this site."""
        with self._lock:
            ent = self._dispatch.setdefault(site,
                                            {"hits": 0, "misses": 0})
            if misses > 0:
                ent["misses"] += 1
            else:
                ent["hits"] += 1

    # ---- memory watermarks ----

    def record_memory(self, device: str, kind: str,
                      nbytes: float) -> None:
        with self._lock:
            self._mem[(str(device), str(kind))] = float(nbytes)

    def sample_memory(self, min_interval_s: float = 1.0) -> None:
        """Refresh device/HBM watermarks from ``device.memory_stats()``
        where the backend exposes it.  Rate-limited; a backend without
        memory stats (CPU) contributes nothing.  Never imports jax —
        only reads it if the process already did."""
        if not self.enabled:
            return
        jax = sys.modules.get("jax")
        if jax is None or not _jax_backend_initialized(jax, self):
            # imported-but-uninitialized jax: reading local_devices()
            # would INITIALIZE the backend as a side effect of a
            # metrics scrape (multi-second stall; on a TPU box it can
            # grab the chip in a process that scores natively) — skip
            return
        now = time.monotonic()
        with self._lock:
            if now - self._mem_t < min_interval_s:
                return
            self._mem_t = now
        try:
            for d in jax.local_devices():
                stats = (d.memory_stats()
                         if hasattr(d, "memory_stats") else None)
                if not stats:
                    continue
                label = f"{d.platform}:{d.id}"
                # the TPU runtime counts the loaded programs'
                # temporaries apart, as reserved: a chip's watermark is
                # peak_bytes_in_use + peak_bytes_reserved
                for kind in ("bytes_in_use", "peak_bytes_in_use",
                             "bytes_reserved", "peak_bytes_reserved",
                             "bytes_limit"):
                    if kind in stats:
                        self.record_memory(label, kind, stats[kind])
        except Exception:  # noqa: BLE001 - a watermark read must never
            pass           # hurt the path it observes

    # ---- stack sampler (opt-in) ----

    def start_sampler(self, hz: float = 100.0,
                      thread_prefixes: Optional[Tuple[str, ...]] = None,
                      max_stacks: int = 4096,
                      duty_cap: float = 0.05) -> "Profiler":
        """Start the opt-in collapsed-stack sampler: ~``hz`` snapshots
        of every (filtered) thread's Python stack per second.
        ``thread_prefixes`` limits sampling to threads whose name
        starts with one of them (default: every thread but the sampler
        itself).  ``duty_cap`` bounds the sampler's own CPU share: if a
        snapshot costs c seconds the next sleep is at least
        ``c * (1/duty_cap - 1)``, so a slow ``sys._current_frames`` on
        a big process degrades the RATE, never the host."""
        if self._sampler_thread is not None:
            return self
        self._sampler_stop.clear()
        interval = 1.0 / max(1e-3, float(hz))
        self._stacks_cap = int(max_stacks)

        def loop():
            me = threading.get_ident()
            while not self._sampler_stop.is_set():
                t0 = time.perf_counter()
                try:
                    names = {t.ident: t.name
                             for t in threading.enumerate()}
                    for ident, frame in sys._current_frames().items():
                        if ident == me:
                            continue
                        name = names.get(ident, "?")
                        if thread_prefixes is not None and not any(
                                name.startswith(p)
                                for p in thread_prefixes):
                            continue
                        parts: List[str] = []
                        f = frame
                        depth = 0
                        while f is not None and depth < 64:
                            code = f.f_code
                            parts.append(
                                f"{os.path.basename(code.co_filename)}"
                                f":{code.co_name}")
                            f = f.f_back
                            depth += 1
                        key = name + ";" + ";".join(reversed(parts))
                        with self._lock:
                            self._samples += 1
                            if key in self._stacks or \
                                    len(self._stacks) < self._stacks_cap:
                                self._stacks[key] = \
                                    self._stacks.get(key, 0) + 1
                            else:
                                self._stacks["<overflow>"] = \
                                    self._stacks.get("<overflow>", 0) + 1
                except Exception:  # noqa: BLE001 - sampling must never
                    pass           # take the process down
                cost = time.perf_counter() - t0
                self._sampler_stop.wait(
                    max(interval - cost, cost * (1.0 / duty_cap - 1.0)))

        self._sampler_thread = threading.Thread(
            target=loop, name="profile-sampler", daemon=True)
        self._sampler_thread.start()
        return self

    def stop_sampler(self) -> None:
        self._sampler_stop.set()
        t = self._sampler_thread
        if t is not None:
            t.join(timeout=5)
        self._sampler_thread = None

    def flamegraph_lines(self, top: Optional[int] = None) -> List[str]:
        """Collapsed-stack lines (``thread;frame;...;leaf count``) in
        descending count order — feed straight to ``flamegraph.pl`` or
        speedscope."""
        with self._lock:
            items = sorted(self._stacks.items(), key=lambda kv: -kv[1])
        if top is not None:
            items = items[:top]
        return [f"{k} {v}" for k, v in items]

    # ---- snapshot / exposition ----

    def snapshot(self, top_stacks: int = 50) -> dict:
        """JSON-able profile block: phases (StageStats shape — merge
        with ``telemetry.merge_snapshots``), the compile/dispatch
        ledger, jax event accumulations, memory watermarks, the newest
        regions (:meth:`region`; their clock is this process's
        ``perf_counter``) and the sampler's top collapsed stacks.
        Embedded in flight records and bench artifacts;
        ``tools/perf_report.py`` consumes it."""
        self.sample_memory()
        with self._lock:
            jax_events = {k: {"count": int(v[0]),
                              "total_s": round(v[1], 6)}
                          for k, v in self._jax_events.items()}
            dispatch = {k: dict(v) for k, v in self._dispatch.items()}
            mem = {f"{d}/{k}": v for (d, k), v in self._mem.items()}
            samples = self._samples
            spans = list(self._spans)[-self.SPAN_SNAPSHOT_TAIL:]
        return {
            "enabled": self.enabled,
            "phases": self.stats.snapshot(),
            "jax_events": jax_events,
            "compile_seq": self._compile_seq,
            "dispatch": dispatch,
            "memory_bytes": mem,
            "spans": spans,
            "sampler": {"samples": samples,
                        "stacks": self.flamegraph_lines(top_stacks)},
        }

    def render_prometheus(self, prefix: str = PREFIX) -> str:
        """The ``mmlspark_tpu_profile_*`` families (appended to every
        registry render through ``register_exposition``)."""
        self.sample_memory()
        lines: List[str] = []

        def fam(suffix: str, typ: str, help_: str) -> str:
            name = f"{prefix}_profile_{suffix}"
            lines.append(f"# HELP {name} {help_}")
            lines.append(f"# TYPE {name} {typ}")
            return name

        n = fam("enabled", "gauge",
                "1 while the always-on profiler is recording.")
        lines.append(f"{n} {1 if self.enabled else 0}")

        snap = self.stats.snapshot()
        stages = snap.get("stages") or {}
        if stages:
            n = fam("phase_seconds", "histogram",
                    "Attributed wall time per named hot-path phase "
                    "(log-bucketed, cross-process mergeable).")
            for phase in sorted(stages):
                s = stages[phase]
                lab = {"phase": phase}
                buckets = s.get("buckets") or {}
                cum = 0
                for le, c in sorted(
                        ((le, c) for le, c in buckets.items()
                         if le != "+Inf"),
                        key=lambda kv: float(kv[0])):
                    cum += int(c)
                    lines.append(
                        f"{n}_bucket{_labels({**lab, 'le': le})} {cum}")
                lines.append(
                    f"{n}_bucket{_labels({**lab, 'le': '+Inf'})} "
                    f"{_fmt(s.get('count', 0))}")
                lines.append(
                    f"{n}_sum{_labels(lab)} "
                    f"{_fmt(s.get('total_s', 0.0))}")
                lines.append(
                    f"{n}_count{_labels(lab)} "
                    f"{_fmt(s.get('count', 0))}")

        with self._lock:
            jax_events = {k: (int(v[0]), float(v[1]))
                          for k, v in self._jax_events.items()}
            dispatch = {k: dict(v) for k, v in self._dispatch.items()}
            mem = dict(self._mem)
            samples = self._samples
        if dispatch:
            n = fam("dispatch_total", "counter",
                    "Bracketed jitted dispatches per site, split "
                    "compile-cache hit vs miss.")
            for site in sorted(dispatch):
                for outcome in ("hit", "miss"):
                    lines.append(
                        f"{n}{_labels({'site': site, 'outcome': outcome})}"
                        f" {dispatch[site].get(outcome + 's', 0)}")
        if jax_events:
            n = fam("jax_events_total", "counter",
                    "jax.monitoring event counts (backend_compile = "
                    "one real compilation).")
            for ev in sorted(jax_events):
                lines.append(f"{n}{_labels({'event': ev})} "
                             f"{jax_events[ev][0]}")
            n = fam("jax_seconds_total", "counter",
                    "Cumulative seconds per jax.monitoring event "
                    "(the compile-time ledger).")
            for ev in sorted(jax_events):
                lines.append(f"{n}{_labels({'event': ev})} "
                             f"{_fmt(round(jax_events[ev][1], 6))}")
        if mem:
            n = fam("memory_bytes", "gauge",
                    "Device memory watermarks where the backend "
                    "exposes memory_stats().")
            for (dev, kind) in sorted(mem):
                lines.append(
                    f"{n}{_labels({'device': dev, 'kind': kind})} "
                    f"{_fmt(mem[(dev, kind)])}")
        n = fam("sampler_samples_total", "counter",
                "Thread-stack samples taken by the opt-in sampler.")
        lines.append(f"{n} {samples}")
        return "\n".join(lines) + "\n"


_profiler = Profiler()
_jax_hooks_installed = threading.Event()
_jax_hooks_lock = threading.Lock()


def get_profiler() -> Profiler:
    """The process-global profiler every hot-path hook feeds.  Installs
    the jax.monitoring listener on first use if jax is already
    imported (idempotent; see :func:`install_jax_hooks`)."""
    if not _jax_hooks_installed.is_set() and "jax" in sys.modules:
        install_jax_hooks()
    return _profiler


def install_jax_hooks() -> bool:
    """Register the profiler's jax.monitoring duration listener ONCE
    per process (listeners cannot be unregistered individually, so the
    callback itself checks ``enabled``).  Returns True when installed
    (now or earlier), False when jax/monitoring is unavailable."""
    if _jax_hooks_installed.is_set():
        return True
    with _jax_hooks_lock:
        # re-check under the lock: listeners cannot be unregistered,
        # so a check-then-act race would double-count every compile
        # event for the life of the process
        if _jax_hooks_installed.is_set():
            return True
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(
                _profiler._on_jax_duration)
        except Exception:  # noqa: BLE001 - no jax / API drift:
            return False   # profiler still works, sans compile events
        _jax_hooks_installed.set()
    return True


# the profile families join every /metrics scrape (one failing provider
# is skipped by the registry, never fatal to the scrape)
get_registry().register_exposition(
    "profile", lambda: _profiler.render_prometheus())
