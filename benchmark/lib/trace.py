"""From a profiler trace to numbers: device-busy union, idle gaps by host
span, device time by operation.

The arithmetic works on plain lists, so that it can be checked against a
hand-made event list (benchmark/tests/test_trace_reduction.py) and every
later PR computes the per-layer numbers the same way:

* an *event* is ``(name, start_s, duration_s)``, on one device;
* a *span* is ``(name, start_s, end_s)``, on the host, same clock.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` wrote into those
lists with nothing but jax.
"""

import glob
import os
import re

#: lines of a TPU device plane that hold one event per executed HLO op;
#: the other lines ("Steps", "XLA Modules", ...) span whole programs and
#: would count a program's idle time between its ops as busy
OP_LINES = ("XLA Ops",)
SPAN_PREFIX = "bench:"


def merge(intervals):
    """Sorted, disjoint ``[start, end]`` list covering the same points."""
    out = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def busy_seconds(events, window):
    """Seconds of ``window = (lo, hi)`` in which some event ran."""
    lo, hi = window
    return sum(b - a for a, b in merge(
        clip([(s, s + d) for _, s, d in events], lo, hi)))


def self_times(events):
    """Device seconds by event name, each event's time less the time of
    the events nested inside it (a ``while`` spans its body's ops), sorted
    descending."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    stack = []      # [name, end, self]
    totals = {}

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            totals[name] = totals.get(name, 0.0) + max(own, 0.0)

    for name, start, dur in order:
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return sorted(totals.items(), key=lambda kv: -kv[1])


def idle_gaps(events, window, spans):
    """Idle stretches of the window, longest first, each named by the host
    span that holds its middle and the events on either side:
    ``[("fit0: <window start> .. copy.1", seconds), ...]``."""
    lo, hi = window
    named = sorted(((s, s + d, n) for n, s, d in events
                    if s + d > lo and s < hi), key=lambda e: e[0])
    gaps = []
    edge, before = lo, "<window start>"
    for a, b, name in named:
        if a > edge:
            gaps.append((edge, a, before, name))
        if b > edge:
            edge, before = b, name
    if hi > edge:
        gaps.append((edge, hi, before, "<window end>"))
    out = []
    for a, b, left, right in gaps:
        mid = (a + b) / 2
        # innermost span holding the gap's middle: the latest to start
        inside = [sp for sp in spans if sp[1] <= mid < sp[2]]
        host = max(inside, key=lambda sp: sp[1])[0] if inside else "no span"
        out.append((f"{host}: {short(left)} .. {short(right)}", b - a))
    return sorted(out, key=lambda g: -g[1])


def short(name, limit=60):
    """An HLO op's name and shape without its operands:
    ``%copy.470 = f32[255,2000]{1,0} copy(...)`` -> ``copy.470 f32[255,2000]``.
    """
    m = re.match(r"%?([\w.\-]+) = ([\w\[\],() ]+?)(\{|\s\w+\()", name)
    text = f"{m.group(1)} {m.group(2).strip()}" if m else name
    return text[:limit]


# ---------------------------------------------------------------- loader


def newest_xplane(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load(trace_dir, allow_host_ops=False):
    """``({device_name: [event, ...]}, [span, ...])`` from the newest
    trace under ``trace_dir``, times in seconds from the trace's start.

    ``allow_host_ops`` (the CPU rehearsal only): where there is no TPU
    plane, the CPU client's HLO-op events stand in as device "cpu:0".
    """
    from jax.profiler import ProfileData

    path = newest_xplane(trace_dir)
    if path is None:
        return {}, []
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    host_ops = []
    for plane in data.planes:
        is_device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if is_device:
                if line.name not in OP_LINES:
                    continue
                devices.setdefault(plane.name, []).extend(
                    (e.name, e.start_ns / 1e9, e.duration_ns / 1e9)
                    for e in line.events)
                continue
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.name[len(SPAN_PREFIX):],
                                  e.start_ns / 1e9,
                                  (e.start_ns + e.duration_ns) / 1e9))
                elif allow_host_ops and e.duration_ns > 0 \
                        and any(k == "hlo_op" for k, _ in e.stats):
                    host_ops.append((e.name, e.start_ns / 1e9,
                                     e.duration_ns / 1e9))
    if not devices and allow_host_ops and host_ops:
        devices["cpu:0"] = host_ops
    return devices, spans


def reduce(devices, spans, window_span="window", top=10):
    """The summary the per-layer readers and ``breakdown`` use.

    The window is the host span named ``window_span``; without it, the
    extent of the device events.  Per device: busy seconds inside the
    window.  ``busiest`` names the device with most; ``device_ops`` and
    ``idle_gaps`` are that device's."""
    if not devices:
        return None
    win = [sp for sp in spans if sp[0] == window_span]
    if win:
        window = (win[0][1], win[0][2])
    else:
        window = (min(s for ev in devices.values() for _, s, _ in ev),
                  max(s + d for ev in devices.values() for _, s, d in ev))
    busy = {name: busy_seconds(ev, window) for name, ev in devices.items()}
    busiest = max(busy, key=busy.get)
    inside = [e for e in devices[busiest]
              if e[1] + e[2] > window[0] and e[1] < window[1]]
    return {
        "window_s": window[1] - window[0],
        "busy_s": busy,
        "busy_mean_s": sum(busy.values()) / len(busy),
        "busiest": busiest,
        "busiest_busy_s": busy[busiest],
        "device_ops": [[short(n), s] for n, s in self_times(inside)[:top]],
        "idle_gaps": [[n, s] for n, s in idle_gaps(
            inside, window, [sp for sp in spans if sp[0] != window_span]
        )[:top]],
        "events": sum(len(ev) for ev in devices.values()),
    }
