"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell comes from data: ``BENCHMARK.json`` names the
cell's configuration, traffic mix and chips; ``configs/<config>.json`` is
the configuration as run; ``traffic/<traffic>.json`` names its driver in
``drivers/``; ``limits/<workload>.json`` holds the limits of the
comparison that decides ``correct``; and each per-layer metric is read by
``metrics/<name>.py``.  Nothing here knows a cell, a configuration or a
metric by name (benchmark/README.md).

A run needs a TPU with at least the cell's chips and exits 2 without one.
``--rehearse`` is the explicit dry run at the configuration's tiny
``rehearsal`` size on whatever backend jax has: it names that backend,
prints its line to standard error only, and exits 3, so that it can never
be read as a measurement.
"""

import time

T_START = time.perf_counter()

import argparse          # noqa: E402
import contextlib        # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import types             # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
REHEARSAL_EXIT = 3
NO_CHIP_EXIT = 2


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


class Context:
    """What a driver is given, and where it leaves spans and counters."""

    def __init__(self, cell, config, traffic, seed, rehearse, tracing):
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.chips = int(cell["chips"])
        self.seed = int(seed)
        self.rehearse = rehearse
        self.tracing = tracing
        self.counters = {}
        self.spans = []          # (name, start_s, end_s) since T_START

    @contextlib.contextmanager
    def span(self, name):
        """A host span on the run's own clock and, in a traced run, in the
        profiler's trace (``bench:<name>``)."""
        note = contextlib.nullcontext()
        if self.tracing:
            import jax
            note = jax.profiler.TraceAnnotation("bench:" + name)
        t0 = time.perf_counter()
        try:
            with note:
                yield
        finally:
            self.spans.append((name, t0 - T_START,
                               time.perf_counter() - T_START))


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(bench_json, workload):
    """``(bench, cell, config, traffic)`` for one workload: everything
    about a cell is found by the names in ``BENCHMARK.json``."""
    bench = load_json(bench_json)
    cell = find(bench["workloads"], workload, "workload")
    config = load_json(ROOT, find(bench["configs"], cell["config"],
                                  "configuration")["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return bench, cell, config, traffic


def load_limits(workload):
    return load_json(HERE, "limits", workload + ".json")["limits"]


def metric_applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def memory_peak():
    """Peak bytes of device memory on the fullest chip: what the allocator
    has handed out at its peak (``peak_bytes_in_use``: arrays) plus what
    the runtime has reserved at its peak for loaded programs' temporaries
    (``peak_bytes_reserved``), which the TPU runtime counts apart and keeps
    while the program stays loaded."""
    import jax
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else 0


def compare(numbers, limits):
    """``({name: {"value": v, "limit": l}}, correct)``: every number the
    limits name has to be there and at or under its limit."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        compared[name] = {"value": value, "limit": limit}
        if value is None or not value <= limit:
            ok = False
    return compared, ok


def parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--bench-json", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="another BENCHMARK.json, to rehearse a cell that "
                         "is not in the accepted one yet")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny dry run on any backend; exits 3, no result "
                         "on standard output")
    return ap.parse_args(argv)


def execute(args, have_chip=None):
    """One run.  Returns ``(exit_code, result)``; the result is None where
    the run may print none.  ``have_chip`` (tests only) replaces the look
    for a TPU."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    bench, cell, config, traffic = load_cell(args.bench_json, args.workload)
    limits = load_limits(cell["name"])
    seconds = float(bench["run_seconds"] if args.seconds is None
                    else args.seconds)

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"[bench] {cell['name']} seed {args.seed} on {device}"
        + (" REHEARSAL, not a measurement" if args.rehearse else ""))
    if have_chip is None:
        have_chip = args.rehearse or (
            device["platform"] == "tpu"
            and device["count"] >= int(cell["chips"]))
    if not have_chip:
        log(f"[bench] needs {cell['chips']} TPU chip(s); found {device}")
        return NO_CHIP_EXIT, None
    peaks = load_json(HERE, "peaks.json")
    if device["kind"] not in peaks and not args.rehearse:
        log(f"[bench] no peaks for device kind {device['kind']!r} in "
            "benchmark/peaks.json")
        return NO_CHIP_EXIT, None
    try:
        from mmlspark_tpu.core.backend import configure_compile_cache
    except ImportError as e:
        log(f"[bench] the system under test is not in this checkout: {e}")
        return NO_CHIP_EXIT, None
    log(f"[bench] compile cache: {configure_compile_cache()}")

    driver = importlib.import_module("benchmark.drivers." + traffic["driver"])
    ctx = Context(cell, config, traffic, args.seed, args.rehearse,
                  bool(args.trace))
    state = driver.setup(ctx)

    trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - T_START
    with ctx.span("window"):
        work = driver.window(ctx, state, seconds)
    if args.trace:
        jax.profiler.stop_trace()
    device["memory_peak_bytes"] = memory_peak()
    log(f"[bench] set-up {setup_s:.1f} s, window {work['window_s']:.1f} s, "
        f"work {work}")

    metrics = {"setup_s": setup_s}
    metrics.update(driver.end_to_end(ctx, state, work))
    metrics["peak_hbm_bytes"] = device["memory_peak_bytes"]

    driver.release(ctx, state)
    t0 = time.perf_counter()
    numbers = driver.check(ctx, state)
    compared, correct = compare(numbers, limits)
    log(f"[bench] reference took {time.perf_counter() - t0:.1f} s; "
        f"also read: " + json.dumps(
            {k: v for k, v in numbers.items() if k not in limits},
            default=str))

    result = {"correct": correct, "attempted": work["attempted"],
              "failed": work["failed"]}
    wanted = bench["per_layer"] if args.trace else bench["end_to_end"]
    if args.trace:
        from benchmark.lib import trace as trace_lib
        loaded = trace_lib.load(trace_dir, allow_host_ops=args.rehearse)
        summary = trace_lib.reduce(*loaded)
        shutil.rmtree(trace_dir, ignore_errors=True)
        run = types.SimpleNamespace(
            trace=summary, counters=ctx.counters, spans=ctx.spans,
            work=work, state=state, chips=ctx.chips, cell=cell,
            config=config, traffic=traffic, device=device,
            peak=peaks.get(device["kind"]) or (
                next(iter(peaks.values())) if args.rehearse else None),
            end_to_end=metrics)
        metrics = {}
        for m in wanted:
            if not metric_applies(m, cell["name"]):
                continue
            reader = importlib.import_module("benchmark.metrics." + m["name"])
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = value
        if summary is not None:
            device["busy_s"] = summary["busy_mean_s"]
            device["window_s"] = summary["window_s"]
            result["breakdown"] = {"device_ops": summary["device_ops"],
                                   "idle_gaps": summary["idle_gaps"]}
            log(f"[bench] trace: {summary['events']} device events, "
                f"busy {summary['busy_s']}")
    result["metrics"] = {
        m["name"]: {"value": float(metrics[m["name"]]), "unit": m["unit"]}
        for m in wanted
        if m["name"] in metrics and metric_applies(m, cell["name"])}
    result["device"] = device
    result["info"] = {"seconds": seconds, "fits": work.get("fits"),
                      "trees": work.get("trees"),
                      "window_s": work["window_s"]}
    result["compared"] = compared

    for name, c in compared.items():
        log(f"[compared] {name} {c['value']} limit {c['limit']}")
    log(f"[compared] correct {correct}")
    return (REHEARSAL_EXIT if args.rehearse else 0), result


def main(argv=None):
    args = parse(argv)
    code, result = execute(args)
    if result is None:
        return code
    line = json.dumps(result)
    if args.rehearse:
        log("[bench] REHEARSAL on " + result["device"]["platform"]
            + ", not a measurement: " + line)
    else:
        print(line, flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
