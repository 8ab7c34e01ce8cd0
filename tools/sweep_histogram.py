"""On-device histogram-method sweep → a markdown table + the auto-method table.

Measures every histogram formulation in :mod:`mmlspark_tpu.ops.histogram`
across the row-bucket sizes the compacting grower actually issues
(2048 … 2^⌈lg n⌉), on whatever backend jax selects.

Timing is **in-program**: each method runs R times inside one compiled
``lax.scan`` and once inside another, and the per-call time is the slope
``(t_R - t_1) / (R - 1)``, so the per-launch dispatch cost, which can
exceed a sub-millisecond kernel, cancels.

Writes:

* ``--out`` (default ``chiprun_out/sweep_histogram.md``) — the
  human-readable sweep table.
* ``mmlspark_tpu/ops/_sweep_<backend>.json`` — winner per bucket size,
  consumed by ``_auto_method`` so ``hist_method="auto"`` picks from
  measured data for this backend.  ``pallas_bf16`` is reported but
  excluded from the winner table: "auto" must not silently change
  numerics (bf16 operand rounding); it stays opt-in.

Usage:  python tools/sweep_histogram.py [--features 50] [--bins 256]

--reps guidance: the measured signal is the cost of the R-1 extra
in-program reps, so it must clear the host's dispatch jitter — measure
that spread on the machine at hand first.  A per-call cost of tens of
microseconds (bucket sizes <= 16k) needs a few hundred reps to add up to
milliseconds of signal; the default R=17 suits per-call times in the
hundreds of microseconds.  Buckets whose slope still clamps to 0 are
recorded as unresolved rather than ranked.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

EXACT_METHODS = ["segment", "dot16", "onehot", "pallas"]
ALL_METHODS = EXACT_METHODS + ["pallas_bf16"]
# "native" (XLA FFI custom call) is CPU-only and auto-selected there
# without consulting the sweep table; include it explicitly with
# --methods to measure it against the XLA formulations.


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--features", type=int, default=50)
    ap.add_argument("--bins", type=int, default=256)
    ap.add_argument("--reps", type=int, default=17,
                    help="in-program repetitions for the slope measurement")
    ap.add_argument("--out", default="chiprun_out/sweep_histogram.md")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend")
    ap.add_argument("--methods", nargs="*", default=None,
                    help="subset of methods for this invocation")
    ap.add_argument("--hist-dtype", default="f32",
                    choices=("f32", "int16", "int32"),
                    help="gradient dtype for the sweep (ISSUE 17): f32 "
                         "is the normal path; int16/int32 feed grid "
                         "codes (|code| <= 127 / 32767) so every method "
                         "accumulates int32 — readings land in the same "
                         "table under 'method@dtype' keys, reported as "
                         "extra columns but never ranked into the "
                         "winner table (_sanitize_sweep refuses them)")
    ap.add_argument("--sizes", type=int, nargs="*", default=None,
                    help="subset of bucket sizes for this invocation "
                         "(results merge into the existing table, so a "
                         "long sweep can be split across runs)")
    ap.add_argument("--collectives", action="store_true",
                    help="measure the cross-shard histogram reduction "
                         "instead of the local formulations: "
                         "fused gather+hist+ring (pallas_ring) vs "
                         "fused-hist + ring_allreduce vs fused-hist + "
                         "psum, plus the voted-payload column "
                         "(voted+ring / voted+psum: reduce only the 2k "
                         "candidate slab, ISSUE 16), per bucket size, "
                         "on a data-only mesh over every visible device "
                         "(needs >= 2; same in-program R-slope "
                         "discipline)")
    ap.add_argument("--dot16", action="store_true",
                    help="time the builds of the dot16 contraction "
                         "against each other at the shapes the benchmark's "
                         "cells run (ISSUE 28): today's XLA formulation, "
                         "the Mosaic kernel that makes its one-hots in "
                         "VMEM, and two XLA reformulations with bf16 "
                         "operands from the start; writes "
                         "chiprun_out/sweep_dot16.{json,md} and leaves the "
                         "auto-method table alone.  Off the TPU it "
                         "rehearses at tiny shapes, kernel in interpret "
                         "mode")
    args = ap.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    from mmlspark_tpu.core.backend import configure_compile_cache
    from mmlspark_tpu.ops.histogram import compute_histogram

    # ~90 jitted programs per full sweep: reruns must not repay them
    configure_compile_cache()
    backend = jax.default_backend()
    if args.collectives:
        return collective_sweep(args, backend)
    if args.dot16:
        return dot16_sweep(args, backend)
    f, B, R = args.features, args.bins, args.reps
    sizes = args.sizes or [2048, 4096, 8192, 16384, 32768, 65536, 131072,
                           262144, 524288]
    rng = np.random.default_rng(0)

    sweep_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "mmlspark_tpu", "ops", f"_sweep_{backend}.json")
    state = {"backend": backend, "features": f, "num_bins": B,
             "winner_by_rows": {}, "times_us_by_rows": {}}
    try:
        with open(sweep_path) as fh:
            prev = json.load(fh)
        if prev.get("features") == f and prev.get("num_bins") == B:
            state.update(prev)
    except (OSError, ValueError):
        pass

    def flush_state():
        """Persist winners + raw times after every size: a timeout loses
        at most the in-flight point (the first run of this tool lost 50
        minutes of measurements to a buffered pipe + SIGTERM)."""
        state["device_kind"] = jax.devices()[0].device_kind
        with open(sweep_path, "w") as fh:
            json.dump(state, fh, indent=1)
        write_markdown(args.out, state, backend, f, B, R)

    # quantized sweep column (ISSUE 17): grid codes at the dtype's
    # grid width; every method then accumulates in int32
    mc = {"int16": 127, "int32": 32767}.get(args.hist_dtype, 0)
    suffix = "" if args.hist_dtype == "f32" else f"@{args.hist_dtype}"
    acc_np = np.float32 if not mc else np.int32

    def timed_per_call(method, bins, gh_stack):
        """Per-call seconds via the two-point in-program slope."""
        n = bins.shape[0]

        def make(reps):
            @jax.jit
            def run(bins, gh_stack):
                def body(acc, gh):
                    out = compute_histogram(bins, gh, B, method=method,
                                            max_code=mc)
                    return acc + out, None
                acc, _ = jax.lax.scan(
                    body, jnp.zeros((f, B, 3), acc_np),
                    gh_stack[:reps])
                return acc
            return run

        run_r, run_1 = make(R), make(1)
        out = run_r(bins, gh_stack); out.block_until_ready()
        out = run_1(bins, gh_stack); out.block_until_ready()
        # Each endpoint's min over tries estimates its dispatch-noise
        # floor; differencing the MINS (not min of differences, which
        # picks the most negative noise pair and clamps to 0) leaves the
        # in-program cost of the extra R-1 reps (see the --reps
        # guidance in the module docstring).
        best_r = best_1 = np.inf
        for _ in range(5):
            t0 = time.perf_counter()
            out = run_r(bins, gh_stack); out.block_until_ready()
            best_r = min(best_r, time.perf_counter() - t0)
            t0 = time.perf_counter()
            out = run_1(bins, gh_stack); out.block_until_ready()
            best_1 = min(best_1, time.perf_counter() - t0)
        return max((best_r - best_1) / (R - 1), 0.0)

    for n in sizes:
        bins = jnp.asarray(rng.integers(0, B, size=(n, f)), jnp.uint8)
        if mc:
            codes = rng.integers(-mc, mc + 1, size=(R, n, 2))
            gh_stack = jnp.asarray(
                np.concatenate([codes, np.ones((R, n, 1))], axis=2),
                jnp.int16 if args.hist_dtype == "int16" else jnp.int32)
        else:
            gh_stack = jnp.asarray(rng.normal(size=(R, n, 3)), jnp.float32)
        ref = None
        times = dict(state["times_us_by_rows"].get(str(n), {}))
        for m in (args.methods or ALL_METHODS):
            if mc and m == "pallas_bf16":
                continue        # bf16 operands have no quantized mode
            try:
                out = jax.jit(
                    lambda b, g, m=m: compute_histogram(b, g, B, method=m,
                                                        max_code=mc)
                )(bins, gh_stack[0])
                out.block_until_ready()
                if ref is None:
                    ref = np.asarray(out)
                else:
                    err = float(np.max(np.abs(np.asarray(out) - ref)))
                    scale = float(np.max(np.abs(ref))) or 1.0
                    assert err / scale < 2e-2, f"{m} mismatch {err}"
                times[m + suffix] = timed_per_call(m, bins, gh_stack) * 1e6
            except Exception as e:  # noqa: BLE001
                times[m + suffix] = None
                print(f"  n={n} {m}{suffix}: FAIL {type(e).__name__}: {e}",
                      file=sys.stderr)
        # A slope clamped to 0.0 means that method's measurement sat
        # below the dispatch-noise floor — it may be the FASTEST method
        # or pure noise; either way the bucket can't be ranked.  Leave
        # the bucket out of the winner table (``_auto_method`` then uses
        # the nearest larger measured bucket, or the backend default)
        # and re-measure with a larger --reps so the in-program signal
        # (R-1 extra reps) clears the noise.
        ok = {k: v for k, v in times.items()
              if v is not None and k in EXACT_METHODS}
        if ok and all(v > 0.0 for v in ok.values()):
            best = min(ok, key=ok.get)
            state["winner_by_rows"][str(n)] = best
        else:
            best = "UNRESOLVED (0-clamped slope; rerun with larger --reps)"
            state["winner_by_rows"].pop(str(n), None)
        state["times_us_by_rows"][str(n)] = times
        flush_state()
        print(f"n={n:7d} " + " ".join(
            f"{m}={times[m]:.0f}us" if times.get(m) is not None
            else f"{m}=—" for m in ALL_METHODS) + f"  -> {best}",
            flush=True)

    print(f"wrote {args.out} and {sweep_path}", flush=True)


def collective_sweep(args, backend):
    """Per-bucket A/B of the cross-shard reduction (ISSUE 10): the fused
    gather→hist→ring kernel vs the two-step fused-hist + ring vs
    fused-hist + psum, measured with the same in-program slope (the
    per-launch RPC floor cancels).  Results merge into the sweep JSON
    under ``collective_us_by_rows`` — the winner knob stays manual
    (``collective=ring`` through passThroughArgs) until an official
    bench A/B flips the default."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mmlspark_tpu.core.mesh import DATA_AXIS
    from mmlspark_tpu.ops.pallas_collectives import (
        fused_ring_applicable, fused_segment_hist_ring, ring_allreduce,
        ring_allreduce_select)
    from mmlspark_tpu.ops.pallas_histogram import histogram_pallas_fused

    D = len(jax.devices())
    if D < 2:
        sys.exit("--collectives needs >= 2 devices (chip mesh, or "
                 "XLA_FLAGS=--xla_force_host_platform_device_count=N "
                 "on CPU)")
    interpret = backend != "tpu"
    mesh = Mesh(np.asarray(jax.devices()), (DATA_AXIS,))
    f, B, R = args.features, args.bins, args.reps
    sizes = args.sizes or [2048, 4096, 8192, 16384, 32768, 65536]
    rng = np.random.default_rng(0)

    sweep_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "mmlspark_tpu", "ops", f"_sweep_{backend}.json")
    try:
        with open(sweep_path) as fh:
            state = json.load(fh)
    except (OSError, ValueError):
        state = {"backend": backend, "features": f, "num_bins": B}
    coll = dict(state.get("collective_us_by_rows") or {})

    def smap(fn, n_in):
        specs = tuple([P(DATA_AXIS, None), P(DATA_AXIS, None),
                       P(DATA_AXIS)][:n_in])
        return jax.shard_map(fn, mesh=mesh, in_specs=specs,
                             out_specs=P(DATA_AXIS, None, None),
                             check_vma=False)

    for size in sizes:
        n_local = size          # shard rows ~ bucket size
        if not fused_ring_applicable(f, n_local, B, D):
            print(f"size={size}: fused-ring VMEM gate refuses "
                  f"(f={f}, n={n_local}, D={D}); skipping", flush=True)
            continue
        binsT = jnp.asarray(
            rng.integers(0, B, size=(D * f, n_local)), jnp.int32)
        gh = jnp.asarray(rng.normal(size=(D * size, 3)), jnp.float32)
        idx = jnp.asarray(rng.integers(0, n_local, size=(D * size,)),
                          jnp.int32)
        sh = lambda a, s: jax.device_put(a, NamedSharding(mesh, s))
        binsT = sh(binsT, P(DATA_AXIS, None))
        gh = sh(gh, P(DATA_AXIS, None))
        idx = sh(idx, P(DATA_AXIS))

        variants = {
            "pallas_ring": lambda b, g, i: fused_segment_hist_ring(
                b, g, i, B, size, DATA_AXIS, D, interpret=interpret),
            "fused+ring": lambda b, g, i: ring_allreduce(
                histogram_pallas_fused(b, g, i, B, size,
                                       interpret=interpret),
                DATA_AXIS, D, interpret=interpret),
            "fused+psum": lambda b, g, i: jax.lax.psum(
                histogram_pallas_fused(b, g, i, B, size,
                                       interpret=interpret), DATA_AXIS),
        }
        # Voted-payload column (ISSUE 16): the PV-Tree candidate slab —
        # reduce only 2k columns of the fused histogram, over the
        # select-ring and over psum.  k2 is a representative 2*top_k for
        # this feature count; the point of the column is the payload
        # slope vs the dense variants above, not the exact k.
        k2 = max(2, min(f, 2 * min(20, max(1, f // 2))))
        cand = jnp.asarray(
            np.sort(rng.choice(f, size=k2, replace=False)), jnp.int32)
        variants["voted+ring"] = lambda b, g, i: ring_allreduce_select(
            histogram_pallas_fused(b, g, i, B, size,
                                   interpret=interpret),
            cand, DATA_AXIS, D, interpret=interpret)
        variants["voted+psum"] = lambda b, g, i: jax.lax.psum(
            jnp.take(histogram_pallas_fused(b, g, i, B, size,
                                            interpret=interpret),
                     cand, axis=0), DATA_AXIS)
        times = dict(coll.get(str(size), {}))
        ref = None
        for name, fn in variants.items():
            def run_r(reps, fn=fn):
                @jax.jit
                def run(b, g, i):
                    def body(acc, _):
                        return acc + smap(fn, 3)(b, g, i), None
                    acc, _ = jax.lax.scan(
                        body, jnp.zeros_like(smap(fn, 3)(b, g, i)),
                        None, length=reps)
                    return acc
                return run
            try:
                pr, p1 = run_r(R), run_r(1)
                out = p1(binsT, gh, idx)
                jax.block_until_ready(out)
                if ref is None:
                    ref = np.asarray(out)
                else:
                    want = ref
                    if name.startswith("voted"):
                        # the voted slab is the dense reference gathered
                        # at the candidate columns, per shard block
                        want = ref.reshape(D, f, B, 3)[
                            :, np.asarray(cand)].reshape(-1, B, 3)
                    err = float(np.max(np.abs(np.asarray(out) - want)))
                    scale = float(np.max(np.abs(want))) or 1.0
                    assert err / scale < 2e-2, f"{name} mismatch {err}"
                jax.block_until_ready(pr(binsT, gh, idx))
                best_r = best_1 = float("inf")
                for _ in range(5):
                    t0 = time.perf_counter()
                    jax.block_until_ready(pr(binsT, gh, idx))
                    best_r = min(best_r, time.perf_counter() - t0)
                    t0 = time.perf_counter()
                    jax.block_until_ready(p1(binsT, gh, idx))
                    best_1 = min(best_1, time.perf_counter() - t0)
                us = (best_r - best_1) / (R - 1) * 1e6
                # a slope at/below zero sat under the dispatch-noise
                # floor: record it UNRESOLVED (None), never as a 0.0
                # that a reader could rank — the exact artifact class
                # _sanitize_sweep refuses in the main table
                times[name] = us if us > 0.0 else None
            except Exception as e:  # noqa: BLE001
                times[name] = None
                print(f"  size={size} {name}: FAIL "
                      f"{type(e).__name__}: {e}", file=sys.stderr)
        coll[str(size)] = times
        state["collective_us_by_rows"] = coll
        state["collective_device_count"] = D
        with open(sweep_path, "w") as fh:
            json.dump(state, fh, indent=1)
        print(f"size={size:7d} " + " ".join(
            f"{k}={v:.0f}us" if v is not None else f"{k}=—"
            for k, v in times.items()), flush=True)
    print(f"wrote {sweep_path} (collective_us_by_rows; D={D}, "
          f"interpret={interpret})", flush=True)


#: (features, largest bucket rung, rows at the root) of the benchmark's
#: cells: epsilon_fit, bosch_fit, criteo_fit (epsilon_fit_dp4 has
#: 100 000 rows a chip and rungs to 2^17)
_DOT16_CELLS = [(2000, 1 << 19, 400_000), (968, 1 << 21, 1_183_747),
                (39, 1 << 25, 30_000_000)]


def _xla_bf16(bins, gh, num_bins, chunk=8192, per_channel=False):
    """The dot16 contraction in plain XLA with both operands bf16 from
    the start: ``rhs = where(hi_onehot, g, 0)``, never an f32 broadcast.
    ``per_channel``: three ``(c, F, 16) x (c, F, 16)`` contractions."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops.histogram import _sum_over_row_chunks
    n, f = bins.shape
    n_hi = (num_bins + 15) // 16
    lo_iota = jnp.arange(16, dtype=jnp.uint8)
    hi_iota = jnp.arange(n_hi, dtype=jnp.uint8)
    zero = jnp.zeros((), jnp.bfloat16)

    def step(acc, b, g):
        lo = ((b & 15)[:, :, None] == lo_iota).astype(jnp.bfloat16)
        hi = (b >> 4)[:, :, None] == hi_iota                  # (c, f, Hh)
        if per_channel:
            out = jnp.stack([
                jnp.einsum("cfl,cfh->fhl", lo,
                           jnp.where(hi, g[:, None, None, x], zero),
                           preferred_element_type=jnp.float32)
                for x in range(3)], axis=-1)                  # (f, Hh, 16, 3)
        else:
            rhs = jnp.where(hi[..., None], g[:, None, None, :], zero)
            out = jnp.einsum("cfl,cfhx->fhlx", lo, rhs,
                             preferred_element_type=jnp.float32)
        return acc + out.reshape(f, n_hi * 16, 3)[:, :num_bins]

    return _sum_over_row_chunks(
        step, bins, gh.astype(jnp.bfloat16), min(chunk, n),
        jnp.zeros((f, num_bins, 3), jnp.float32))


def dot16_sweep(args, backend):
    """ns per (row, feature) cell of each build of the dot16 contraction,
    at every bucket rung and at the root of the benchmark's cells.  One
    compiled program per (build, shape) runs the build R times on
    gradients that change with the repetition, so a launch's cost is
    spread over R and nothing is hoisted out of the loop."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.ops.histogram import _hist_dot16
    from mmlspark_tpu.ops.pallas_histogram import (DOT16_CHUNK,
                                                   histogram_dot16)

    B = 255
    tiny = interpret = backend != "tpu"
    cells = ([(39, 4096, 5000), (16, 2048, 3000)] if tiny
             else _DOT16_CELLS)
    budget = 2e5 if tiny else 1.2e9        # cells of work a timed call

    def mosaic(chunk):
        return lambda b, g: histogram_dot16(
            b.T, g, B, chunk=chunk, interpret=interpret)

    builds = {
        "xla": lambda b, g: _hist_dot16(b, g, B, 8192),
        "mosaic": mosaic(256 if tiny else DOT16_CHUNK),
        "xla_bf16": lambda b, g: _xla_bf16(b, g, B),
        "xla_bf16x3": lambda b, g: _xla_bf16(b, g, B, per_channel=True),
    }
    # other row chunks of the kernel, at three shapes (PR 28 also swept
    # blocks of 32 and 64 features a step, 4 and 8 folds: slower at every
    # shape, and gone from the kernel)
    variants = {f"mosaic_c{c}": mosaic(c)
                for c in ([128] if tiny else [2048, 4096, 16384])}

    def timed(fn, bins, gh, reps):
        f = bins.shape[1]

        @jax.jit
        def run(bins, gh):
            def body(i, acc):
                return acc + fn(bins, gh * (1.0 + i.astype(jnp.float32)))
            return jax.lax.fori_loop(0, reps, body,
                                     jnp.zeros((f, B, 3), jnp.float32))
        t0 = time.perf_counter()
        run(bins, gh).block_until_ready()
        first = time.perf_counter() - t0
        best = np.inf
        for _ in range(2):
            t0 = time.perf_counter()
            run(bins, gh).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best / reps, first

    rng = np.random.default_rng(0)
    doc = {"backend": backend, "device_kind": jax.devices()[0].device_kind,
           "num_bins": B, "rows": []}
    out_json = os.path.join(os.path.dirname(args.out) or ".",
                            "sweep_dot16.json")
    os.makedirs(os.path.dirname(out_json) or ".", exist_ok=True)

    def data(n, f):
        bins = jnp.asarray(rng.integers(0, B, size=(n, f), dtype=np.uint8))
        gh = np.concatenate([rng.normal(size=(n, 2)),
                             np.ones((n, 1))], axis=1).astype(np.float32)
        return bins, jnp.asarray(gh)

    def measure(name, fn, n, f, bins, gh, ref=None):
        reps = int(max(2, min(200, budget // (n * f))))
        row = {"build": name, "rows": n, "features": f, "reps": reps}
        try:
            if ref is not None:
                got = np.asarray(jax.jit(fn)(bins, gh))
                scale = float(np.max(np.abs(ref))) or 1.0
                row["max_gap_vs_xla"] = float(
                    np.max(np.abs(got - ref))) / scale
                row["counts_equal"] = bool(
                    np.array_equal(got[..., 2], ref[..., 2]))
            sec, first = timed(fn, bins, gh, reps)
            row.update(us_per_call=sec * 1e6, ns_per_cell=sec * 1e9 / (n * f),
                       first_call_s=first)
        except Exception as e:  # noqa: BLE001 - a refused build is a row
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        doc["rows"].append(row)
        with open(out_json, "w") as fh:
            json.dump(doc, fh, indent=1)
        print(json.dumps(row), flush=True)
        return row

    # 1. the kernel's row chunk, at three shapes
    for n, f in ([(2048, 16)] if tiny else
                 [(2048, 2000), (32768, 2000), (65536, 39)]):
        bins, gh = data(n, f)
        ref = np.asarray(jax.jit(builds["xla"])(bins, gh))
        for name, fn in variants.items():
            measure(name, fn, n, f, bins, gh, ref)

    # 2. every rung and the root, per cell
    for f, top, root in cells:
        rungs = []
        s = 2048
        while s <= top:
            rungs.append(s)
            s *= 2
        for n in rungs + [root]:
            bins, gh = data(n, f)
            sparse = n in (rungs[0], rungs[len(rungs) // 2], rungs[-1], root)
            ref = None
            for name in ("xla", "mosaic", "xla_bf16", "xla_bf16x3"):
                if name.startswith("xla_") and not sparse:
                    continue
                if name == "xla" and f < 100 and not sparse \
                        and rungs.index(n) % 2:
                    continue
                r = measure(name, builds[name], n, f, bins, gh,
                            ref if name == "mosaic" else None)
                if name == "xla" and "error" not in r and n <= (1 << 19):
                    ref = np.asarray(jax.jit(builds["xla"])(bins, gh))
            del bins, gh

    write_dot16_markdown(os.path.splitext(out_json)[0] + ".md", doc)
    print(f"wrote {out_json}", flush=True)


def write_dot16_markdown(path, doc):
    by = {}
    for r in doc["rows"]:
        by.setdefault((r["features"], r["rows"]), {})[r["build"]] = r
    names = sorted({r["build"] for r in doc["rows"]})
    lines = ["# dot16 builds, ns per (row, feature) cell",
             "",
             f"Backend **{doc['backend']}** ({doc['device_kind']}), "
             f"{doc['num_bins']} bins; `mosaic` is the kernel at its "
             "default row chunk, `mosaic_c<rows>` at another.",
             "",
             "| F | rows | " + " | ".join(names) + " |",
             "|---:|---:|" + "---:|" * len(names)]
    for (f, n) in sorted(by):
        cells = [f"{by[(f, n)][b]['ns_per_cell']:.3f}"
                 if "ns_per_cell" in by[(f, n)].get(b, {}) else "—"
                 for b in names]
        lines.append(f"| {f} | {n} | " + " | ".join(cells) + " |")
    with open(path, "w") as fh:
        fh.write("\n".join(lines) + "\n")


def write_markdown(out_path, state, backend, f, B, R):
    kind = state.get("device_kind")
    if not kind:
        import jax
        kind = jax.devices()[0].device_kind
    by_rows = state["times_us_by_rows"]
    # quantized-dtype columns (ISSUE 17): whatever method@int16 /
    # method@int32 readings --hist-dtype sweeps have recorded
    qcols = sorted({k for t in by_rows.values() for k in t if "@" in k})
    cols = ALL_METHODS + qcols
    lines = [
        "# Histogram-method sweep",
        "",
        f"Backend: **{backend}** ({kind}); "
        f"shapes: (n, {f}) uint8 bins, {B} bins, 3 gradient channels.  "
        f"Per-call microseconds via the in-program slope "
        f"(R={R} scan reps vs 1; each endpoint min over 5 timed runs), "
        "so per-launch dispatch cost cancels.  `method@int16`/`@int32` "
        "columns are the quantized-gradient builds (grid codes in, "
        "int32 accumulation; ISSUE 17) — informational, never ranked.",
        "",
        "| rows | " + " | ".join(cols) + " | winner (f32-exact) |",
        "|---:|" + "---:|" * (len(cols) + 1),
    ]
    for n in sorted(by_rows, key=int):
        times = by_rows[n]
        cells = [f"{times[m]:.0f}" if times.get(m) is not None else "—"
                 for m in cols]
        win = state["winner_by_rows"].get(n, "(unresolved: 0-clamped)")
        lines.append(f"| {n} | " + " | ".join(cells)
                     + f" | **{win}** |")
    lines += [
        "",
        "`compute_histogram(method='auto')` consults the per-backend winner "
        f"table (`mmlspark_tpu/ops/_sweep_{backend}.json`, written by this "
        "script) keyed by the static row count of each call site — the "
        "compacting grower's bucket branches each get the method measured "
        "fastest at that size.  Backends without a table fall back to "
        "segment (CPU) / dot16 (accelerators).  `pallas_bf16` is excluded "
        "from 'auto' (numerics) and stays opt-in.",
        "",
    ]
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as fh:
        fh.write("\n".join(lines))


if __name__ == "__main__":
    main()
