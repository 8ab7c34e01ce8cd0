"""In-program micro-bench: leaf-segment gather strategies on the accelerator.

The DataPartition grower's per-split hot path is ``take(bins, rows)`` of the
smaller child's rows followed by a histogram (PERF.md round-3 headroom: the
gather's ~26 ns/row was comparable to the dot16 histogram itself).  This tool
measures, at the grower's real bucket sizes, the in-program per-call cost of:

* ``gather_u8``    — take of (size, f) uint8 rows (the shipped path)
* ``gather_pk``    — take of (size, ceil(f/4)) int32 rows with 4 bins packed
                     per word, plus the shift/mask unpack to (size, f)
* ``hist_dot16``   — the histogram alone on pre-gathered rows (baseline)
* ``fused_u8``     — gather_u8 + dot16 (what one ladder branch costs today)
* ``fused_pk``     — packed gather + unpack + dot16 (the candidate)

Timing is the two-point in-program slope with min-per-endpoint (same
methodology as tools/sweep_histogram.py; see its --reps guidance).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=400_000)
    ap.add_argument("--features", type=int, default=50)
    ap.add_argument("--bins", type=int, default=256)
    ap.add_argument("--reps", type=int, default=257)
    ap.add_argument("--sizes", type=int, nargs="*",
                    default=[2048, 4096, 8192, 16384, 32768])
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--out", default="artifacts/bench_gather.json")
    args = ap.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    from mmlspark_tpu.core.backend import configure_compile_cache
    from mmlspark_tpu.ops.histogram import compute_histogram

    configure_compile_cache()

    n, f, B, R = args.rows, args.features, args.bins, args.reps
    f4 = (f + 3) // 4
    rng = np.random.default_rng(0)
    bins_np = rng.integers(0, B, size=(n, f)).astype(np.uint8)
    pk_np = np.zeros((n, f4 * 4), np.uint8)
    pk_np[:, :f] = bins_np
    pk_np = pk_np.reshape(n, f4, 4)
    packed_np = (pk_np[..., 0].astype(np.uint32)
                 | (pk_np[..., 1].astype(np.uint32) << 8)
                 | (pk_np[..., 2].astype(np.uint32) << 16)
                 | (pk_np[..., 3].astype(np.uint32) << 24)).astype(np.int32)

    bins_d = jnp.asarray(bins_np)
    binsT_d = jnp.asarray(bins_np.T)     # fit-invariant, like the scan's
    packed_d = jnp.asarray(packed_np)
    gh_d = jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)
    interp = jax.default_backend() == "cpu"

    def unpack(pk):                      # (s, f4) int32 -> (s, f) int32
        u = pk.astype(jnp.uint32)
        parts = jnp.stack([(u >> (8 * k)) & 0xFF for k in range(4)], -1)
        return parts.reshape(pk.shape[0], f4 * 4)[:, :f].astype(jnp.int32)

    def make_variants(size):
        idx0 = jnp.asarray(
            rng.permutation(n)[:size].astype(np.int32))

        def gather_u8(r):
            return jnp.take(bins_d, r, axis=0).astype(jnp.int32).sum()

        def gather_pk(r):
            return unpack(jnp.take(packed_d, r, axis=0)).sum()

        def hist_only(r):
            # pre-gathered contiguous rows: dynamic_slice, no gather.
            # The offset must depend on the rotated index vector or XLA
            # hoists the whole histogram out of the rep loop (LICM) and
            # the slope measures nothing.
            off = jnp.abs(r[0]) % jnp.int32(max(n - size, 1))
            sub = jax.lax.dynamic_slice(bins_d, (off, 0), (size, f))
            gh = jax.lax.dynamic_slice(gh_d, (off, 0), (size, 3))
            return compute_histogram(sub, gh, B, method="dot16").sum()

        def fused_u8(r):
            sub = jnp.take(bins_d, r, axis=0)
            gh = jnp.take(gh_d, r, axis=0)
            return compute_histogram(sub, gh, B, method="dot16").sum()

        def fused_pk(r):
            sub = unpack(jnp.take(packed_d, r, axis=0))
            gh = jnp.take(gh_d, r, axis=0)
            return compute_histogram(sub, gh, B, method="dot16").sum()

        def pallas_fused(r):
            # r5: the in-kernel VMEM gather (ops/pallas_histogram.py
            # histogram_pallas_fused) — gather + histogram in ONE kernel
            from mmlspark_tpu.ops.pallas_histogram import (
                histogram_pallas_fused)
            gh = jnp.take(gh_d, r, axis=0)
            return histogram_pallas_fused(binsT_d, gh, r, B, size,
                                          interpret=interp).sum()

        variants = {"gather_u8": gather_u8, "gather_pk": gather_pk,
                    "hist_dot16": hist_only, "fused_u8": fused_u8,
                    "fused_pk": fused_pk}
        if B <= 256:
            variants["pallas_fused"] = pallas_fused
        return idx0, variants

    def slope(fn, idx0, reps):
        def make(reps):
            @jax.jit
            def run(idx0):
                def body(acc, k):
                    # rotate indices so XLA can't CSE the gather across reps
                    out = fn(jnp.roll(idx0, k))
                    return acc + out, None
                acc, _ = jax.lax.scan(body, jnp.float32(0),
                                      jnp.arange(reps))
                return acc
            return run
        run_r, run_1 = make(reps), make(1)
        run_r(idx0).block_until_ready()
        run_1(idx0).block_until_ready()
        br = b1 = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            run_r(idx0).block_until_ready()
            br = min(br, time.perf_counter() - t0)
            t0 = time.perf_counter()
            run_1(idx0).block_until_ready()
            b1 = min(b1, time.perf_counter() - t0)
        return max((br - b1) / (reps - 1), 0.0)

    out = {"backend": jax.default_backend(), "rows": n, "features": f,
           "reps": R, "per_call_us": {}}
    for size in args.sizes:
        idx0, variants = make_variants(size)
        row = {}
        for name, fn in variants.items():
            t = slope(fn, idx0, R) * 1e6
            row[name] = round(t, 2)
        out["per_call_us"][str(size)] = row
        print(f"size={size:7d} " + "  ".join(
            f"{k}={v:.0f}us" for k, v in row.items()), flush=True)
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=1)
    print(f"wrote {args.out}", flush=True)


if __name__ == "__main__":
    main()
