"""On-device micro-benchmarks of the histogram layer, in-program.

Every timing is taken **inside one compiled program** that repeats the
operation, so the per-launch dispatch cost, which can exceed a
sub-millisecond kernel, is spread over the repetitions or cancels.  Two
modes, each writing under ``chiprun_out/``:

``--dot16``
    ns per (row, feature) cell of each build of the dot16 contraction at
    every bucket rung and root of the benchmark's cells (PERF.md
    Findings, PR 28): ``sweep_dot16.{json,md}``.

``--collectives``
    The cross-shard reduction of one histogram, ``lax.psum`` against the
    Pallas ring (``ops/pallas_collectives.py``), dense and voted, on a
    data-only mesh over every visible device: ``sweep_collectives.json``.

Off the TPU both rehearse at tiny shapes with the kernels in interpret
mode; a rehearsal's times are not device numbers.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--dot16", action="store_true",
                      help="time the builds of the dot16 contraction "
                           "against each other at the shapes the "
                           "benchmark's cells run: today's XLA formulation, "
                           "the Mosaic kernel that makes its one-hots in "
                           "VMEM, and two XLA reformulations with bf16 "
                           "operands from the start")
    mode.add_argument("--collectives", action="store_true",
                      help="time lax.psum against the ring kernels on a "
                           "(features, bins, 3) float32 histogram per "
                           "device and on its voted (2k, bins, 3) slab "
                           "(needs >= 2 devices)")
    ap.add_argument("--features", type=int, nargs="+",
                    default=[2000, 1365, 50],
                    help="--collectives: histogram widths to reduce "
                         "(2000: epsilon_fit_dp4's 6.1 MB payload; 1365: "
                         "the widest the dense ring's 4 MB gate admits)")
    ap.add_argument("--bins", type=int, default=256)
    ap.add_argument("--reps", type=int, default=65,
                    help="--collectives: in-program repetitions")
    ap.add_argument("--out-dir", default="chiprun_out")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend")
    args = ap.parse_args()

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    from mmlspark_tpu.core.backend import configure_compile_cache

    configure_compile_cache()
    backend = jax.default_backend()
    if args.collectives:
        return collective_sweep(args, backend)
    return dot16_sweep(args, backend)


def collective_sweep(args, backend):
    """``lax.psum`` against the ring kernels on what a mesh fit reduces at
    every split: the dense ``(f, B, 3)`` float32 histogram, and the
    PV-Tree slab of ``2k`` voted columns (k = 20) gathered from it.  Each
    variant runs as a chain ``x <- reduce(x) / D`` of R steps inside one
    program and of one step inside another; the per-call time is the
    slope ``(t_R - t_1) / (R - 1)``.  A ring whose VMEM gate refuses the
    payload is recorded as refused: the fit would take psum there."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mmlspark_tpu.core.mesh import DATA_AXIS
    from mmlspark_tpu.ops.pallas_collectives import (ring_allreduce,
                                                     ring_allreduce_select)

    D = len(jax.devices())
    if D < 2:
        sys.exit("--collectives needs >= 2 devices (chip mesh, or "
                 "XLA_FLAGS=--xla_force_host_platform_device_count=N "
                 "on CPU)")
    interpret = backend != "tpu"
    widths = [16] if interpret else args.features
    mesh = Mesh(np.asarray(jax.devices()), (DATA_AXIS,))
    B, R = args.bins, args.reps
    spec = P(DATA_AXIS, None, None)
    rng = np.random.default_rng(0)
    doc = {"backend": backend, "device_kind": jax.devices()[0].device_kind,
           "devices": D, "num_bins": B, "reps": R, "rows": []}
    out_json = os.path.join(args.out_dir, "sweep_collectives.json")
    os.makedirs(args.out_dir, exist_ok=True)

    def chain(fn, reps):
        """R dependent reductions of a shard's block inside one program."""
        def steps(x):
            return jax.lax.fori_loop(
                0, reps, lambda _, c: fn(c) * (1.0 / D), x)
        return jax.jit(jax.shard_map(steps, mesh=mesh, in_specs=spec,
                                     out_specs=spec, check_vma=False))

    def best_of(prog, x, tries=5):
        jax.block_until_ready(prog(x))
        best = np.inf
        for _ in range(tries):
            t0 = time.perf_counter()
            jax.block_until_ready(prog(x))
            best = min(best, time.perf_counter() - t0)
        return best

    for f in widths:
        k2 = min(40, f)
        cand = jnp.asarray(np.sort(rng.choice(f, size=k2, replace=False)),
                           jnp.int32)
        x = jax.device_put(
            jnp.asarray(rng.normal(size=(D * f, B, 3)), jnp.float32),
            NamedSharding(mesh, spec))

        def voted(reduce_slab):
            # the slab goes back into its columns, so the chain's carry
            # keeps the histogram's shape
            return lambda h: h.at[cand].set(reduce_slab(h))

        variants = {
            "psum": lambda h: jax.lax.psum(h, DATA_AXIS),
            "ring": lambda h: ring_allreduce(h, DATA_AXIS, D,
                                             interpret=interpret),
            "voted+psum": voted(lambda h: jax.lax.psum(
                jnp.take(h, cand, axis=0), DATA_AXIS)),
            "voted+ring": voted(lambda h: ring_allreduce_select(
                h, cand, DATA_AXIS, D, interpret=interpret)),
        }
        want = {}
        for name, fn in variants.items():
            slab = name.startswith("voted")
            row = {"variant": name, "features": f,
                   "payload_bytes": (k2 if slab else f) * B * 3 * 4}
            try:
                once = chain(fn, 1)
                one = np.asarray(once(x))
                ref = want.setdefault(slab, one)
                row["max_gap_vs_psum"] = float(np.max(np.abs(one - ref)))
                t1, tr = best_of(once, x), best_of(chain(fn, R), x)
                us = (tr - t1) / (R - 1) * 1e6
                # a slope at or under zero sat below the noise: unresolved
                row["us_per_call"] = us if us > 0.0 else None
            except ValueError as e:     # the ring's VMEM gate
                row["refused"] = str(e)[:200]
            doc["rows"].append(row)
            with open(out_json, "w") as fh:
                json.dump(doc, fh, indent=1)
            print(json.dumps(row), flush=True)
    print(f"wrote {out_json} (D={D}, interpret={interpret})", flush=True)


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
    out_json = os.path.join(args.out_dir, "sweep_dot16.json")
    os.makedirs(args.out_dir, exist_ok=True)

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


if __name__ == "__main__":
    main()
