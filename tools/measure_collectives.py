"""Measure the scaling-model collectives on the 8-virtual-device host mesh.

docs/scaling.md predicts bytes-per-split for each mesh layout; this tool
MEASURES the same collectives (VERDICT r4 next #9) two ways:

* **bytes on the wire** — read from the compiled HLO's all-reduce /
  all-gather operands, so the table's `bytes per split` column is checked
  against what XLA actually schedules, not just arithmetic;
* **wall time per collective** — the in-program slope method from
  tools/sweep_histogram.py ((t(R reps) − t(1 rep)) / (R−1), min over
  repeated endpoints) so dispatch overhead cancels.

Host-mesh caveat, stated on every row: the 8 "devices" are CPU threads
sharing one memory system — collectives are memcpy-speed, so wall times
validate SCALING (payload-linearity, layout ratios), not ICI latency.
Run with:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python tools/measure_collectives.py
"""

import json
import os
import sys
import time

# the 8-device CPU host mesh, set before any backend initializes
import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

if jax.default_backend() != "cpu":
    sys.exit("measure_collectives must run on the CPU host mesh")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from mmlspark_tpu.core.mesh import DATA_AXIS, FEATURE_AXIS  # noqa: E402

B, K3 = 256, 3
D = 8
OUT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "artifacts", "collectives_hostmesh.json")


def slope_us(fn, arg, reps=17, runs=3):
    """In-program per-op cost: scan the op R times vs once, diff mins."""
    p1 = jax.jit(lambda a: jax.lax.scan(
        lambda c, _: (fn(c), None), a, None, length=1)[0])
    pR = jax.jit(lambda a: jax.lax.scan(
        lambda c, _: (fn(c), None), a, None, length=reps)[0])
    jax.block_until_ready(p1(arg))
    jax.block_until_ready(pR(arg))
    t1 = _time(p1, arg, runs)
    tR = _time(pR, arg, runs)
    return max(tR - t1, 0.0) / (reps - 1) * 1e6


def _time(p, arg, runs):
    best = np.inf
    for _ in range(runs):
        t0 = time.perf_counter()
        jax.block_until_ready(p(arg))
        best = min(best, time.perf_counter() - t0)
    return best


def hlo_allreduce_bytes(fn, arg):
    """Sum of all-reduce/all-gather RESULT bytes in the compiled HLO.

    Line-based: only instructions whose opcode (right of `=`) is a
    collective count, and only their result shape — matching the free
    `all-reduce` substring anywhere would also hit the instruction NAME
    and double-count every collective."""
    import re
    txt = jax.jit(fn).lower(arg).compile().as_text()
    total = 0
    for line in txt.splitlines():
        if "=" not in line:
            continue
        rhs = line.split("=", 1)[1].lstrip()
        m = re.match(r"f32\[([\d,]*)\][^ ]* (all-reduce|all-gather)\(",
                     rhs)
        if not m:
            continue
        n = 1
        for d in m.group(1).split(","):
            if d:
                n *= int(d)
        total += 4 * n
    return total


def main():
    devs = np.asarray(jax.devices()[:D])
    mesh = Mesh(devs.reshape(D, 1), (DATA_AXIS, FEATURE_AXIS))
    rows = []

    for f, label in ((39, "Criteo-shape f=39"), (4096, "wide f=4096")):
        hist = jax.device_put(
            jnp.ones((D, f, B, K3), jnp.float32),
            NamedSharding(mesh, P(DATA_AXIS)))

        def psum_hist(h):
            # carry-type-preserving for lax.scan: every shard keeps the
            # reduced block at its own slot (out spec = in spec)
            return jax.shard_map(
                lambda x: jax.lax.psum(x, DATA_AXIS),
                mesh=mesh, in_specs=P(DATA_AXIS),
                out_specs=P(DATA_AXIS), check_vma=False)(h)

        us = slope_us(psum_hist, hist)
        measured_b = hlo_allreduce_bytes(psum_hist, hist)
        rows.append({"layout": "data", "shape": label,
                     "predicted_bytes": 12 * f * B,
                     "hlo_allreduce_bytes": measured_b,
                     "wall_us_per_split": round(us, 1)})

    # voting: psum of <= 2k candidate histograms only
    k = 20
    cand = jax.device_put(jnp.ones((D, 2 * k, B, K3), jnp.float32),
                          NamedSharding(mesh, P(DATA_AXIS)))

    def psum_vote(h):
        return jax.shard_map(lambda x: jax.lax.psum(x, DATA_AXIS),
                             mesh=mesh, in_specs=P(DATA_AXIS),
                             out_specs=P(DATA_AXIS), check_vma=False)(h)

    rows.append({"layout": "voting k=20", "shape": "any f",
                 "predicted_bytes": 12 * 2 * k * B,
                 "hlo_allreduce_bytes": hlo_allreduce_bytes(psum_vote, cand),
                 "wall_us_per_split": round(slope_us(psum_vote, cand), 1)})

    # feature layout: owner broadcasts ONE split column of n rows (psum
    # of a one-hot-owner column == the owner-broadcast the grower uses)
    n = 400_000
    col = jax.device_put(jnp.ones((D, n // D), jnp.float32),
                         NamedSharding(mesh, P(DATA_AXIS)))

    def bcast_col(c):
        # gather the full column, keep the local slice (type-preserving)
        def body(x):
            g = jax.lax.all_gather(x, DATA_AXIS, tiled=True)
            i = jax.lax.axis_index(DATA_AXIS)
            return jax.lax.dynamic_slice_in_dim(
                g, i * x.shape[0], x.shape[0])
        return jax.shard_map(body, mesh=mesh, in_specs=P(DATA_AXIS),
                             out_specs=P(DATA_AXIS), check_vma=False)(c)

    rows.append({"layout": "feature (column broadcast)", "shape": "n=400k",
                 "predicted_bytes": 4 * n,
                 "hlo_allreduce_bytes": hlo_allreduce_bytes(bcast_col, col),
                 "wall_us_per_split": round(slope_us(bcast_col, col), 1)})

    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as fh:
        json.dump({"device_count": D, "backend": jax.default_backend(),
                   "rows": rows}, fh, indent=1)
    for r in rows:
        print(f"{r['layout']:28s} {r['shape']:18s} "
              f"predicted {r['predicted_bytes']:>10,d} B  "
              f"HLO {r['hlo_allreduce_bytes']:>10,d} B  "
              f"{r['wall_us_per_split']:>8.1f} us/split")
    print(f"wrote {OUT}")


if __name__ == "__main__":
    main()
