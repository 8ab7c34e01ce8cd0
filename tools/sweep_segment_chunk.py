"""On-device sweep of ``grower.SEGMENT_CHUNK_ROWS``: the top rung of the
ladder ``grower._segment_hist`` gathers rows on, and the chunk of its
walk over a longer segment.

For each table shape and each candidate size, one node's histogram build
is timed at several node sizes, on a segment that ascends in row id as
every leaf's does.  ``whole`` is the ladder that ends at 2^ceil(lg n),
what the grower ran before PR 34: the node gathered at the next power of
two.  Times are the host's clock around a call that ends in
``block_until_ready``, the least of ``--reps``; a node of 10^5 rows or
more takes milliseconds, a dispatch some tens of microseconds.  (PR 34
swept a chunked ``_partition_switch`` the same way and took it out: in
a tree its 2^16-element gathers ran at 18.5 ns an element.)  Prints a line a node and writes
``chiprun_out/sweep_segment_chunk.json`` (PERF.md Findings, PR 34).

Off the TPU it rehearses at tiny shapes; a rehearsal's times are not
device numbers.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: rows x table columns of the benchmark's three largest cells
SHAPES = {"criteo": (30_000_000, 39), "istella": (7_325_625, 220),
          "allstate": (13_184_290, 90)}
SHARES = (1.0, 0.7, 0.25, 1 / 16, 1 / 64, 1 / 256)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES))
    ap.add_argument("--chunks", nargs="+",
                    default=["whole", "14", "15", "16", "17", "18"],
                    help="lg of the chunk rows, or 'whole'")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--offset", type=int, default=4097,
                    help="where the segment starts in row_order (it "
                         "moves up where the node leaves no room)")
    ap.add_argument("--out-dir", default="chiprun_out")
    ap.add_argument("--aot", action="store_true",
                    help="no chip: compile every program of the sweep for "
                         "a described v5e at the real shapes and say what "
                         "each compile cost this host")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.gbdt import grower
    on_tpu = jax.default_backend() == "tpu" or args.aot
    if args.aot:
        # the build is chosen by the backend the process sees
        import mmlspark_tpu.ops.histogram as H
        H.jax.default_backend = lambda: "tpu"
    shapes = {k: SHAPES[k] for k in args.shapes} if on_tpu else \
        {"rehearsal": (20_000, 8)}
    chunks = args.chunks if on_tpu else ["whole", "10", "12"]
    cfg = grower.GrowerConfig(
        num_bins=256, hist_method="dot16" if on_tpu else "segment")
    rows_out = []
    import numpy as np
    for shape, (n, f) in shapes.items():
        # made on the host: a sort or a permutation of 3 x 10^7 rows
        # costs the TPU's compiler minutes and tens of GB
        rng = np.random.default_rng(n % 9973)
        if not args.aot:
            bins = jnp.asarray(rng.integers(0, 256, (n, f), dtype=np.uint8))
            gh = jnp.asarray(rng.standard_normal((n, 3), dtype=np.float32))
            pick = rng.random(n, dtype=np.float32)
        for lg in chunks:
            grower.SEGMENT_CHUNK_ROWS = \
                1 << 40 if lg == "whole" else 1 << int(lg)
            sizes = grower._build_sizes(n, cfg)

            # the segment's offset is traced, as in a tree: a constant
            # would let the compiler align every slice of the walk
            def hist(order, off, cnt, bins, gh):
                return grower._segment_hist(bins, gh, order, off, cnt, n,
                                            sizes, cfg)

            hist_j = jax.jit(hist)
            if args.aot:
                from jax.experimental import topologies
                from jax.sharding import SingleDeviceSharding
                one = SingleDeviceSharding(topologies.get_topology_desc(
                    topology_name="v5e:2x2", platform="tpu").devices[0])

                def sds(shape, dtype):
                    return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
                order = sds((n + sizes[-1],), jnp.int32)
                t0 = time.perf_counter()
                hist_j.lower(order, sds((), jnp.int32), sds((), jnp.int32),
                             sds((n, f), jnp.uint8),
                             sds((n, 3), jnp.float32)).compile()
                import resource
                print(shape, lg, "compiled for the v5e in",
                      round(time.perf_counter() - t0, 1), "s, max rss",
                      resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                      // 1000, "MB", flush=True)
                continue
            t0 = time.perf_counter()
            first = True
            for share in SHARES:
                # an ascending subset of the rows, as a leaf's segment is
                rows = np.flatnonzero(pick < share).astype(np.int32)
                cnt = len(rows)
                lead = min(args.offset, n - cnt)
                order = jnp.asarray(np.concatenate([
                    np.full(lead, n, np.int32), rows,
                    np.full(n - cnt - lead + sizes[-1], n, np.int32)]))
                c, o = jnp.int32(cnt), jnp.int32(lead)
                jax.block_until_ready(hist_j(order, o, c, bins, gh))
                if first:
                    compile_s, first = time.perf_counter() - t0, False
                t_hist = []
                for _ in range(args.reps):
                    t = time.perf_counter()
                    jax.block_until_ready(hist_j(order, o, c, bins, gh))
                    t_hist.append(time.perf_counter() - t)
                walked = int(grower._walked_rows(cnt, sizes))
                rows_out.append({
                    "shape": shape, "rows": n, "columns": f, "chunk": lg,
                    "rungs": len(sizes), "compile_s": round(compile_s, 1),
                    "node_rows": cnt, "offset": lead, "walked_rows": walked,
                    "build_ms": min(t_hist) * 1e3,
                    "build_ns_per_row": min(t_hist) * 1e9 / cnt})
                print(json.dumps(rows_out[-1]), flush=True)
                del order
    if args.aot:
        return
    os.makedirs(args.out_dir, exist_ok=True)
    out = {"device": jax.devices()[0].device_kind,
           "backend": jax.default_backend(), "rows": rows_out}
    with open(os.path.join(args.out_dir, "sweep_segment_chunk.json"),
              "w") as fh:
        json.dump(out, fh, indent=1)
    if not on_tpu:
        print("rehearsal: not device numbers", file=sys.stderr)


if __name__ == "__main__":
    main()
