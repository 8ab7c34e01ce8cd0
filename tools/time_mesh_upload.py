"""Time how a host table reaches the chips.

Puts a ``(rows, cols)`` uint8 table (800 MB at the default, Epsilon's
400 000 x 2000) on a ``data`` mesh of every device, each way timed from
the call to ``block_until_ready``:

* ``prepare_arrays``: ``distributed.prepare_arrays`` itself, the table
  with its labels, weights, row mask and scores;
* ``direct``: ``jax.device_put(table, sharding)``, each device's block of
  rows its own transfer from host memory, issued in one batched call;
* ``threaded``: one host thread per device, each putting its block on its
  device, the blocks then assembled into one global array;
* ``staged``: ``jax.device_put(jnp.asarray(table), sharding)``, the whole
  table on device 0 first and then resharded over the mesh.

Then, for the one-chip upload, the whole table onto device 0 in one
transfer (``one_chip_whole``), a quarter of it (``one_chip_quarter``),
and the whole in ``--chunks`` row chunks put by as many threads at once
(``one_chip_chunks``).  Device memory peaks (``peak_bytes_in_use``, per
device) are read after the mesh's direct ways and again after the rest.

    python tools/time_mesh_upload.py        # on a host with four chips

Prints one JSON line and writes it to ``chiprun_out/mesh_upload.json``.
"""

import argparse
import concurrent.futures
import json
import os
import statistics
import sys
import time

import numpy as np


def _timed(put, reps):
    """Seconds from the call to ready, ``reps`` times after one warm-up."""
    import jax
    jax.block_until_ready(put())
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        arr = jax.block_until_ready(put())
        out.append(time.perf_counter() - t0)
        del arr
    return out


def _peaks():
    import jax
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
            for d in jax.devices()]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=400_000)
    ap.add_argument("--cols", type=int, default=2000)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--chunks", type=int, default=4)
    ap.add_argument("--out", default="chiprun_out/mesh_upload.json")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mmlspark_tpu.gbdt.distributed import prepare_arrays, resolve_mesh

    devs = jax.devices()
    D = len(devs)
    mesh = resolve_mesh("data")
    sh = NamedSharding(mesh, P("data", None))
    rng = np.random.default_rng(0)
    table = rng.integers(0, 256, (args.rows, args.cols), dtype=np.uint8)
    labels = rng.integers(0, 2, args.rows).astype(np.float32)
    weights = np.ones(args.rows, np.float32)
    S = -(-args.rows // D)
    blocks = [table[d * S:(d + 1) * S] for d in range(D)]
    jax.block_until_ready([jax.device_put(np.zeros(8), d) for d in devs])

    def threaded():
        with concurrent.futures.ThreadPoolExecutor(D) as ex:
            parts = list(ex.map(jax.device_put, blocks, devs))
        return jax.make_array_from_single_device_arrays(
            table.shape, sh, parts)

    def one_chip_chunks():
        rows = np.array_split(np.arange(args.rows), args.chunks)
        with concurrent.futures.ThreadPoolExecutor(args.chunks) as ex:
            return list(ex.map(
                lambda r: jax.device_put(table[r[0]:r[-1] + 1], devs[0]),
                rows))

    times = {}
    times["prepare_arrays"] = _timed(
        lambda: prepare_arrays(table, labels, weights, mesh, 1, 0.0)[:5],
        args.reps)
    times["direct"] = _timed(lambda: jax.device_put(table, sh), args.reps)
    times["threaded"] = _timed(threaded, args.reps)
    peaks_direct = _peaks()
    times["one_chip_whole"] = _timed(
        lambda: jax.device_put(table, devs[0]), args.reps)
    times["one_chip_quarter"] = _timed(
        lambda: jax.device_put(blocks[0], devs[0]), args.reps)
    times["one_chip_chunks"] = _timed(one_chip_chunks, args.reps)
    times["staged"] = _timed(
        lambda: jax.device_put(jnp.asarray(table), sh), args.reps)
    peaks_all = _peaks()

    nbytes = {"one_chip_quarter": blocks[0].nbytes}
    rec = {"device_kind": devs[0].device_kind, "devices": D,
           "rows": args.rows, "cols": args.cols, "table_bytes": table.nbytes,
           "reps": args.reps, "chunks": args.chunks,
           "peak_bytes_in_use_after_direct": peaks_direct,
           "peak_bytes_in_use_after_all": peaks_all}
    for k, v in times.items():
        med = statistics.median(v)
        rec[k] = {"s": [round(x, 5) for x in v], "median_s": round(med, 5),
                  "GB_per_s": round(nbytes.get(k, table.nbytes) / med / 1e9,
                                    3)}
    line = json.dumps(rec)
    print(line)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as fh:
        fh.write(line + "\n")


if __name__ == "__main__":
    main()
