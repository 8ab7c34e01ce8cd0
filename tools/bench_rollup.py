"""Host timing of the reference profile's rollup
(``core/sketch.build_reference_profile`` with the fine counts given: the
span ``train.refprofile_rollup``) at the benchmark cells' feature counts
and ladder shapes, against the per-feature loop it replaced (PR 37).

HOST numbers, not device metrics: the rollup is numpy and Python on the
host, and these are the host's clock around the call, the least and the
median of ``--reps``.  The chip's host ran the loop 2.5-2.7x slower than
the builder's sandbox (ISSUE 37); the benchmark's own metric is
``refprofile_rollup_ms_per_tree`` (PERF.md).

The mappers are synthetic, shaped as the cells' are: Epsilon and Bosch
every feature 254 bounds; ``criteo_fit`` 13 count columns of few
distinct values beside 26 categorical; ``istella_fit`` every eighth
column of few values; ``allstate_fit`` 14 numeric columns beside 4214
one-hot ones of one bound.  The old loop is the oracle of
``tests/test_sketch_rollup.py``, loaded from there (one copy).  Prints a
line a cell and writes ``chiprun_out/bench_rollup.json``.
"""

import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from mmlspark_tpu.core.sketch import build_reference_profile  # noqa: E402
from mmlspark_tpu.gbdt.binning import BinMapper  # noqa: E402

NB = 256            # 255 value bins and the missing one, every cell
MARGINS = 32_768    # the capture's sampled rows


def _ladder_lengths(cell, rng):
    """(lengths, categorical flags) of a cell's mapper."""
    if cell in ("epsilon_fit", "epsilon_fit_dp4"):
        return [254] * 2000, None
    if cell == "bosch_fit":
        return [254] * 968, None
    if cell == "criteo_fit":
        lens = rng.integers(8, 80, 13).tolist() + [254] * 26
        return lens, [False] * 13 + [True] * 26
    if cell == "istella_fit":
        return [int(rng.integers(2, 16)) if j % 8 == 0 else 254
                for j in range(220)], None
    if cell == "allstate_fit":
        return [254] * 8 + [190] * 4 + [12] * 2 + [1] * 4214, None
    raise ValueError(cell)


def _inputs(cell, seed):
    rng = np.random.default_rng(seed)
    lens, cat = _ladder_lengths(cell, rng)
    ubs = [np.sort(rng.choice(10 ** 6, L, replace=False)) / 1e3
           for L in lens]
    mapper = BinMapper(
        upper_bounds=[u.astype(np.float64) for u in ubs],
        has_missing=np.ones(len(lens), bool), num_total_bins=NB,
        missing_bin=NB - 1,
        categorical=None if cat is None else np.asarray(cat, bool),
        cat_values=None if cat is None else
        [np.arange(L + 1) if c else None for L, c in zip(lens, cat)])
    fine = rng.integers(0, 2000, (len(lens), NB)).astype(np.int64)
    for j, L in enumerate(lens):
        if not (cat and cat[j]):
            fine[j, L + 1:NB - 1] = 0
    return mapper, fine, rng.normal(size=MARGINS)


def _time(fn, reps):
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        out.append((time.perf_counter() - t0) * 1e3)
    return min(out), statistics.median(out)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cells", default="epsilon_fit,bosch_fit,"
                    "criteo_fit,istella_fit,allstate_fit")
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    spec = importlib.util.spec_from_file_location(
        "_rollup_oracle", os.path.join(ROOT, "tests",
                                       "test_sketch_rollup.py"))
    oracle = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(oracle)
    rows = []
    for cell in args.cells.split(","):
        mapper, fine, margins = _inputs(cell, args.seed)
        shape = (int(fine[0].sum()), mapper.num_features)
        bins = np.broadcast_to(np.uint8(0), shape)   # only its shape is read
        row = {"cell": cell, "features": shape[1]}
        row["rollup_ms"] = _time(lambda: build_reference_profile(
            bins, mapper, margins, fine_counts=fine), args.reps)
        row["loop_ms"] = _time(lambda: oracle.per_feature_profile(
            bins, mapper, margins, fine_counts=fine), args.reps)
        rows.append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "bench_rollup.json"),
              "w") as fh:
        json.dump({"host_numbers_not_device_metrics": True,
                   "rows": rows}, fh, indent=1)


if __name__ == "__main__":
    main()
