"""Spreads of two sets of runs, as the bound rule reads them.

    python3 benchmark/checks/spread.py chiprun_out/sets <workload>

Reads ``<dir>/<workload>_set<k>_<seed>.out`` (benchmark/checks/sets.sh
writes them).  For each end-to-end metric and each set: median, and the
distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median; then the
wider of the two, times five: the bound that the rule gives.
"""

import glob
import json
import os
import statistics
import sys


def main():
    folder, workload = sys.argv[1], sys.argv[2]
    sets = {}
    for path in sorted(glob.glob(os.path.join(folder, f"{workload}_set*_*.out"))):
        k = os.path.basename(path).split("_set")[1].split("_")[0]
        with open(path) as fh:
            lines = [ln for ln in fh.read().splitlines() if ln.startswith("{")]
        if not lines:
            print("no result in", path)
            continue
        res = json.loads(lines[-1])
        if not res["correct"]:
            print("NOT CORRECT:", path, res["compared"])
        sets.setdefault(k, []).append(res)
    names = sorted({m for runs in sets.values() for r in runs
                    for m in r["metrics"]})
    for name in names:
        widest = 0.0
        for k, runs in sorted(sets.items()):
            vals = [r["metrics"][name]["value"] for r in runs]
            if name == "setup_s":
                vals = vals[1:] if k == min(sets) else vals   # first compiles
            q = statistics.quantiles(vals, n=4)
            med = statistics.median(vals)
            spread = (q[2] - q[0]) / med
            widest = max(widest, spread)
            print(f"{workload} {name} set {k}: n={len(vals)} median "
                  f"{med:.6g} iqr/median {spread:.5f} min {min(vals):.6g} "
                  f"max {max(vals):.6g}")
        print(f"{workload} {name}: widest spread {widest:.5f} -> x5 = "
              f"{5 * widest:.4f}")


if __name__ == "__main__":
    main()
