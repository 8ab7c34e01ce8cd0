"""One run of a cell through the harness's own ``execute``, with the
sha256 of the forest its last fit exported on standard error: what a
paired run on two checkouts compares to say that a change left a cell's
programs as they were.

    python3 benchmark/checks/forest_sha.py --workload epsilon_fit --seed 7 --seconds 20 --trace 0

The arguments are ``benchmark/run.py``'s; so is the line on standard
output.  Run it from the root of the checkout it measures.
"""

import hashlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as harness      # noqa: E402


def main(argv=None):
    args = harness.parse(argv)
    import importlib
    traffic = harness.load_cell(args.bench_json, args.workload)[3]
    driver = importlib.import_module("benchmark.drivers." + traffic["driver"])
    release = driver.release

    def hashing(ctx, state):
        release(ctx, state)
        for key, text in state.items():
            if key.endswith("model_text"):
                harness.log(f"[forest] {args.workload} seed {args.seed} "
                            f"sha256 {hashlib.sha256(text.encode()).hexdigest()}")

    driver.release = hashing
    code, result = harness.execute(args)
    if result is not None and not args.rehearse:
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
