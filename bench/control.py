"""Readings of the control: the plain reference, computed in bfloat16,
put where the system under test stands.

    python3 bench/control.py --workload <name> --seeds 1,2,3 \\
        --queries 400

For each seed it makes the cell's corpus and query pool as a run does,
answers the first ``--queries`` queries of the pool with the reference's
bfloat16 scan (``answers(..., dtype="bfloat16")``), and compares those
answers as a run compares the program's (``tsbench.check``).  One JSON
line per seed gives each number compared beside its limit; the control
has to come out not correct.  The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def readings(root: str, workload: str, seed: int,
             queries: int) -> dict:
    """The checks of the control's answers for one seed."""
    from tsbench import check, gen, spec

    cell = spec.load_cell(root, workload)
    cfg = cell.config
    g = cfg["generator"]
    gmod = spec.plugin(root, "data", g["name"])
    n, T = int(cfg["rows"]), int(cfg["length"])
    k = int(cell.traffic["k"])
    corpus = gen.series(gmod, g["args"], seed, gen.CORPUS, n, T)
    pool = gen.queries(gmod, g["args"], cell.traffic, seed, T)[:queries]
    ref = spec.plugin(root, "reference", cfg["reference"])
    ids, dists = ref.answers(corpus, pool, k, dtype="bfloat16")
    answers = [(ids[r], dists[r], "linear") for r in range(len(pool))]
    return check.compare(corpus, pool, answers, k, ref, cfg["limits"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--queries", type=int, default=400)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    from tsbench import check, harness
    harness.prepare_env()
    import jax
    harness.enable_compile_cache(ROOT)
    dev = jax.devices()[0]
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        checks = readings(ROOT, args.workload, seed, args.queries)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "dtype": "bfloat16", "device": dev.device_kind,
                          "correct": check.passed(checks),
                          "seconds": time.perf_counter() - t,
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
