"""Benchmark runner — one module per paper table/figure plus the roofline
report.  Prints ``name,us_per_call,derived`` CSV rows and writes one
machine-readable ``results/BENCH_<suite>.json`` per suite (wall-clock,
the suite's result rows — candidates examined, bytes moved, bitwise
verdicts — and any error), so the perf trajectory is diffable across
PRs instead of living in log text.

    PYTHONPATH=src python -m benchmarks.run [--only entropy,tlb,...]

Paper artifact map:
    entropy  -> Fig. 4      tlb      -> Fig. 5     pruning -> Fig. 6
    approx   -> Fig. 7      matching -> Table 5    kernels -> (engine)
    ingest   -> (store subsystem: append throughput + query-under-ingest)
    subseq   -> (subsequence subsystem: pruned windowed scan vs brute)
    index    -> (index subsystem: tree candidates vs linear sweep)
    sharded_verify -> (device-resident sharded verification vs host)
    serving  -> (service subsystem: coalescing queue + planner under load)
    selfjoin -> (profile subsystem: FFT dot crossover + exact motifs)
    roofline -> EXPERIMENTS.md §Roofline (from results/dryrun.json)
"""

from __future__ import annotations

import argparse
import importlib
import inspect
import json
import os
import sys
import time

SUITES = ["entropy", "tlb", "pruning", "approx", "matching", "kernels",
          "extensions", "ingest", "subseq", "index", "sharded_verify",
          "serving", "selfjoin", "roofline", "perf"]

RESULTS_DIR = "results"


def _rows_payload(rows) -> list:
    """Normalize a suite's ``run()`` return into [{"name", "derived"}]
    — suites return a list of (name, derived) pairs, None, or their own
    shapes; anything unrecognized is dropped, never fatal."""
    out = []
    if isinstance(rows, (list, tuple)):
        for r in rows:
            if (isinstance(r, (list, tuple)) and len(r) == 2
                    and isinstance(r[0], str)):
                out.append({"name": r[0], "derived": str(r[1])})
    return out


def _write_json(suite: str, payload: dict):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, f"BENCH_{suite}.json"), "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)


def _summary(snap: dict, seconds: float) -> dict:
    """The cross-suite comparable summary every BENCH json carries —
    the same five numbers no matter which suite produced them, pooled
    from whatever ``bench.*`` / ``match.*`` / ``subseq.*`` metrics the
    suite recorded (suites record through
    ``benchmarks.common.observe_topk`` or an engine's ``metrics=``)."""
    c, g = snap["counters"], snap["gauges"]

    def _tot(suffix):
        return sum(v for k, v in c.items() if k.endswith(suffix))

    pp = [v for k, v in g.items() if ".pruning_power" in k]
    return {
        "pruning_power": (sum(pp) / len(pp)) if pp else None,
        "rows_fetched": _tot(".rows_fetched"),
        "modeled_io_s": _tot(".modeled_io_s"),
        "wall_s": seconds,
        "host_bytes": _tot(".host_order_bytes") + _tot(".h2d_bytes"),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated subset of: " + ",".join(SUITES))
    ap.add_argument("--dryrun", action="store_true",
                    help="forward dryrun=True to every suite that "
                    "accepts it (tiny CI sizes)")
    ap.add_argument("--strict", action="store_true",
                    help="exit non-zero if any selected suite errored "
                    "(CI: a diverging bench fails the leg, with the "
                    "BENCH json still written for the artifact upload)")
    args = ap.parse_args()
    chosen = args.only.split(",") if args.only else SUITES

    # every suite runs in this one process (a child would find the chip
    # held), so one cache serves them all
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.obs import REGISTRY

    failed = []
    print("name,us_per_call,derived")
    for suite in SUITES:
        if suite not in chosen:
            continue
        t0 = time.time()
        modname = {"roofline": "benchmarks.roofline",
                   "perf": "benchmarks.perf_report"}.get(
                       suite, f"benchmarks.bench_{suite}")
        # suite boundary: metrics recorded by one suite must never bleed
        # into the next suite's snapshot
        REGISTRY.reset()
        try:
            mod = importlib.import_module(modname)
            kwargs = {}
            if args.dryrun and "dryrun" in inspect.signature(
                    mod.run).parameters:
                kwargs["dryrun"] = True
            rows = mod.run(**kwargs)
            seconds = time.time() - t0
            snap = REGISTRY.snapshot()
            _write_json(suite, {"suite": suite, "ok": True,
                                "seconds": seconds,
                                "dryrun": args.dryrun,
                                "rows": _rows_payload(rows),
                                "metrics": snap,
                                "summary": _summary(snap, seconds)})
            print(f"suite/{suite},{seconds * 1e6:.0f},ok", flush=True)
        except Exception as e:   # noqa: BLE001 — report, keep going
            seconds = time.time() - t0
            snap = REGISTRY.snapshot()
            _write_json(suite, {"suite": suite, "ok": False,
                                "seconds": seconds,
                                "dryrun": args.dryrun,
                                "error": f"{type(e).__name__}: {e}",
                                "metrics": snap,
                                "summary": _summary(snap, seconds)})
            print(f"suite/{suite},,ERROR {type(e).__name__}: {e}",
                  flush=True)
            failed.append(suite)
    if failed and args.strict:
        sys.exit(f"benchmarks failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
