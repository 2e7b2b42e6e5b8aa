"""Helpers of the benchmark's tests: a copy of the benchmark at a size the
CPU holds, driven in-process."""

from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: rows and query pool of the tiny copies
TINY_ROWS = {"season_large_q": 2048, "hydra_rw256_q": 4096}
TINY_POOL = 256


def tiny_root(tmp_path) -> str:
    """A checkout holding ``BENCHMARK.json`` and a copy of ``bench/`` whose
    configurations and query pools are cut to CPU size."""
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        path = os.path.join(root, c["file"])
        with open(path) as f:
            cfg = json.load(f)
        cfg["rows"] = TINY_ROWS.get(c["name"], 2048)
        with open(path, "w") as f:
            json.dump(cfg, f)
    tdir = os.path.join(root, "bench", "traffic")
    for name in os.listdir(tdir):
        with open(os.path.join(tdir, name)) as f:
            t = json.load(f)
        t["pool"] = TINY_POOL
        with open(os.path.join(tdir, name), "w") as f:
            json.dump(t, f)
    return root


def run(root: str, workload: str, seed: int = 7, seconds: float = 1.0,
        trace: bool = False) -> dict:
    """One run on the CPU, the look for a chip skipped."""
    from tsbench import harness
    return harness.run_cell(root, workload, seed, seconds, trace,
                            require_tpu=False)
