"""The harness finds configurations, traffic mixes and metrics by name:
adding one is adding a file."""

import json
import os

import pytest

import benchtest_util as U
from tsbench import spec


def test_every_named_piece_exists():
    with open(os.path.join(U.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = spec.load_cell(U.REPO, w["name"])
        spec.plugin(U.REPO, "data", cell.config["generator"]["name"])
        spec.plugin(U.REPO, "reference", cell.config["reference"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert hasattr(spec.plugin(U.REPO, "metrics", m["name"]), "read")


def test_peaks_know_the_chip_and_refuse_others():
    assert spec.peaks(U.REPO, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.peaks(U.REPO, "TPU v9 imaginary")


def test_new_files_are_picked_up_by_name(tmp_path):
    """A new configuration, traffic mix and per-layer metric, each one
    new file plus its entry in BENCHMARK.json, run with no other edit."""
    root = U.tiny_root(tmp_path)
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "season_large_q.json")) as f:
        cfg = json.load(f)
    cfg.update(name="season_tiny_w24", rows=1024)
    cfg["encoder"]["args"]["W"] = 24
    with open(os.path.join(b, "configs", "season_tiny_w24.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(b, "traffic", "closed3.k4.json"), "w") as f:
        json.dump({"loop": "closed", "clients": 3, "k": 4, "pool": 64,
                   "query_seed": 5}, f)
    with open(os.path.join(b, "metrics", "answered_share.py"), "w") as f:
        f.write("def read(run):\n"
                "    return 100.0 * sum(r.ok for r in run.requests)"
                " / len(run.requests)\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "season_tiny_w24", "source": "test",
                             "file": "bench/configs/season_tiny_w24.json",
                             "reduced": ["rows"], "why": "test"})
    bench["workloads"].append({"name": "season_tiny_w24.closed3.k4",
                               "config": "season_tiny_w24",
                               "traffic": "closed3.k4", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "answered_share", "unit": "%",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["season_tiny_w24.closed3.k4"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    out = U.run(root, "season_tiny_w24.closed3.k4", seconds=0.5)
    assert out["correct"], out["checks"]
    assert out["metrics"]["answered_share"] == {"value": 100.0, "unit": "%"}
    # metrics without a "workloads" list report in every cell; the
    # latencies list their cells and leave this one out
    assert {"qps", "setup_s"} <= set(out["metrics"])
    assert "latency_p50_ms" not in out["metrics"]


def test_unknown_workload_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.load_cell(U.REPO, "no_such.cell")


SERIAL_LOOP = '''
import time


def drive(session, pool, traffic, *, t_end, explain=False):
    reqs = []
    for i, q in enumerate(pool):
        if time.monotonic() >= t_end:
            return reqs, False
        r = session.submit(q, k=int(traffic["k"]), explain=explain)
        r.wait(60.0)
        reqs.append((i, r))
    return reqs, True
'''


def _add_mix(root, loop):
    """A traffic mix driven by ``loop`` and a cell of it over the tiny
    season configuration, as new files and entries only."""
    with open(os.path.join(root, "bench", "traffic", "one.k4.json"),
              "w") as f:
        json.dump({"loop": loop, "clients": 1, "k": 4, "pool": 64,
                   "query_seed": 5}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "season_large_q.one.k4",
                               "config": "season_large_q",
                               "traffic": "one.k4", "chips": 1,
                               "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return "season_large_q.one.k4"


def test_new_loop_is_picked_up_by_name(tmp_path):
    """A new kind of loop is one new file under ``loops/``."""
    root = U.tiny_root(tmp_path)
    with open(os.path.join(root, "bench", "loops", "serial.py"), "w") as f:
        f.write(SERIAL_LOOP)
    out = U.run(root, _add_mix(root, "serial"), seconds=0.5)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_unknown_loop_is_an_error(tmp_path):
    root = U.tiny_root(tmp_path)
    with pytest.raises(spec.SpecError):
        U.run(root, _add_mix(root, "no_such_loop"), seconds=0.5)
