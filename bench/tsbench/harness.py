"""One run of one cell: set-up, a measured window, the check, the line.

``run_cell`` makes the inputs from the seed, builds the system under
test, warms up every program shape the window uses, drives the traffic
mix's loop (``bench/loops/<loop>.py``) for ``seconds``, reads the
device's peak memory, frees the program's device state, checks every
answer of the window against the plain reference, and reduces what it
recorded to the cell's metrics: its end-to-end metrics with
``trace=False``, its per-layer metrics with ``trace=True`` (the
program's spans and counters and a profiler trace of the window's first
``TRACE_S`` seconds, then one more dispatch traced with the Python
tracer on, to name the host's activity in the device's idle gaps).
"""

from __future__ import annotations

import gc
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

import numpy as np

from tsbench import check, devtrace, gen, spec, sut

#: seconds at the start of a traced window that the profiler records
TRACE_S = 3.0
_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - _T0:.1f}s] {msg}",
          file=sys.stderr, flush=True)


def prepare_env() -> None:
    """Before JAX is imported: keep the TPU runtime's logs off the disk."""
    os.environ["TPU_LOG_DIR"] = "disabled"


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path in the
    checkout, for every program however quick to compile."""
    import jax
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts programs lowered (compiled or loaded from the persistent
    cache) while ``on``."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",)

    def __init__(self):
        import jax
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and event in self.EVENTS:
            self.count += 1


def device_peak_bytes(device) -> int:
    """The most device memory the chip has held: the allocator's peak in
    use, plus the peak it reserved for compiled programs' temporaries.
    ``peak_bytes_in_use`` leaves the temporaries out, and a reservation
    is kept once a program has needed it, so the in-use peak of the
    window sits on top of it."""
    stats = device.memory_stats() or {}
    return (int(stats.get("peak_bytes_in_use", 0))
            + int(stats.get("peak_bytes_reserved", 0)))


@dataclass
class Run:
    """What a metric reader (``bench/metrics/<name>.py``) reads."""

    root: str
    seconds: float                  # window asked for
    setup_s: float
    rows: int
    length: int
    requests: list                  # MatchRequest of the window
    t_start: float                  # window bounds, monotonic clock
    t_end: float
    t_drained: float                # the window's last answer
    counters: dict
    memory_peak_bytes: int
    mirror_bytes: dict
    peaks: dict
    dispatches: list = field(default_factory=list)
    device: devtrace.DeviceTrace = None

    def work(self, kernel: str):
        return spec.plugin(self.root, "work", kernel)

    @property
    def latencies_ms(self) -> np.ndarray:
        """Submit-to-answer times of every answered request of the
        window, including answers that came after it closed."""
        return np.asarray([(r.t_done - r.t_submit) * 1e3
                           for r in self.requests if r.ok])

    @property
    def answered(self) -> int:
        """Requests of the window that were answered."""
        return sum(1 for r in self.requests if r.ok)


def dispatch_records(requests) -> list:
    """One record per traced dispatch of the window: its spans, rounds,
    the rows it verified, and for each request it answered the time
    spent outside it (submit-to-answer less ``order`` and ``verify``)."""
    by_trace = {}
    for r in requests:
        tr = getattr(r, "trace", None)
        if tr is None or not r.ok:
            continue
        rec = by_trace.get(id(tr))
        if rec is None:
            rec = by_trace[id(tr)] = {
                "order_s": tr.span_seconds("order"),
                "verify_s": tr.span_seconds("verify"),
                "rounds": len(tr.rounds),
                "examined": sum(int(x.get("examined", 0))
                                for x in tr.rounds),
                "waits_ms": [],
            }
        rec["waits_ms"].append(
            (r.t_done - r.t_submit - rec["order_s"] - rec["verify_s"])
            * 1e3)
    return list(by_trace.values())


def _profile(trace_dir: str, python: bool):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 1 if python else 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, require_tpu: bool = True) -> dict:
    """One run; returns the result line as a dict, or raises."""
    import jax

    cell = spec.load_cell(root, workload)
    devs = jax.devices()
    dev0 = devs[0]
    if require_tpu and (dev0.platform != "tpu" or len(devs) < cell.chips):
        raise RuntimeError(f"cell {workload} needs {cell.chips} TPU chips; "
                           f"JAX found {len(devs)} x {dev0.platform}")
    devs = devs[:cell.chips]
    peaks = (spec.peaks(root, dev0.device_kind) if require_tpu
             else spec.load_json(os.path.join(root, "bench", "peaks.json"))
             .get(dev0.device_kind, {}))
    cfg, traffic = cell.config, cell.traffic
    drive = spec.plugin(root, "loops", traffic["loop"]).drive
    k = int(traffic["k"])
    n, T = int(cfg["rows"]), int(cfg["length"])
    max_batch = int(cfg["session"]["max_batch"])
    log(f"cell {workload}: {n} x {T} rows, k={k}, {traffic['loop']} "
        f"loop, seed {seed}; {len(devs)} x {dev0.device_kind}")

    t = time.perf_counter()
    g = cfg["generator"]
    gmod = spec.plugin(root, "data", g["name"])
    corpus = gen.series(gmod, g["args"], seed, gen.CORPUS, n, T)
    log(f"set-up corpus: {time.perf_counter() - t:.1f}s")
    t = time.perf_counter()
    pool = gen.queries(gmod, g["args"], traffic, seed, T)
    warm = gen.series(gmod, g["args"], int(traffic["query_seed"]),
                      gen.WARM, sut.buckets(max_batch)[-1], T)
    log(f"set-up queries: {time.perf_counter() - t:.1f}s")

    t = time.perf_counter()
    engine, session = sut.build(cfg, corpus, devs)
    log(f"set-up store and encode: {time.perf_counter() - t:.1f}s")
    t = time.perf_counter()
    calls = sut.warm_up(engine, warm, k, max_batch)
    session.start()
    session.serve(warm[:1], k=k, timeout=600)
    log(f"set-up mirrors and warm-up ({calls} calls): "
        f"{time.perf_counter() - t:.1f}s; mirrors "
        f"{engine.sweep.mirror_bytes}")
    base = session.metrics.snapshot()["counters"]

    counter = CompileCounter()
    trace_dir = os.path.join(root, ".bench_trace", f"{workload}.{seed}")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        _profile(os.path.join(trace_dir, "window"), python=False)
    setup_s = time.perf_counter() - _T0
    counter.on = True
    t_start = time.monotonic()
    t_end = t_start + seconds
    pairs, dev_trace = [], None
    if trace:
        # the profiler records every operation, thousands a verification
        # round: trace the window's first TRACE_S seconds, drained, and
        # serve the rest untraced
        pairs, _ = drive(session, pool, traffic,
                         t_end=min(t_end, t_start + TRACE_S), explain=True)
        t_traced = time.monotonic()
        jax.profiler.stop_trace()
        dev_trace = devtrace.load(
            devtrace.find_xplane(os.path.join(trace_dir, "window")),
            t_traced - t_start)
        traced = [r for _, r in pairs]
        log(f"trace: {len(traced)} requests in {dev_trace.window_s:.2f}s; "
            f"{sum(len(v) for v in dev_trace.ops.values())} device "
            f"operations")
    rest, exhausted = drive(session, pool[len(pairs):], traffic,
                            t_end=t_end)
    pairs += [(i + len(pairs), r) for i, r in rest]
    t_drained = time.monotonic()
    counter.on = False
    counters = {c: v - base.get(c, 0) for c, v in
                session.metrics.snapshot()["counters"].items()}
    log(f"window: {len(pairs)} requests in {seconds}s, the last answered "
        f"{t_drained - t_end:.2f}s after it closed; programs lowered "
        f"inside it: {counter.count}"
        + ("; QUERY POOL EXHAUSTED" if exhausted else ""))
    if trace:
        # one dispatch more, with the Python tracer on, to name what the
        # host does while the device idles
        _profile(os.path.join(trace_dir, "python"), python=True)
        nxt = len(pairs)
        session.serve(pool[nxt:nxt + max_batch], k=k, timeout=600)
        jax.profiler.stop_trace()
        dev_trace.python_gaps = devtrace.load(
            devtrace.find_xplane(os.path.join(trace_dir, "python")),
            0.0).idle_gaps()

    peak = max(device_peak_bytes(d) for d in devs)
    mirror_bytes = engine.sweep.mirror_bytes
    reqs = [r for _, r in pairs]
    run = Run(root=root, seconds=seconds, setup_s=setup_s, rows=n,
              length=T, requests=reqs, t_start=t_start, t_end=t_end,
              t_drained=t_drained, counters=counters,
              memory_peak_bytes=peak, mirror_bytes=mirror_bytes, peaks=peaks,
              dispatches=dispatch_records(traced) if trace else [],
              device=dev_trace)
    sut.free(engine, session)
    del engine, session
    gc.collect()

    t = time.perf_counter()
    answers = [(r.indices, r.distances, r.tier_served) if r.ok else None
               for r in reqs]
    ref = spec.plugin(root, "reference", cfg["reference"])
    checks = check.compare(corpus, pool[[i for i, _ in pairs]], answers,
                           k, ref, cfg["limits"])
    log(f"check of {len(answers)} answers: "
        f"{time.perf_counter() - t:.1f}s")

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = spec.plugin(root, "metrics", m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": check.passed(checks), "attempted": len(reqs),
           "failed": sum(1 for r in reqs if not r.ok),
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = dev_trace.busy_s()
        device["window_s"] = dev_trace.window_s
        out["breakdown"] = {"device_ops": dev_trace.top_ops(),
                            "idle_gaps": dev_trace.python_gaps}
        shutil.rmtree(trace_dir, ignore_errors=True)
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return out

