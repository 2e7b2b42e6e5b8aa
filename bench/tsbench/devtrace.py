"""Reduction of a profiler trace to device busy time, kernel time and
idle gaps.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  A device is a plane named
``/device:TPU:<n>``; its ``XLA Modules`` line holds one event per
program run and its ``XLA Ops`` line one per operation.  Host threads
are planes named ``/host:...``; the Python tracer writes its function
calls there, on lines named ``python...``.
"""

from __future__ import annotations

import glob
import gzip
import os
from collections import defaultdict
from dataclasses import dataclass, field

MODULES = "XLA Modules"
OPS = "XLA Ops"
#: idle gaps shorter than this are not attributed to host activity
MIN_GAP_NS = 20_000


@dataclass
class DeviceTrace:
    """Events of the traced window, per device: ``(name, start_ns,
    end_ns)`` of each program run (``modules``) and each operation
    (``ops``); ``python`` holds the Python tracer's lines of the host."""

    window_s: float
    modules: dict = field(default_factory=dict)   # device -> [events]
    ops: dict = field(default_factory=dict)
    python: dict = field(default_factory=dict)    # line -> [events]
    python_gaps: list = field(default_factory=list)  # idle_gaps() of a
    #   second trace taken with the Python tracer on

    @property
    def devices(self) -> list:
        return sorted(set(self.modules) | set(self.ops))

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        devs = self.devices
        if not devs:
            return 0.0
        return sum(union_ns(self._busy_events(d)) for d in devs) \
            / len(devs) / 1e9

    def _busy_events(self, dev) -> list:
        return self.ops.get(dev) or self.modules.get(dev) or []

    def module_seconds(self, match) -> float:
        """Device seconds of the program runs whose name ``match(name)``
        accepts, summed over devices."""
        return sum(e - s for evs in self.modules.values()
                   for n, s, e in evs if match(n)) / 1e9

    def module_names(self) -> dict:
        """{module name: (runs, seconds)} over all devices."""
        out = defaultdict(lambda: [0, 0.0])
        for evs in self.modules.values():
            for n, s, e in evs:
                out[n][0] += 1
                out[n][1] += (e - s) / 1e9
        return {n: tuple(v) for n, v in out.items()}

    def top_ops(self, top: int = 10) -> list:
        """[[op name, device seconds]] of the costliest operations."""
        tot = defaultdict(float)
        for evs in self.ops.values():
            for n, s, e in evs:
                tot[n] += (e - s) / 1e9
        return [[n, v] for n, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:top]]

    def idle_gaps(self, top: int = 10) -> list:
        """[[host activity, idle seconds]]: the device's idle gaps, each
        named by the innermost Python call running on the dispatching
        thread across the gap's middle, summed by name."""
        line = self._dispatch_line()
        if line is None:
            return []
        host = sorted(self.python[line], key=lambda ev: ev[1])
        tot = defaultdict(float)
        for dev in self.devices:
            for g0, g1 in gaps_ns(self._busy_events(dev)):
                if g1 - g0 < MIN_GAP_NS:
                    continue
                mid = (g0 + g1) // 2
                name = innermost(host, mid)
                tot[name or "(no Python call)"] += (g1 - g0) / 1e9
        return [[n, v] for n, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:top]]

    def _dispatch_line(self):
        """The Python line that spends the most time in the program's
        serving code (the session's dispatcher)."""
        best, best_t = None, 0
        for line, evs in self.python.items():
            t = sum(e - s for n, s, e in evs
                    if "_run_batch" in n or "topk_verify" in n)
            if t > best_t:
                best, best_t = line, t
        return best


def union_ns(events) -> int:
    total, cur_s, cur_e = 0, None, None
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(events) -> list:
    """[(start, end)] of the idle gaps between the device's busy
    intervals."""
    out, cur_e = [], None
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if cur_e is not None and s > cur_e:
            out.append((cur_e, s))
        cur_e = e if cur_e is None else max(cur_e, e)
    return out


def innermost(host_events, t: int):
    """Name of the shortest event of ``host_events`` (sorted by start)
    that spans ``t``."""
    best, best_len = None, None
    for n, s, e in host_events:
        if s > t:
            break
        if e >= t and (best_len is None or e - s < best_len):
            best, best_len = n, e - s
    return best


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, window_s: float) -> DeviceTrace:
    """Read one ``.xplane.pb`` file (or its gzip, ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    out = DeviceTrace(window_s=window_s)
    for plane in pd.planes:
        if plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CUSTOM"):
            for line in plane.lines:
                if line.name in (MODULES, OPS):
                    evs = [(ev.name, int(ev.start_ns),
                            int(ev.start_ns + ev.duration_ns))
                           for ev in line.events]
                    dest = out.modules if line.name == MODULES else out.ops
                    if evs:
                        dest[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if line.name.startswith("python"):
                    key = f"{plane.name}/{line.name}/{len(out.python)}"
                    out.python[key] = [
                        (ev.name, int(ev.start_ns),
                         int(ev.start_ns + ev.duration_ns))
                        for ev in line.events]
    return out
