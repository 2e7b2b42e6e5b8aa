"""Reductions of the program's span tree and of its spans' profiler
annotations, for the per-layer readers.

A traced dispatch of ``MatchSession`` records the spans ``dispatch``,
``dispatch/order`` and ``dispatch/verify``, and under the last one each
verification round's ``peek``, ``take``, ``dist`` and ``merge``
(``repro.obs.Trace``).  Every span is also a profiler annotation named
``repro/<path>`` on the host line of the thread that ran it, on the
device trace's clock.  A program that records none of these gives the
readers nothing to read: they return None.
"""

from __future__ import annotations

import numpy as np


def traces(run) -> list:
    """The distinct traces of the window's answered requests: one per
    traced dispatch."""
    seen = {}
    for r in run.requests:
        tr = getattr(r, "trace", None)
        if tr is not None and r.ok:
            seen.setdefault(id(tr), tr)
    return list(seen.values())


def round_step_ms(run, step: str):
    """Median over the traced dispatches of the summed
    ``dispatch/verify/<step>`` spans, in ms."""
    path = f"dispatch/verify/{step}"
    per = [sum(s.seconds for s in sps) * 1e3
           for sps in ([s for s in tr.spans if s.name == path]
                       for tr in traces(run)) if sps]
    return float(np.median(per)) if per else None


def _merged(intervals) -> list:
    """Disjoint, sorted ``[start, end]`` covering ``(start, end)``
    pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap_ns(a, b) -> int:
    """Length of the intersection of two merged interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_within_share(run, path: str):
    """Share (%) of the traced window in which the annotation
    ``repro/<path>`` is open on a host line and no operation runs on the
    device, averaged over devices."""
    dev = run.device
    if dev is None or not dev.devices or dev.window_s <= 0:
        return None
    name = f"repro/{path}"
    spans = _merged((s, e) for evs in dev.python.values()
                    for n, s, e in evs if n == name)
    if not spans:
        return None
    open_ns = sum(e - s for s, e in spans)
    idle = [open_ns - _overlap_ns(spans, _merged(
        (s, e) for _, s, e in dev._busy_events(d))) for d in dev.devices]
    return 100.0 * sum(idle) / len(idle) / 1e9 / dev.window_s


def roofline_share(run, role: str, bytes_moved: float):
    """Share (%) of the HBM roofline of the program ``jit_<role>``:
    ``bytes_moved`` over its device seconds in the traced window, over
    the chip's peak bandwidth."""
    peak = run.peaks.get("hbm_bytes_per_s")
    if run.device is None or not peak or bytes_moved <= 0:
        return None
    mod = f"jit_{role}"
    t = run.device.module_seconds(
        lambda n: n == mod or n.startswith(mod + "("))
    return 100.0 * bytes_moved / peak / t if t > 0 else None
