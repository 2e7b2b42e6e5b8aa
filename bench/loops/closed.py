"""Closed-loop load: ``clients`` callers, each submitting one request and
waiting for its answer before the next.

Callers take queries from the pool in order, so no query repeats.  A
caller stops submitting once the window has closed and waits for what it
has in flight; the requests submitted inside the window are the
window's.

A traffic mix names its loop by ``"loop"``; the harness imports
``bench/loops/<loop>.py`` and calls its ``drive``.
"""

from __future__ import annotations

import itertools
import threading
import time

#: how long past the window's close an answer is awaited
LATE_S = 60.0


def drive(session, pool, traffic: dict, *, t_end: float,
          explain: bool = False) -> tuple:
    """Run the mix's ``clients`` callers at its ``k`` until the monotonic
    clock passes ``t_end``.  Returns ((pool index, request) pairs in pool
    order, pool exhausted?)."""
    k, clients = int(traffic["k"]), int(traffic["clients"])
    order = itertools.count()
    lock = threading.Lock()
    reqs = []
    exhausted = threading.Event()

    def caller():
        while time.monotonic() < t_end:
            with lock:
                i = next(order)
            if i >= len(pool):
                exhausted.set()
                return
            r = session.submit(pool[i], k=k, explain=explain)
            with lock:
                reqs.append((i, r))
            r.wait(max(t_end - time.monotonic(), 0.0) + LATE_S)

    threads = [threading.Thread(target=caller, name=f"caller-{c}")
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    reqs.sort(key=lambda p: p[0])
    return reqs, exhausted.is_set()
