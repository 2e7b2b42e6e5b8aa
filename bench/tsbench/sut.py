"""The system under test, built from a configuration.

The served path of ``repro``: ``make_engine_service`` (store, sharded
encode, round-robin device mirrors, device verification) behind a
``MatchSession`` (coalescing queue and planner).  The benchmark hands it
the generated corpus and queries and reads back its answers, spans and
counters; it never reaches inside it except to warm it up with the same
calls a request makes and to free its device memory after the window.
"""

from __future__ import annotations

import numpy as np


def build(config: dict, corpus: np.ndarray, devices):
    """(engine, session) over ``corpus`` on ``devices``; the session is
    not started."""
    from jax.sharding import Mesh

    import repro.core as rc
    from repro.core.distributed import make_engine_service
    from repro.obs import MetricsRegistry
    from repro.service import MatchSession

    enc = config["encoder"]
    encoder = getattr(rc, enc["class"])(**enc["args"])
    eng = config["engine"]
    mesh = Mesh(np.asarray(devices), ("data",))
    engine = make_engine_service(encoder, corpus, mesh,
                                 batch_size=int(eng["batch_size"]),
                                 verify=eng["verify"])
    if eng.get("index"):
        engine.store.build_index()
    session = MatchSession(engine, metrics=MetricsRegistry(),
                           max_batch=int(config["session"]["max_batch"]))
    return engine, session


def buckets(max_batch: int) -> list:
    """The query counts a coalesced dispatch is padded to: the powers of
    two up to the first one that holds ``max_batch``."""
    out = [1]
    while out[-1] < max_batch:
        out.append(2 * out[-1])
    return out


def warm_up(engine, queries: np.ndarray, k: int, max_batch: int) -> int:
    """Run every program shape a window of requests at ``k`` can use:
    one exact call per dispatch size, pinned to the current epoch as
    the session pins it; each width of the candidate stream's take for
    every size; and the frontier merge for every count of active
    queries.  Returns the number of calls made."""
    store = engine.store
    epoch = store.current_epoch()
    n_e = int(epoch.n_rows)
    batch = engine.batch_size
    calls = 0
    for q in buckets(max_batch):
        engine.topk(queries[:q], k=k, epoch=epoch)
        stream = engine.sweep.candidate_stream(
            queries[:q], mask_fn=lambda ids: ids >= n_e)
        stream.peek()
        for a in range(1, q + 1):
            stream.take(np.arange(a), batch)
        calls += 1 + q
    for a in range(1, buckets(max_batch)[-1] + 1):
        engine.merge(np.zeros((a, k + batch)),
                     np.zeros((a, k + batch), np.int64), k)
        calls += 1
    return calls


def free(engine, session) -> None:
    """Release the device memory the program holds (its mirrors)."""
    session.close(drain=False)
    sweep = engine.sweep
    for mir in list(sweep._mirrors or ()) + [sweep._raw_mirror]:
        if mir is not None and mir.buf is not None:
            mir.buf.delete()
            mir.buf = None
