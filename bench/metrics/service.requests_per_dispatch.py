"""Requests per coalesced dispatch in the window: the queue's counters
``serve.batched_requests / serve.batches``."""


def read(run):
    b = run.counters.get("serve.batches", 0)
    return run.counters.get("serve.batched_requests", 0) / b if b else None
