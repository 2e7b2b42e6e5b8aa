"""Verification rounds per dispatch in the window, from the trace's
``record_round`` records (all rounds over all dispatches)."""


def read(run):
    d = run.dispatches
    return sum(x["rounds"] for x in d) / len(d) if d else None
