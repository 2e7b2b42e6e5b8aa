"""Share of the HBM roofline of the bound sweep (``jit_rr_bounds``):
the work of one sweep per traced dispatch (``bench/work/bound_sweep.py``,
for the requests the dispatch answered) over the program's device
seconds in the traced window, over the chip's peak bandwidth."""

from tsbench import spans


def read(run):
    w = run.work("bound_sweep")
    moved = sum(w.bytes_moved(run.mirror_bytes["rep"], len(d["waits_ms"]),
                              run.rows) for d in run.dispatches)
    return spans.roofline_share(run, "rr_bounds", moved)
