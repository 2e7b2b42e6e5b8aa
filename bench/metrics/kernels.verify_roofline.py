"""Share of the HBM roofline of the row-verify program
(``jit_rr_rows_verify``): the work of the candidate rows the traced
dispatches examined (``bench/work/row_verify.py``) over the program's
device seconds in the traced window, over the chip's peak bandwidth."""

from tsbench import spans


def read(run):
    rows = sum(d["examined"] for d in run.dispatches)
    moved = run.work("row_verify").bytes_moved(rows, run.length)
    return spans.roofline_share(run, "rr_rows_verify", moved)
