"""Median over the traced dispatches of the summed ``dispatch/verify/dist``
spans (program spans, ``explain=True``): in each verification round of
``topk_verify``, the true distances of the round's candidates, from the
row-verify program."""

from tsbench import spans


def read(run):
    return spans.round_step_ms(run, "dist")
