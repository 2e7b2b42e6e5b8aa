"""Median over the traced dispatches of the summed ``dispatch/verify/peek``
spans (program spans, ``explain=True``): in each verification round of
``topk_verify``, the next unverified bound of every query, fetched from
the device stream."""

from tsbench import spans


def read(run):
    return spans.round_step_ms(run, "peek")
