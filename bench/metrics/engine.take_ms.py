"""Median over the traced dispatches of the summed ``dispatch/verify/take``
spans (program spans, ``explain=True``): in each verification round of
``topk_verify``, the next batch of candidate ids of the active queries,
gathered from the device stream."""

from tsbench import spans


def read(run):
    return spans.round_step_ms(run, "take")
