"""Median over the traced dispatches of the summed
``dispatch/verify/merge`` spans (program spans, ``explain=True``): in
each verification round of ``topk_verify``, the merge of the round's
distances into the best-k frontier, on the device."""

from tsbench import spans


def read(run):
    return spans.round_step_ms(run, "merge")
