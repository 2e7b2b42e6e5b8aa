"""Share of the traced dispatches whose verification rounds ran as one
device program (the trace's ``device_loop`` counter, which
``topk_verify`` sets to 1 on the device loop and 0 on the host loop).
None where no traced dispatch carries the counter."""

from tsbench import spans


def read(run):
    marks = [tr.get("device_loop") for tr in spans.traces(run)]
    if all(m is None for m in marks):
        return None
    return 100.0 * sum(1 for m in marks if m) / len(marks)
