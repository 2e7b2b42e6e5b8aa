"""Share of the traced window in which the ``repro/dispatch/order``
annotation (the ``dispatch/order`` span: query encode, bound sweep,
lexsort) is open on a host line and no operation runs on the device."""

from tsbench import spans


def read(run):
    return spans.idle_within_share(run, "dispatch/order")
