"""Share of the traced window in which the ``repro/dispatch/verify``
annotation (the ``dispatch/verify`` span: the verification rounds) is
open on a host line and no operation runs on the device."""

from tsbench import spans


def read(run):
    return spans.idle_within_share(run, "dispatch/verify")
