"""device (TPU v5e): share of the traced window in which no operation
ran on the device: 1 minus the union of the ``XLA Ops`` intervals over
the window, averaged over the chips used."""
from benchlib import trace_reduce as R


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["device"]:
        return None
    w = ctx["window_ns"]
    busy = R.busy_ns(tr, w)
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / (w[1] - w[0]))
