"""model step: device time of one run of the jitted decode program (the
engine's batched ESPIM decode step), mean over the runs wholly inside the
traced window.  The program is found by its XLA module name."""
from benchlib import trace_reduce as R

PROGRAM = r"^jit_fn\b"


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    ev = R.module_events(tr, ctx["window_ns"], PROGRAM)
    if not ev:
        return None
    return sum(e[2] for e in ev) / len(ev) / 1e6
