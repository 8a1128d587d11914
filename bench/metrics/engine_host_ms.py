"""engine / scheduler (serve/engine.py, serve/scheduler.py): host-clock
milliseconds per engine tick outside the device work: each
``engine.step`` span minus its ``prefill.launch`` / ``decode.launch``
spans (fenced device time of the jitted programs) and its
``cache.gather`` / ``cache.scatter`` spans (read by ``kv_copy_ms``)."""
from benchlib import trace_reduce as R

DEVICE = ("prefill.launch", "decode.launch", "cache.gather", "cache.scatter")


def read(ctx):
    ticks = R.under(ctx.get("spans") or [], "engine.step")
    if not ticks:
        return None
    ns = sum(root.dur_ns - sum(s.dur_ns for s in kids if s.name in DEVICE)
             for root, kids in ticks.items())
    return ns / len(ticks) / 1e6
