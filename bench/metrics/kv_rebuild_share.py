"""paged KV cache (serve/paged_cache.py): decode ticks that gathered the
whole cache view anew, per 100 decode ticks.  Each rebuild runs the
cache's view-gather program (XLA module ``jit_kv_gather_view``) once, and
each decode tick the decode program (``jit_fn``) once: the share is the
first's runs over the second's, both wholly inside the traced window.  It
reads 0 for a program that runs cache programs (``jit_kv_*``) but never
the gather, and nothing for a program whose cache programs carry other
names, since a missing gather then says nothing about rebuilds."""
from benchlib import trace_reduce as R

GATHER = r"^jit_kv_gather_view\b"
CACHE = r"^jit_kv_"
DECODE = r"^jit_fn\b"


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    w = ctx["window_ns"]
    steps = R.module_events(tr, w, DECODE)
    if not steps or not R.module_events(tr, w, CACHE):
        return None
    return 100.0 * len(R.module_events(tr, w, GATHER)) / len(steps)
