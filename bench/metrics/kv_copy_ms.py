"""paged KV cache (serve/paged_cache.py): host-clock milliseconds per
decode tick in the cache's view gather and page scatter, from the
program tracer's ``cache.gather`` and ``cache.scatter`` spans under each
``decode.step`` (the tracer fences device work at span boundaries)."""
from benchlib import trace_reduce as R


def read(ctx):
    ticks = R.under(ctx.get("spans") or [], "decode.step")
    if not ticks:
        return None
    ns = sum(s.dur_ns for kids in ticks.values() for s in kids
             if s.name in ("cache.gather", "cache.scatter"))
    return ns / len(ticks) / 1e6
