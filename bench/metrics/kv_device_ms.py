"""paged KV cache (serve/paged_cache.py): device milliseconds per decode
tick in the cache's own programs (XLA modules ``jit_kv_*``: the view
gather, the page scatters, the state masks), over the runs of the decode
program (``jit_fn``), both counted wholly inside the traced window.  The
time of each program is also printed.

Cache work inside the decode program is not counted: the extracted trace
names ops by instruction, not by scope, and the step's masked update of
the whole view fuses into the layer scan's ops anyway.  So a change that
moves cache work into ``jit_fn`` lowers this metric without removing the
work; read ``decode_step_ms`` beside it."""
import sys

from benchlib import trace_reduce as R

CACHE = r"^jit_kv_"
DECODE = r"^jit_fn\b"


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    w = ctx["window_ns"]
    steps = R.module_events(tr, w, DECODE)
    runs = R.module_events(tr, w, CACHE)
    if not steps or not runs:
        return None
    by_program: dict = {}
    for name, _, d in runs:
        prog = name.split("(", 1)[0]
        n, t = by_program.get(prog, (0, 0.0))
        by_program[prog] = (n + 1, t + d)
    print("kv_device_ms by program, ms per decode tick: " + ", ".join(
        f"{k} {t / len(steps) / 1e6:.4f} ({n} runs)"
        for k, (n, t) in sorted(by_program.items())),
        file=sys.stderr, flush=True)
    return sum(t for _, t in by_program.values()) / len(steps) / 1e6
