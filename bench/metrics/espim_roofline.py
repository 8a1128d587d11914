"""Pallas kernels (kernels/espim_spmv.py): the ESPIM launches' share of
their roofline.  Bytes are every value, index and scale plane of every
launch as stored, plus x and the output (``flops.espim_step_bytes``),
per decode step; the least time is the larger of bytes over the chip's
HBM bandwidth and operations over its bf16 peak (bytes bound it).  The
time is the device time of the kernel events inside the decode program
runs wholly inside the traced window."""
from benchlib import trace_reduce as R

KERNEL = r"^%espim_spmv_planes\b"
DECODE = r"^jit_fn\b"


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    steps = R.module_events(tr, ctx["window_ns"], DECODE)
    t_ns = R.kernel_ns(tr, steps, KERNEL) if steps else 0.0
    if t_ns <= 0:
        return None
    peak = ctx["peak"]
    least = max(len(steps) * ctx["espim_step_bytes"] / peak["hbm_bytes_s"],
                len(steps) * ctx["espim_step_ops"] / peak["bf16_flops"])
    return 100.0 * least / (t_ns / 1e9)
