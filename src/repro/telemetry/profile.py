"""Kernel launch profiling (DESIGN §12): the one timing harness both
benches consume instead of their ad-hoc best-of-N loops.

``time_launch`` runs a jitted callable with explicit warmup discard
(compile + cache effects never pollute the sample), records every timed
iteration into a telemetry ``Histogram`` (fixed log-spaced buckets), and
returns best / p50 / p95 microseconds plus — when the caller passes the
pack's streamed plane bytes — the effective GB/s the launch sustained
and its fraction of the *dense roofline* (the bandwidth the dense matmul
achieved on the same device: the paper's own yardstick, Section IV).
"""
from __future__ import annotations

import dataclasses
import time

from repro.telemetry.metrics import US_BUCKETS, Histogram
from repro.telemetry.trace import NULL_TRACER

__all__ = ["LaunchTiming", "time_launch"]


@dataclasses.dataclass
class LaunchTiming:
    """One profiled launch site.  Times in microseconds."""
    iters: int
    warmup: int
    best_us: float
    p50_us: float
    p95_us: float
    mean_us: float
    bytes_moved: int | None = None       # value+index plane bytes per call
    gbps_best: float | None = None       # bytes_moved at best_us
    roofline_frac: float | None = None   # vs dense GB/s on same device

    def to_dict(self) -> dict:
        d = {"iters": self.iters, "warmup": self.warmup,
             "best_us": round(self.best_us, 1),
             "p50_us": round(self.p50_us, 1),
             "p95_us": round(self.p95_us, 1),
             "mean_us": round(self.mean_us, 1)}
        if self.bytes_moved is not None:
            d["bytes_moved"] = int(self.bytes_moved)
            d["gbps_best"] = round(self.gbps_best, 3)
        if self.roofline_frac is not None:
            d["roofline_frac"] = round(self.roofline_frac, 3)
        return d


def _block(x):
    # works for jax arrays and pytrees of them; tolerates plain numpy
    blocker = getattr(x, "block_until_ready", None)
    if blocker is not None:
        blocker()
        return
    import jax
    jax.block_until_ready(x)


def time_launch(fn, *args, iters: int = 5, warmup: int = 1,
                bytes_moved: int | None = None,
                dense_bytes: int | None = None,
                dense_us: float | None = None,
                tracer=NULL_TRACER, label: str = "launch") -> LaunchTiming:
    """Profile ``fn(*args)``: ``warmup`` discarded calls (compile), then
    ``iters`` timed calls, each fenced with block_until_ready so async
    dispatch cannot smear across iterations.  Timed iterations land in a
    log-bucket Histogram — p50/p95 are its streaming quantiles, ``best``
    is exact (the benches' historic best-of figure, kept byte-compatible).

    ``bytes_moved`` (the pack's value+index plane bytes per call) turns
    the best time into effective GB/s; adding ``dense_bytes``+``dense_us``
    (the dense matmul on the same shapes) expresses it as a fraction of
    the dense roofline.
    """
    if iters < 1 or warmup < 0:
        raise ValueError(f"bad iters={iters} warmup={warmup}")
    for _ in range(max(1, warmup)):
        with tracer.span(label, cat="warmup"):
            out = fn(*args)
            _block(out)
    hist = Histogram("launch_us", {}, edges=US_BUCKETS)
    best = float("inf")
    for _ in range(iters):
        with tracer.span(label, cat="timed"):
            t0 = time.perf_counter()
            out = fn(*args)
            _block(out)
            us = (time.perf_counter() - t0) * 1e6
        hist.observe(us)
        best = min(best, us)
    t = LaunchTiming(iters=iters, warmup=max(1, warmup), best_us=best,
                     p50_us=hist.quantile(0.50), p95_us=hist.quantile(0.95),
                     mean_us=hist.sum / hist.count)
    if bytes_moved is not None:
        t.bytes_moved = int(bytes_moved)
        t.gbps_best = bytes_moved / max(best * 1e-6, 1e-12) / 1e9
        if dense_bytes is not None and dense_us is not None and dense_us > 0:
            dense_gbps = dense_bytes / (dense_us * 1e-6) / 1e9
            t.roofline_frac = t.gbps_best / max(dense_gbps, 1e-12)
    return t

