"""Exact percentiles and the window accounting of tokens and gaps.

Every token the engine emits is stamped on the host clock when the
``step()`` that emitted it returns (``step`` ends in a host read-back of
the sampled tokens).  A window ``[t0, t1]`` then counts:

* tokens: stamps in ``(t0, t1]``;
* inter-token gaps: consecutive stamps of one request, both in
  ``(t0, t1]``;
* time to first token: first stamp minus the request's due time.
"""
from __future__ import annotations

import math

__all__ = ["percentile", "TokenLog"]


def percentile(values, p: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least ``p``
    percent of the samples at or below it (a sample, never an
    interpolation or a histogram bucket)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    k = max(1, math.ceil(p / 100.0 * len(xs)))
    return float(xs[k - 1])


class TokenLog:
    """Per-request token stamps and due times."""

    def __init__(self):
        self.stamps: dict[int, list[float]] = {}
        self.due: dict[int, float] = {}

    def offer(self, rid: int, due: float) -> None:
        self.due[rid] = due
        self.stamps.setdefault(rid, [])

    def stamp(self, rid: int, n_new: int, t: float) -> None:
        self.stamps[rid].extend([t] * n_new)

    def tokens(self, t0: float, t1: float) -> int:
        return sum(1 for ts in self.stamps.values() for t in ts
                   if t0 < t <= t1)

    def gaps(self, t0: float, t1: float) -> list[float]:
        out = []
        for ts in self.stamps.values():
            for a, b in zip(ts, ts[1:]):
                if t0 < a and b <= t1:
                    out.append(b - a)
        return out

    def ttfts(self, rids) -> list[float]:
        """First-token delays of ``rids``, from each one's due time."""
        return [self.stamps[r][0] - self.due[r] for r in rids
                if self.stamps.get(r)]
