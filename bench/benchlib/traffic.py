"""One general traffic generator, driven by a mix file and a cell file.

A mix file (``bench/traffic/<mix>.json``) holds the distributions:

* ``loop``: ``"closed"`` (a backlog: the queue always holds as many
  requests beyond those in service as the cell has slots) or ``"open"``
  (arrivals on the wall clock at the cell's ``rate_rps``, whatever the
  server does);
* ``prompt`` and ``output``: token-length distributions, each
  ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
  ``{"dist": "uniform", "min", "max"}``;
* ``block``: requests per block.  Every block holds the same multiset of
  prompt lengths, output lengths and inter-arrival gaps (a stratified
  grid over each distribution), shuffled inside the block in an order
  that is the same for every seed; ``--seed`` draws the token ids.  So
  every seed offers the same work in the same order (the engine's timing
  depends on the lengths and their order, not on the ids), and two seeds
  differ no more than two runs of one seed.

The cell file (``bench/cells/<cell>.json``) holds what sizes the engine
and the offered load: ``slots``, ``max_len``, ``prefill_chunk`` and, for
an open loop, ``rate_rps``.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np

__all__ = ["grid", "Traffic"]


def grid(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified draws of a length distribution: its quantiles at
    (i + 1/2) / n, rounded to whole tokens and clipped to [min, max]."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        v = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    elif spec["dist"] == "uniform":
        v = lo + q * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def exp_gaps(mean_s: float, n: int) -> np.ndarray:
    """``n`` stratified exponential inter-arrival gaps of mean ``mean_s``
    (a Poisson process's gaps, at quantiles (i + 1/2) / n)."""
    q = (np.arange(n) + 0.5) / n
    return -mean_s * np.log1p(-q)


class Traffic:
    """The requests of one run, in order, drawn from ``seed``.

    ``next()`` returns ``{"index", "prompt", "max_new", "due_s"}``;
    ``due_s`` is the arrival time after the window opens (open loop) or
    ``None`` (closed loop).  The stream never ends."""

    def __init__(self, mix: dict, cell: dict, vocab: int, seed: int):
        self.mix, self.cell, self.vocab = mix, cell, int(vocab)
        self.loop = mix["loop"]
        if self.loop not in ("closed", "open"):
            raise ValueError(f"unknown loop kind {self.loop!r}")
        if self.loop == "open" and not cell.get("rate_rps"):
            raise ValueError("an open loop needs the cell's rate_rps")
        self.block = int(mix.get("block", 32))
        self._rng = np.random.default_rng(seed)
        self._order = np.random.default_rng(0)
        self._prompts = grid(mix["prompt"], self.block)
        self._outputs = grid(mix["output"], self.block)
        if int(self._prompts.max()) + int(self._outputs.max()) + 1 \
                > int(cell["max_len"]):
            raise ValueError("the longest prompt and output do not fit the "
                             f"cell's max_len {cell['max_len']}")
        self._gaps = (exp_gaps(1.0 / float(cell["rate_rps"]), self.block)
                      if self.loop == "open" else None)
        self._queue: list = []
        self._index = 0
        self._clock = 0.0

    def _refill(self) -> None:
        rng, order = self._rng, self._order
        prompts = order.permutation(self._prompts)
        outputs = order.permutation(self._outputs)
        gaps = (order.permutation(self._gaps) if self._gaps is not None
                else None)
        for j in range(self.block):
            due = None
            if gaps is not None:
                self._clock += float(gaps[j])
                due = self._clock
            n = int(prompts[j])
            self._queue.append({
                "index": self._index,
                "prompt": rng.integers(0, self.vocab, size=n).tolist(),
                "max_new": int(outputs[j]),
                "due_s": due})
            self._index += 1

    def next(self) -> dict:
        if not self._queue:
            self._refill()
        return self._queue.pop(0)

    def peek_due(self) -> float:
        """The next request's due time (open loop)."""
        if not self._queue:
            self._refill()
        return self._queue[0]["due_s"]

    @property
    def backlog(self) -> int:
        """Closed loop: requests the queue keeps beyond those in service."""
        return int(self.cell["slots"])
