"""True chunked prefill: C prompt tokens per jitted call.

The seed engine prefills by replaying the prompt token-by-token through
the decode path — TTFT scales as O(prompt_len) jitted decode steps.  The
prefiller instead runs the family's ``prefill_chunk`` (or the ESPIM-format
sparse variant) over fixed-width chunks: ceil(prompt_len / C) jitted calls
to first token, with the final partial chunk padded up to C (pad positions
are masked so every recurrent/attention state lands exactly where replay
would put it — see the per-family ``prefill_chunk`` docstrings).

The ESPIM engine applies the paper's flexible dense/sparse datapath
(Section III-I) per serving phase: the GEMM-shaped prefill chunk runs the
pruned *dense* copies of every covered projection — attention included
when the pack groups cover the whole layer (``sparsify_model``) —
while decode runs the packed MV kernels (memory-bound phase, the
format's whole point) — see DESIGN.md sections 8/10.

Each slot prefills into a private (B=1) scratch cache; after every chunk
the freshly written K/V rows are sliced out for the engine to splice into
the slot's pages (paged) or cache rows (contiguous).  The scratch cache
starts from one shared zero prototype — jax arrays are immutable, so
"resetting" a slot's scratch cache is a pointer copy, not an allocation.
The final chunk also yields the recurrent state leaves (ssm / conv / wkv /
token-shift) and the last valid position's logits, from which the engine
samples the first generated token — TTFT therefore needs no extra decode
step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import sparse_model
from repro.models import factory

__all__ = ["ChunkedPrefiller"]


class ChunkedPrefiller:
    def __init__(self, cfg: ModelConfig, chunk: int, max_len: int,
                 seq_names, state_names, sparse: dict | None = None,
                 impl: str = "ref"):
        self.cfg = cfg
        self.chunk = chunk
        # scratch length rounded up so the last chunk's pad rows fit
        self.scratch_len = -(-max_len // chunk) * chunk
        self.proto = factory.init_cache(cfg, 1, self.scratch_len)
        self.seq_names = list(seq_names)
        self.state_names = list(state_names)
        if sparse is None:
            self._proj = None
            self._fn = jax.jit(
                lambda p, x, c, b: factory.prefill_chunk(cfg, p, c, b))
        else:
            # the pruned dense copies ride in as an argument (device
            # buffers, never constants of the compiled chunk step)
            self._proj = sparse_model.projection_arrays(sparse, "dense")
            self._fn = jax.jit(
                lambda p, x, c, b: sparse_model.prefill_chunk_sparse(
                    cfg, p, sparse, c, b, impl=impl, proj=x))

    def run_chunk(self, params, pf_cache, prompt, pos: int):
        """Prefill one chunk starting at ``pos``.  Returns (full-chunk
        logits (1, C, V), new scratch cache, n_valid)."""
        c = self.chunk
        n_valid = min(c, len(prompt) - pos)
        tokens = np.zeros((1, c), np.int32)
        tokens[0, :n_valid] = prompt[pos : pos + n_valid]
        batch = {"tokens": jnp.asarray(tokens),
                 "n_valid": jnp.asarray([n_valid], jnp.int32)}
        logits, pf_cache = self._fn(params, self._proj, pf_cache,
                                    batch)
        return logits, pf_cache, n_valid

    def chunk_rows(self, pf_cache: dict, pos: int) -> dict:
        """The K/V rows the chunk just wrote: {name: (Lx, C, ...)}."""
        return {n: pf_cache[n][:, 0, pos : pos + self.chunk]
                for n in self.seq_names}

    def state_rows(self, pf_cache: dict) -> dict:
        """Recurrent state leaves after the final chunk: {name: (Lx, ...)}
        with the B=1 dim squeezed out."""
        return {n: pf_cache[n][:, 0] for n in self.state_names}
