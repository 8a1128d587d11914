"""The jitted serving step: one decode step + greedy/temperature sampling,
with KV-cache shardings.  ``serve_step_fn`` is what the decode-shape dry-run
cells lower (one new token against a seq_len-deep cache);
``serve_step_sparse_fn`` is the ESPIM-format variant whose MLP projections
run through the fused batched chunked-ELL kernel (the paper's deployment:
decode from the compressed format)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import sparse_model
from repro.models import factory
from repro.sharding import partition

__all__ = ["serve_step_fn", "serve_step_sparse_fn", "make_serve_step",
           "prefill_fn", "sample_tokens"]


def sample_tokens(cfg: ModelConfig, last, temperature: float, rng=None):
    """Greedy/temperature sampling over one position's logits (B, V),
    vocab padding masked.  Returns (B,) int32.

    The caller owns the key: the engine splits a fresh subkey per step
    (``batch["rng"]``), so temperature sampling draws an independent
    perturbation every tick instead of replaying PRNGKey(0) forever.
    """
    last = last.astype(jnp.float32)
    if cfg.padded_vocab != cfg.vocab_size:
        pad = cfg.padded_vocab - cfg.vocab_size
        last = jnp.concatenate(
            [last[:, : cfg.vocab_size],
             jnp.full((last.shape[0], pad), -1e30)], axis=-1)
    if temperature > 0.0:
        key = rng if rng is not None else jax.random.PRNGKey(0)
        nxt = jax.random.categorical(key, last / temperature, axis=-1)
    else:
        nxt = jnp.argmax(last, axis=-1)
    return nxt.astype(jnp.int32)


@jax.named_scope("lm_head")
def _sample_next(cfg: ModelConfig, logits, batch: dict, temperature: float):
    """Sampling over the final position of decode logits (B, 1, V)."""
    nxt = sample_tokens(cfg, logits[:, -1, :], temperature,
                        batch.get("rng"))
    return nxt[:, None]


def serve_step_fn(cfg: ModelConfig, params, cache: dict, batch: dict,
                  temperature: float = 0.0):
    """Returns (next_tokens (B, 1), logits (B, 1, V), new_cache)."""
    logits, cache = factory.decode_step(cfg, params, cache, batch)
    return _sample_next(cfg, logits, batch, temperature), logits, cache


def serve_step_sparse_fn(cfg: ModelConfig, params, sparse: dict,
                         cache: dict, batch: dict,
                         temperature: float = 0.0, impl: str = "ref",
                         proj: dict | None = None):
    """ESPIM-format decode step: one scanned layer stack whose covered
    projections run from the width-bucketed pack groups — the fused QKV
    launch + static take, the packed O projection, the fused gate+up
    SpMV with its packed-order product, and the perm-composed down
    projection (``sparse`` from ``sparsify_model``; the
    ``sparsify_mlps`` preset keeps attention dense — DESIGN.md sections
    8/10).  When the packs were built with ``quant="int8"|"int4"`` the
    same scan consumes the quantized value planes (codes + per-row-group
    scale leaves) through the quantized kernels — section 9.

    Same contract as ``serve_step_fn``: (next_tokens, logits, new_cache).
    ``proj`` (``sparse_model.projection_arrays``) carries the pack
    buffers as a jit argument; ``None`` closes over those in ``sparse``.
    """
    logits, cache = sparse_model.decode_step_sparse(
        cfg, params, sparse, cache, batch, impl=impl, proj=proj)
    return _sample_next(cfg, logits, batch, temperature), logits, cache


def prefill_fn(cfg: ModelConfig, params, batch: dict):
    """Full-sequence forward (the prefill-shape cells lower this).  The
    serving TTFT path instead jits ``factory.prefill_chunk`` directly —
    see ``serve/prefill.ChunkedPrefiller``."""
    logits, _ = factory.apply_train(cfg, params, batch)
    return logits


def make_serve_step(cfg: ModelConfig, mesh, params_shapes, cache_shapes,
                    batch_shapes, donate_cache: bool = True):
    pspecs = partition.serve_param_pspecs(params_shapes, mesh)
    cspecs = partition.cache_pspecs(cache_shapes, mesh)
    bspecs = partition.batch_pspecs(batch_shapes, mesh)
    fn = partial(serve_step_fn, cfg)
    return jax.jit(
        fn,
        in_shardings=(partition.named(mesh, pspecs),
                      partition.named(mesh, cspecs),
                      partition.named(mesh, bspecs)),
        out_shardings=(None, None, partition.named(mesh, cspecs)),
        donate_argnums=(1,) if donate_cache else (),
    ), pspecs, cspecs, bspecs
