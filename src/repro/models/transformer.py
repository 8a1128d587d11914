"""Dense decoder-only transformer (GQA) — qwen1.5-110b, qwen2.5-14b,
nemotron-4-15b, granite-3-2b — and the shared attention building blocks
reused by the MoE / VLM / hybrid / enc-dec families.

Layer params are stacked along a leading layer axis and applied with
``jax.lax.scan`` (compile-time and HLO-size critical for the 80-layer
dry-runs); remat policy wraps the scan body.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L

__all__ = [
    "init_params", "forward", "init_cache", "decode_step", "prefill_chunk",
    "init_attn_layer", "attn_apply", "attn_decode_apply",
    "attn_decode_core", "attn_prefill_apply", "attn_prefill_core",
    "splice_rows",
    "init_mlp_layer", "mlp_apply", "remat_wrap", "stack_layer_init",
    "embed_tokens", "logits_from_hidden",
]


# --------------------------------------------------------------------------
# Shared building blocks
# --------------------------------------------------------------------------
def stack_layer_init(layer_init, key, n_layers: int, *args, **kw):
    """vmap a per-layer init over a split key -> stacked params."""
    keys = jax.random.split(key, n_layers)
    return jax.vmap(lambda k: layer_init(k, *args, **kw))(keys)


def init_attn_layer(key, cfg: ModelConfig):
    kq, kk, kv, ko, kb = jax.random.split(key, 5)
    d, hd = cfg.d_model, cfg.hd
    p = {
        "wq": L.init_dense(kq, d, cfg.n_heads * hd, cfg.dtype),
        "wk": L.init_dense(kk, d, cfg.n_kv_heads * hd, cfg.dtype),
        "wv": L.init_dense(kv, d, cfg.n_kv_heads * hd, cfg.dtype),
        "wo": L.init_dense(ko, cfg.n_heads * hd, d, cfg.dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), cfg.dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), cfg.dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), cfg.dtype)
    return p


def _qkv(cfg: ModelConfig, p, x):
    b, s, _ = x.shape
    q = L.dense(x, p["wq"], p.get("bq")).reshape(b, s, cfg.n_heads, cfg.hd)
    k = L.dense(x, p["wk"], p.get("bk")).reshape(b, s, cfg.n_kv_heads, cfg.hd)
    v = L.dense(x, p["wv"], p.get("bv")).reshape(b, s, cfg.n_kv_heads, cfg.hd)
    return q, k, v


def attn_apply(cfg: ModelConfig, p, x, positions, *, causal=True,
               positions3=None, kv_x=None):
    """Full attention over a sequence (train / prefill / cross).

    ``kv_x`` switches to cross-attention (keys/values from the encoder);
    RoPE is skipped for cross-attention and for learned-positions models.
    """
    b, s, _ = x.shape
    if kv_x is None:
        q, k, v = _qkv(cfg, p, x)
        if cfg.mrope and positions3 is not None:
            q, k = L.apply_mrope(q, k, positions3, cfg.rope_theta)
        elif not cfg.learned_pos:
            q, k = L.apply_rope(q, k, positions, cfg.rope_theta)
    else:
        bk, sk, _ = kv_x.shape
        q = L.dense(x, p["wq"], p.get("bq")).reshape(b, s, cfg.n_heads, cfg.hd)
        k = L.dense(kv_x, p["wk"], p.get("bk")).reshape(
            bk, sk, cfg.n_kv_heads, cfg.hd)
        v = L.dense(kv_x, p["wv"], p.get("bv")).reshape(
            bk, sk, cfg.n_kv_heads, cfg.hd)
    out = L.flash_attention(
        q, k, v, causal=causal, q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk
    )
    return L.dense(out.reshape(b, s, cfg.n_heads * cfg.hd), p["wo"])


def _quantize_kv(x):
    """(B, 1, KV, hd) -> (int8 values, (B, 1, KV) scales)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def attn_decode_core(cfg: ModelConfig, q, k, v, k_cache, v_cache, cache_len,
                     positions3=None, k_scale=None, v_scale=None):
    """RoPE + cache update + attention for one decode token, on
    *precomputed* q/k/v heads — the projection-agnostic middle of the
    attention step, shared by the dense path (``attn_decode_apply``) and
    the ESPIM packed-QKV path (``sparse_model``), which computes q/k/v
    through the fused QKV pack and applies the O projection itself.

    q: (B, 1, H, hd); k/v: (B, 1, KV, hd); caches (B, S_max, KV, hd).
    Returns (out (B, 1, H, hd) — pre-O-projection, k_cache, v_cache,
    k_scale, v_scale).
    """
    pos = cache_len.astype(jnp.int32)
    if cfg.mrope and positions3 is not None:
        q, k = L.apply_mrope(q, k, positions3, cfg.rope_theta)
    elif not cfg.learned_pos:
        q, k = L.apply_rope(q, k, pos[:, None], cfg.rope_theta)

    # Masked elementwise update instead of vmap(dynamic_update_slice):
    # shardable along every cache dim (batch, sequence, heads) with zero
    # resharding — a per-batch DUS on a sequence-sharded cache triggers
    # XLA's "involuntary full rematerialization" copies (hillclimb iter 1,
    # EXPERIMENTS.md section Perf).
    with jax.named_scope("kv_cache"):
        s_max = k_cache.shape[1]
        at_pos = (jnp.arange(s_max, dtype=jnp.int32)[None, :]
                  == pos[:, None])[..., None, None]      # (B, S, 1, 1)
        if k_scale is not None:
            kq, ks = _quantize_kv(k)
            vq, vs = _quantize_kv(v)
            k_cache = jnp.where(at_pos, kq, k_cache)
            v_cache = jnp.where(at_pos, vq, v_cache)
            k_scale = jnp.where(at_pos[..., 0], ks, k_scale)
            v_scale = jnp.where(at_pos[..., 0], vs, v_scale)
        else:
            k_cache = jnp.where(at_pos, k.astype(k_cache.dtype), k_cache)
            v_cache = jnp.where(at_pos, v.astype(v_cache.dtype), v_cache)
    out = L.attention_decode(q, k_cache, v_cache, pos + 1,
                             k_scale=k_scale, v_scale=v_scale)
    return out, k_cache, v_cache, k_scale, v_scale


def attn_decode_apply(cfg: ModelConfig, p, x, k_cache, v_cache, cache_len,
                      positions3=None, k_scale=None, v_scale=None):
    """One-token decode: update caches at ``cache_len``, attend over cache.

    x: (B, 1, D); k/v_cache: (B, S_max, KV, hd); cache_len: (B,) int32.
    With an int8 cache, (B, S_max, KV) scales ride along and fold into
    scores/probs exactly (hillclimb iter 6).
    Returns (out (B,1,D), k_cache, v_cache[, k_scale, v_scale]).
    """
    b = x.shape[0]
    q, k, v = _qkv(cfg, p, x)
    out, k_cache, v_cache, k_scale, v_scale = attn_decode_core(
        cfg, q, k, v, k_cache, v_cache, cache_len, positions3=positions3,
        k_scale=k_scale, v_scale=v_scale)
    out = L.dense(out.reshape(b, 1, cfg.n_heads * cfg.hd), p["wo"])
    return out, k_cache, v_cache, k_scale, v_scale


def splice_rows(cache, rows, start):
    """Write ``rows`` (B, C, ...) into ``cache`` (B, S, ...) at sequence
    rows start..start+C-1 (per-batch ``start`` (B,) int32).

    Masked gather + where rather than dynamic_update_slice for the same
    reason as the decode update: shardable along every cache dim with zero
    resharding.
    """
    s_max, c = cache.shape[1], rows.shape[1]
    pos = jnp.arange(s_max, dtype=jnp.int32)[None, :]
    in_chunk = (pos >= start[:, None]) & (pos < start[:, None] + c)
    idx = jnp.clip(pos - start[:, None], 0, c - 1)
    extra = (1,) * (cache.ndim - 2)
    gathered = jnp.take_along_axis(rows, idx.reshape(idx.shape + extra),
                                   axis=1)
    return jnp.where(in_chunk.reshape(in_chunk.shape + extra), gathered,
                     cache)


def attn_prefill_core(cfg: ModelConfig, q, k, v, k_cache, v_cache, start,
                      positions3=None, k_scale=None, v_scale=None):
    """RoPE + cache splice + attention for a prefill chunk on precomputed
    q/k/v heads — the prefill twin of ``attn_decode_core`` (same contract:
    the caller owns the QKV and O projections).

    q: (B, C, H, hd); k/v: (B, C, KV, hd); start: (B,) int32.  Returns
    (out (B, C, H, hd) — pre-O-projection, caches, scales).
    """
    c = q.shape[1]
    pos = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    if cfg.mrope and positions3 is not None:
        q, k = L.apply_mrope(q, k, positions3, cfg.rope_theta)
    elif not cfg.learned_pos:
        q, k = L.apply_rope(q, k, pos, cfg.rope_theta)
    with jax.named_scope("kv_cache"):
        if k_scale is not None:
            kq, ks = _quantize_kv(k)
            vq, vs = _quantize_kv(v)
            k_cache = splice_rows(k_cache, kq, start)
            v_cache = splice_rows(v_cache, vq, start)
            k_scale = splice_rows(k_scale, ks, start)
            v_scale = splice_rows(v_scale, vs, start)
        else:
            k_cache = splice_rows(k_cache, k.astype(k_cache.dtype), start)
            v_cache = splice_rows(v_cache, v.astype(v_cache.dtype), start)
    out = L.attention_prefill(q, k_cache, v_cache, pos,
                              k_scale=k_scale, v_scale=v_scale)
    return out, k_cache, v_cache, k_scale, v_scale


def attn_prefill_apply(cfg: ModelConfig, p, x, k_cache, v_cache, start,
                       positions3=None, k_scale=None, v_scale=None):
    """Chunked prefill: C tokens at absolute positions start..start+C-1.

    x: (B, C, D); k/v_cache: (B, S_max, KV, hd); start: (B,) int32.  The
    chunk's K/V are spliced into the caches and the chunk attends causally
    over the whole cache (earlier chunks included).  Trailing pad tokens of
    a partial final chunk write rows past the valid length — harmless: the
    causal mask hides them from valid queries and the engine drops them at
    page-splice time.  Returns (out (B, C, D), caches[, scales]).
    """
    b, c, _ = x.shape
    q, k, v = _qkv(cfg, p, x)
    out, k_cache, v_cache, k_scale, v_scale = attn_prefill_core(
        cfg, q, k, v, k_cache, v_cache, start, positions3=positions3,
        k_scale=k_scale, v_scale=v_scale)
    out = L.dense(out.reshape(b, c, cfg.n_heads * cfg.hd), p["wo"])
    return out, k_cache, v_cache, k_scale, v_scale


def init_mlp_layer(key, cfg: ModelConfig):
    k1, k2, k3 = jax.random.split(key, 3)
    d, f = cfg.d_model, cfg.d_ff
    if cfg.gated_mlp:
        return {
            "w_gate": L.init_dense(k1, d, f, cfg.dtype),
            "w_up": L.init_dense(k2, d, f, cfg.dtype),
            "w_down": L.init_dense(k3, f, d, cfg.dtype),
        }
    return {
        "w_up": L.init_dense(k1, d, f, cfg.dtype),
        "w_down": L.init_dense(k2, f, d, cfg.dtype),
    }


def mlp_apply(cfg: ModelConfig, p, x):
    if cfg.gated_mlp:
        return L.mlp_gated(x, p["w_gate"], p["w_up"], p["w_down"],
                           cfg.activation)
    return L.mlp_relu2(x, p["w_up"], p["w_down"], cfg.activation)


def _norm(cfg: ModelConfig, p, x):
    if cfg.norm == "layernorm":
        return L.layer_norm(x, p["w"], p["b"], cfg.norm_eps)
    return L.rms_norm(x, p["w"], cfg.norm_eps)


def init_norm(cfg: ModelConfig):
    p = {"w": jnp.ones((cfg.d_model,), cfg.dtype)}
    if cfg.norm == "layernorm":
        p["b"] = jnp.zeros((cfg.d_model,), cfg.dtype)
    return p


def remat_wrap(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


# --------------------------------------------------------------------------
# Dense decoder LM
# --------------------------------------------------------------------------
def _init_layer(key, cfg: ModelConfig):
    ka, km = jax.random.split(key)
    return {
        "ln1": init_norm(cfg),
        "attn": init_attn_layer(ka, cfg),
        "ln2": init_norm(cfg),
        "mlp": init_mlp_layer(km, cfg),
    }


def init_params(cfg: ModelConfig, key) -> dict:
    ke, kl, kh = jax.random.split(key, 3)
    params = {
        "embed": L.init_dense(ke, cfg.padded_vocab, cfg.d_model, cfg.dtype,
                              scale=0.02),
        "layers": stack_layer_init(_init_layer, kl, cfg.n_layers, cfg),
        "final_norm": init_norm(cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_dense(kh, cfg.d_model, cfg.padded_vocab,
                                         cfg.dtype)
    return params


def embed_tokens(cfg: ModelConfig, params, tokens):
    return jnp.take(params["embed"], tokens, axis=0).astype(cfg.cdtype)


@jax.named_scope("lm_head")
def logits_from_hidden(cfg: ModelConfig, params, h):
    h = _norm(cfg, params["final_norm"], h)
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", h, params["embed"].astype(h.dtype))
    return L.dense(h, params["lm_head"])


def forward(cfg: ModelConfig, params, batch: dict) -> jnp.ndarray:
    """Train/prefill forward -> logits (B, S, V).

    batch: tokens (B, S) [+ positions (B, S)], optionally
    embeddings/vis_mask/positions3 for the VLM flavour.
    """
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    positions3 = batch.get("positions3")
    h = embed_tokens(cfg, params, tokens)
    if "embeddings" in batch:  # VLM stub frontend: splice patch embeddings
        vis = batch["embeddings"].astype(h.dtype)
        vis_mask = batch["vis_mask"][..., None]
        h = jnp.where(vis_mask, vis, h)

    def body(h, lp):
        out = h + attn_apply(cfg, lp["attn"], _norm(cfg, lp["ln1"], h),
                             positions, positions3=positions3)
        out = out + mlp_apply(cfg, lp["mlp"], _norm(cfg, lp["ln2"], out))
        return out, None

    h, _ = jax.lax.scan(remat_wrap(cfg, body), h, params["layers"])
    return logits_from_hidden(cfg, params, h)


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int) -> dict:
    shape = (cfg.n_layers, batch_size, max_len, cfg.n_kv_heads, cfg.hd)
    if cfg.kv_cache_dtype == "int8":
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:-1], jnp.bfloat16),
            "v_scale": jnp.zeros(shape[:-1], jnp.bfloat16),
            "len": jnp.zeros((batch_size,), jnp.int32),
        }
    return {
        "k": jnp.zeros(shape, cfg.cdtype),
        "v": jnp.zeros(shape, cfg.cdtype),
        "len": jnp.zeros((batch_size,), jnp.int32),
    }


def decode_step(cfg: ModelConfig, params, cache: dict, batch: dict):
    """One decode step: tokens (B, 1) -> logits (B, 1, V), updated cache."""
    tokens = batch["tokens"]
    h = embed_tokens(cfg, params, tokens)
    positions3 = batch.get("positions3")
    quant = "k_scale" in cache
    dummy = jnp.zeros((cfg.n_layers,), jnp.bfloat16)

    def body(carry, xs):
        h = carry
        lp, kc, vc, ks, vs = xs
        a, kc, vc, ks, vs = attn_decode_apply(
            cfg, lp["attn"], _norm(cfg, lp["ln1"], h), kc, vc, cache["len"],
            positions3=positions3,
            k_scale=ks if quant else None,
            v_scale=vs if quant else None)
        out = h + a
        out = out + mlp_apply(cfg, lp["mlp"], _norm(cfg, lp["ln2"], out))
        return out, (kc, vc, ks, vs)

    h, (k_new, v_new, ks_new, vs_new) = jax.lax.scan(
        body, h, (params["layers"], cache["k"], cache["v"],
                  cache.get("k_scale", dummy), cache.get("v_scale", dummy))
    )
    logits = logits_from_hidden(cfg, params, h)
    new_cache = {"k": k_new, "v": v_new, "len": cache["len"] + 1}
    if quant:
        new_cache["k_scale"] = ks_new
        new_cache["v_scale"] = vs_new
    return logits, new_cache


def prefill_chunk(cfg: ModelConfig, params, cache: dict, batch: dict):
    """One chunked-prefill step: tokens (B, C) land at absolute positions
    cache["len"]..cache["len"]+C-1.

    ``batch["n_valid"]`` (B,) marks how many leading chunk tokens are real
    (a partial final chunk is padded up to the fixed jit'd width C); ``len``
    advances by ``n_valid`` only.  Returns full-chunk logits (B, C, V) and
    the updated cache — the caller reads logits at n_valid-1 for the first
    generated token.
    """
    tokens = batch["tokens"]
    start = cache["len"]
    n_valid = batch.get("n_valid")
    if n_valid is None:
        n_valid = jnp.full_like(start, tokens.shape[1])
    h = embed_tokens(cfg, params, tokens)
    positions3 = batch.get("positions3")
    quant = "k_scale" in cache
    dummy = jnp.zeros((cfg.n_layers,), jnp.bfloat16)

    def body(carry, xs):
        h = carry
        lp, kc, vc, ks, vs = xs
        a, kc, vc, ks, vs = attn_prefill_apply(
            cfg, lp["attn"], _norm(cfg, lp["ln1"], h), kc, vc, start,
            positions3=positions3,
            k_scale=ks if quant else None,
            v_scale=vs if quant else None)
        out = h + a
        out = out + mlp_apply(cfg, lp["mlp"], _norm(cfg, lp["ln2"], out))
        return out, (kc, vc, ks, vs)

    h, (k_new, v_new, ks_new, vs_new) = jax.lax.scan(
        body, h, (params["layers"], cache["k"], cache["v"],
                  cache.get("k_scale", dummy), cache.get("v_scale", dummy))
    )
    logits = logits_from_hidden(cfg, params, h)
    new_cache = {"k": k_new, "v": v_new, "len": start + n_valid}
    if quant:
        new_cache["k_scale"] = ks_new
        new_cache["v_scale"] = vs_new
    return logits, new_cache
