"""Seeded weights: what a checkpoint would hold, made from a seed on the
device.

The tree has the layout the serving program takes for a dense-family
decoder (layer leaves stacked on a leading layer axis).  Every leaf of
every layer comes from its own key, ``fold_in(fold_in(PRNGKey(seed),
leaf), layer)``, so the whole tree is one jitted call for serving, and
the reference can make any one layer again, alone, with the same values.

Scales: embedding 0.02, projections and the LM head 1/sqrt(fan_in), and
the two projections that write into the residual stream (attention out,
MLP down) a further 1/sqrt(2 x layers), the scaled initialisation of
GPT-2 and Megatron-LM that keeps each layer's update small beside the
stream, as in a trained model (with unscaled random layers, a 40-layer
bfloat16 forward pass magnifies rounding until greedy tokens are noise);
norm weights 1 + 0.1 N(0, 1) (not all ones, so a norm weight that is
dropped shows in the comparison).
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp

__all__ = ["padded_vocab", "head_dim", "layer_shapes", "make_params",
           "layer_params", "embed", "lm_head", "final_norm"]


def padded_vocab(m: dict) -> int:
    return -(-int(m["vocab_size"]) // 256) * 256


def head_dim(m: dict) -> int:
    return int(m.get("head_dim") or m["d_model"] // m["n_heads"])


def layer_shapes(m: dict) -> dict:
    """{(module, leaf): shape} of one decoder layer."""
    d, f, hd = int(m["d_model"]), int(m["d_ff"]), head_dim(m)
    h, kv = int(m["n_heads"]), int(m["n_kv_heads"])
    out = {("ln1", "w"): (d,), ("ln2", "w"): (d,),
           ("attn", "wq"): (d, h * hd), ("attn", "wk"): (d, kv * hd),
           ("attn", "wv"): (d, kv * hd), ("attn", "wo"): (h * hd, d)}
    if m["gated_mlp"]:
        out[("mlp", "w_gate")] = (d, f)
    out[("mlp", "w_up")] = (d, f)
    out[("mlp", "w_down")] = (f, d)
    return out


RESIDUAL_OUT = ("attn/wo", "mlp/w_down")


def _leaf(seed: int, name: str, layer, shape, dtype, n_layers: int = 1):
    key = jax.random.fold_in(jax.random.PRNGKey(seed),
                             zlib.crc32(name.encode()) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, layer)
    z = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("/w") and len(shape) == 1:          # a norm weight
        v = 1.0 + 0.1 * z
    elif name == "embed":
        v = 0.02 * z
    else:
        v = z * (1.0 / float(shape[0]) ** 0.5)
        if name in RESIDUAL_OUT:
            v = v * (1.0 / (2.0 * n_layers) ** 0.5)
    return v.astype(dtype)


def _layer(m: dict, seed: int, dtype, layer):
    out: dict = {}
    for (mod, leaf), shape in layer_shapes(m).items():
        out.setdefault(mod, {})[leaf] = _leaf(seed, f"{mod}/{leaf}", layer,
                                              shape, dtype, int(m["n_layers"]))
    return out


def embed(m: dict, seed: int, dtype):
    return _leaf(seed, "embed", 0, (padded_vocab(m), int(m["d_model"])),
                 dtype)


def lm_head(m: dict, seed: int, dtype):
    return _leaf(seed, "lm_head", 0, (int(m["d_model"]), padded_vocab(m)),
                 dtype)


def final_norm(m: dict, seed: int, dtype):
    return _leaf(seed, "final_norm/w", 0, (int(m["d_model"]),), dtype)


def _freeze(m: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.lru_cache(maxsize=None)
def _make_params_fn(frozen: tuple, seed: int):
    m = dict(frozen)
    dtype = jnp.dtype(m["param_dtype"])

    @jax.jit
    def fn():
        layers = jax.vmap(lambda l: _layer(m, seed, dtype, l))(
            jnp.arange(int(m["n_layers"])))
        p = {"embed": embed(m, seed, dtype), "layers": layers,
             "final_norm": {"w": final_norm(m, seed, dtype)}}
        if not m["tie_embeddings"]:
            p["lm_head"] = lm_head(m, seed, dtype)
        return p
    return fn


def make_params(m: dict, seed: int) -> dict:
    """The whole tree, on the device, in the served dtype: one call."""
    return _make_params_fn(_freeze(m), int(seed))()


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen: tuple, seed: int):
    m = dict(frozen)
    dtype = jnp.dtype(m["param_dtype"])
    return jax.jit(lambda l: _layer(m, seed, dtype, l))


def layer_params(m: dict, seed: int, layer: int) -> dict:
    """Layer ``layer`` alone, equal to ``make_params(...)["layers"]`` at
    that index."""
    return _layer_fn(_freeze(m), int(seed))(jnp.int32(layer))
