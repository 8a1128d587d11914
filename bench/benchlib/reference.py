"""The plain float32 reference: a dense-family decoder in straightforward
``jax.numpy``, with no kernel, cache or batching of the program's.

It follows the published layer equations of the configurations it runs
(RMSNorm, rotary position embeddings in the half-split form, grouped-query
attention with a causal mask and 1/sqrt(head_dim) scaling, a gated SiLU
or a squared-ReLU MLP, tied or untied LM head) and departs from them only
where the configuration file says so.  It imports nothing of the program
and takes nothing the program made: it makes each layer's weights again
from the seed (``weights.layer_params``) and prunes them itself, by the
magnitude rule the configuration states: in each projection matrix of
each layer, the ``round(sparsity * size)`` smallest magnitudes, and every
entry tied with the largest of them, are zero.  Every matrix product runs
at ``precision=HIGHEST``.

``readings`` also computes the same logits with the weights or the
activations at another precision, teacher-forced on the same tokens:
the control (``bits=4``: every pruned projection rounded to int4, one
absmax scale per group of 128 output features, one step below the int8
the configuration states) and a witness of the program's own precision
(``bits=8`` and bfloat16 activations, rounded where the program stores
them).  Each reads, at every position, how far the reference's logit of
the token it puts first lies below the reference's best.

It runs layer by layer over a batch of whole sequences (prompt plus the
served tokens, teacher-forced), so only one layer's float32 weights are
on the device at a time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import weights as W

__all__ = ["prune", "quantize", "forward_logits", "served_gaps",
           "readings", "gap_stats"]

HI = jax.lax.Precision.HIGHEST
PROJECTIONS = {"attn": ("wq", "wk", "wv", "wo"),
               "mlp": ("w_gate", "w_up", "w_down")}


@functools.partial(jax.jit, static_argnums=1)
def prune(w, sparsity: float):
    """Zero the ``round(sparsity * w.size)`` smallest |w| and every entry
    tied with the largest of them.  The threshold is the k-th smallest
    magnitude, found exactly by bisection on the float32 bit patterns
    (non-negative floats order as their bits)."""
    k = int(round(sparsity * w.size))
    if k == 0:
        return w
    bits = jax.lax.bitcast_convert_type(jnp.abs(w).astype(jnp.float32),
                                        jnp.int32)

    def body(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        enough = jnp.sum(bits <= mid) >= k
        return jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi)

    _, thresh = jax.lax.fori_loop(0, 32, body,
                                  (jnp.int32(0), jnp.int32(0x7F800000)))
    return jnp.where(bits <= thresh, jnp.zeros_like(w), w)


def quantize(w, bits: int, group: int = 128):
    """``w`` (in, out) rounded to ``bits``-bit symmetric codes, one absmax
    scale per group of ``gcd(group, out)`` output features, dequantized."""
    qmax = 2 ** (bits - 1) - 1
    d_in, d_out = w.shape
    g = int(np.gcd(group, d_out))
    v = w.reshape(d_in, d_out // g, g)
    s = jnp.max(jnp.abs(v), axis=(0, 2), keepdims=True) / qmax
    s = jnp.where(s > 0, s, 1.0)
    return (jnp.clip(jnp.round(v / s), -qmax, qmax) * s).reshape(d_in, d_out)


def _rounder(act: str):
    """Rounds a float32 activation to ``act`` and back (identity for
    float32)."""
    if act == "float32":
        return lambda x: x
    dt = jnp.dtype(act)
    return lambda x: x.astype(dt).astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (K, S, heads, hd) at positions 0..S-1, half-split rotation."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _act(name):
    if name == "silu":
        return jax.nn.silu
    if name == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(f"the reference has no activation {name!r}")


def _layer(m: dict, h, lp, act: str = "float32"):
    k_, s, d = h.shape
    nh, kv, hd = int(m["n_heads"]), int(m["n_kv_heads"]), W.head_dim(m)
    eps = float(m["norm_eps"])
    rd = _rounder(act)
    x = rd(_rms(h, lp["ln1"]["w"], eps))
    a = lp["attn"]
    q = jnp.einsum("ksd,df->ksf", x, a["wq"], precision=HI)
    k = jnp.einsum("ksd,df->ksf", x, a["wk"], precision=HI)
    v = jnp.einsum("ksd,df->ksf", x, a["wv"], precision=HI)
    q = rd(_rope(q.reshape(k_, s, nh, hd), float(m["rope_theta"])))
    k = rd(_rope(k.reshape(k_, s, kv, hd), float(m["rope_theta"])))
    v = rd(v.reshape(k_, s, kv, hd))
    q = q.reshape(k_, s, kv, nh // kv, hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def attend(qkv):                        # one sequence at a time
        q1, k1, v1 = qkv
        sc = jnp.einsum("qgrh,kgh->grqk", q1, k1, precision=HI) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgh->qgrh", p, v1, precision=HI)

    o = rd(jax.lax.map(attend, (q, k, v)))
    h = rd(h + jnp.einsum("ksf,fd->ksd", o.reshape(k_, s, nh * hd),
                          a["wo"], precision=HI))
    x = rd(_rms(h, lp["ln2"]["w"], eps))
    mp, f = lp["mlp"], _act(m["activation"])
    up = jnp.einsum("ksd,df->ksf", x, mp["w_up"], precision=HI)
    if m["gated_mlp"]:
        up = f(jnp.einsum("ksd,df->ksf", x, mp["w_gate"],
                          precision=HI)) * up
    else:
        up = f(up)
    return rd(h + jnp.einsum("ksf,fd->ksd", rd(up), mp["w_down"],
                             precision=HI))


def _frozen(m):
    return W._freeze(m)


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen, seed: int, sparsity: float, projections: str,
              bits: int | None, act: str):
    m = dict(frozen)
    pruned = {mod: names for mod, names in PROJECTIONS.items()
              if projections == "all" or projections == mod}

    @jax.jit
    def fn(h, layer):
        lp = jax.tree.map(lambda w: w.astype(jnp.float32),
                          W._layer(m, seed, jnp.dtype(m["param_dtype"]),
                                   layer))
        for mod, names in pruned.items():
            for n in names:
                if n in lp[mod]:
                    lp[mod][n] = prune(lp[mod][n], sparsity)
                    if bits:
                        lp[mod][n] = quantize(lp[mod][n], bits)
        return _layer(m, h, lp, act)
    return fn


@functools.lru_cache(maxsize=None)
def _head_fn(frozen, seed: int, act: str):
    m = dict(frozen)
    dt = jnp.dtype(m["param_dtype"])

    @jax.jit
    def fn(h, idx):
        """h (K, S, D); idx (K, M) positions -> logits (K, M, vocab)."""
        hs = jnp.take_along_axis(h, idx[..., None], axis=1)
        hs = _rounder(act)(_rms(
            hs, W.final_norm(m, seed, dt).astype(jnp.float32),
            float(m["norm_eps"])))
        if m["tie_embeddings"]:
            w = W.embed(m, seed, dt).astype(jnp.float32).T
        else:
            w = W.lm_head(m, seed, dt).astype(jnp.float32)
        out = jnp.einsum("kmd,dv->kmv", hs, w, precision=HI)
        return out[..., : int(m["vocab_size"])]
    return fn


def forward_logits(m: dict, seed: int, sparsity: float, projections: str,
                   tokens, idx, bits: int | None = None,
                   act: str = "float32"):
    """Logits at positions ``idx`` (K, M) of the sequences ``tokens``
    (K, S), over the logical vocabulary: a device array.  ``bits`` rounds
    every pruned projection (``quantize``); ``act`` rounds the
    activations."""
    fz = _frozen(m)
    dt = jnp.dtype(m["param_dtype"])
    emb = jax.jit(lambda t: jnp.take(W.embed(m, seed, dt), t, axis=0
                                     ).astype(jnp.float32))
    h = emb(jnp.asarray(tokens, jnp.int32))
    fn = _layer_fn(fz, int(seed), float(sparsity), projections, bits, act)
    for layer in range(int(m["n_layers"])):
        h = fn(h, jnp.int32(layer))
    return _head_fn(fz, int(seed), act)(h, jnp.asarray(idx, jnp.int32))


def _teacher(rows: list, length: int):
    """Sequences padded to ``length`` (the causal mask keeps the padding
    out of every compared position), the positions that predicted each
    served token, and the served tokens."""
    k = len(rows)
    width = max(len(o) for _, o in rows)
    tokens = np.zeros((k, length), np.int32)
    idx = np.zeros((k, width), np.int32)
    served = np.zeros((k, width), np.int32)
    for r, (prompt, out) in enumerate(rows):
        seq = list(prompt) + list(out)
        if len(seq) - 1 > length:
            raise ValueError(f"row of {len(seq)} tokens exceeds {length}")
        tokens[r, : len(seq) - 1] = seq[:-1]
        n = len(out)
        idx[r, :n] = len(prompt) - 1 + np.arange(n)
        served[r, :n] = out
    return tokens, idx, served


BLOCK = 4          # rows the reference runs at a time


def readings(m: dict, seed: int, sparsity: float, projections: str,
             rows: list, length: int, variants: dict | None = None) -> dict:
    """For each ``(prompt, output)`` in ``rows``, by how much a token's
    reference logit lies below the reference's best logit at each
    position of the output.  ``"served"``: the served tokens; each entry
    of ``variants`` (name -> ``forward_logits`` keywords): the token that
    variant puts first.  Runs ``BLOCK`` rows at a time.  Returns {name:
    one float64 array per row}."""
    out: dict = {}
    for b in range(0, len(rows), BLOCK):
        got = _readings(m, seed, sparsity, projections, rows[b: b + BLOCK],
                        length, variants or {})
        for k, g in got.items():
            out.setdefault(k, []).extend(g)
    return out


def _readings(m, seed, sparsity, projections, rows, length, variants):
    tokens, idx, served = _teacher(rows, length)
    ref = forward_logits(m, seed, sparsity, projections, tokens, idx)
    best = ref.max(-1)

    def below(tok):
        got = jnp.take_along_axis(ref, jnp.asarray(tok)[..., None], -1)
        return np.asarray(best - got[..., 0], np.float64)
    out = {"served": below(served)}
    for name, kw in variants.items():
        lg = forward_logits(m, seed, sparsity, projections, tokens, idx, **kw)
        out[name] = below(jnp.argmax(lg, -1))
        del lg
    return {k: [g[r, : len(o)] for r, (_, o) in enumerate(rows)]
            for k, g in out.items()}


def served_gaps(m: dict, seed: int, sparsity: float, projections: str,
                rows: list, length: int) -> list:
    """``readings(...)["served"]``: one float64 array per row."""
    return readings(m, seed, sparsity, projections, rows, length)["served"]


def gap_stats(gaps: list) -> dict:
    """The numbers a comparison reads from per-row gap arrays: the widest
    gap, the mean gap over every token, and the share of tokens more
    than 0.1 below the best."""
    allg = np.concatenate(gaps)
    return {"widest_gap": float(allg.max()), "mean_gap": float(allg.mean()),
            "share_over_0.1": float((allg > 0.1).mean())}
