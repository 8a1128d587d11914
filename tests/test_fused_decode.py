"""Parity tests for every step of the fused sparse decode stack (PR 3):
scan vs Python loop, fused gate+up vs separate SpMVs, perm-folded output
vs scatter, vectorized vs looped kernel gather, and the width-bucketed
pack round-trip + padding guarantees."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dep: fall back to a seeded random sweep
    from _hypothesis_fallback import given, settings, st

from repro.configs.registry import get_config
from repro.core import sparse_model as SM
from repro.core.pruning import magnitude_prune
from repro.core.sparse_format import (bucketed_stack_to_dense,
                                      pack_bucketed_stack, pack_ell_chunked)
from repro.core.sparse_model import (bucket_planes, decode_step_sparse,
                                     prefill_chunk_sparse, sparse_stats,
                                     sparsify_mlps)
from repro.kernels import ops, ref
from repro.kernels.espim_spmv import espim_spmv_batched_pallas
from repro.models import factory

KEY = jax.random.PRNGKey(0)


def _setup(arch="llama7b-espim", sparsity=0.9, **kw):
    cfg = get_config(arch, reduced=True)
    params = factory.init_params(cfg, KEY)
    sparse = sparsify_mlps(cfg, params, sparsity, **kw)
    return cfg, params, sparse


# --------------------------------------------------------------------------
# 1) scanned layer loop == Python loop (fp32-accumulation tolerance)
# --------------------------------------------------------------------------
def test_scanned_decode_matches_python_loop():
    cfg, params, sparse = _setup()
    B, S = 2, 5
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    cache_s = factory.init_cache(cfg, B, S + 2)
    cache_u = factory.init_cache(cfg, B, S + 2)
    scan_fn = jax.jit(lambda p, c, b: decode_step_sparse(cfg, p, sparse,
                                                         c, b))
    loop_fn = jax.jit(lambda p, c, b: decode_step_sparse(cfg, p, sparse,
                                                         c, b, unroll=True))
    for i in range(S):
        batch = {"tokens": toks[:, i:i + 1]}
        lg_s, cache_s = scan_fn(params, cache_s, batch)
        lg_u, cache_u = loop_fn(params, cache_u, batch)
        np.testing.assert_allclose(np.asarray(lg_s), np.asarray(lg_u),
                                   rtol=1e-5, atol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=1e-5, atol=1e-5),
        cache_s, cache_u)


def test_scanned_prefill_matches_python_loop():
    cfg, params, sparse = _setup()
    toks = jax.random.randint(KEY, (1, 6), 0, cfg.vocab_size)
    batch = {"tokens": toks, "n_valid": jnp.asarray([6], jnp.int32)}
    cache_s = factory.init_cache(cfg, 1, 8)
    cache_u = factory.init_cache(cfg, 1, 8)
    lg_s, _ = prefill_chunk_sparse(cfg, params, sparse, cache_s, batch,
                                   proj_path="kernel")
    lg_u, _ = prefill_chunk_sparse(cfg, params, sparse, cache_u, batch,
                                   proj_path="kernel", unroll=True)
    np.testing.assert_allclose(np.asarray(lg_s), np.asarray(lg_u),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# 2) fused gate+up == two separate SpMV calls on per-projection packs
# --------------------------------------------------------------------------
def test_fused_gateup_matches_separate_spmv():
    cfg, params, sparse = _setup(row_tile=32)
    gu = sparse["gateup"]
    l = 1
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((cfg.d_model, 5)), jnp.float32)

    # fused: one SpMV per bucket, halves split in packed order, then
    # mapped back to logical rows for the comparison
    packed = []
    for gi, rg in enumerate(gu["bucket_rows"]):
        vals, cols = bucket_planes(gu, gi)
        yp = ops.espim_spmv_batched(vals[l], cols[l], x,
                                    chunk_cols=gu["chunk_cols"], impl="ref")
        packed.append((yp[:rg], yp[rg:]))
    gate_p = jnp.concatenate([g for g, _ in packed], axis=0)
    up_p = jnp.concatenate([u for _, u in packed], axis=0)
    inv = gu["inv_perm"][l]
    fused_gate = jnp.take(gate_p, inv, axis=0)
    fused_up = jnp.take(up_p, inv, axis=0)

    # separate: each projection packed on its own, two kernel launches
    for name, got in (("w_gate", fused_gate), ("w_up", fused_up)):
        w = np.asarray(sparse[f"{name}_pruned"][l], np.float32).T
        pack = pack_ell_chunked(w, chunk_cols=ops.DEFAULT_CHUNK_COLS)
        yp = ops.espim_spmv_batched(jnp.asarray(pack.values),
                                    jnp.asarray(pack.cols, jnp.int32), x,
                                    chunk_cols=pack.chunk_cols, impl="ref")
        want = ref.scatter_rows_ref(yp, jnp.asarray(pack.perm), pack.n_rows)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got), w @ np.asarray(x),
                                   rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# 3) perm folded into the pack == runtime scatter_rows_ref
# --------------------------------------------------------------------------
def test_perm_folded_output_matches_scatter():
    cfg, params, sparse = _setup()
    dn = sparse["down"]
    l = 2
    rng = np.random.default_rng(5)
    yd = jnp.asarray(rng.standard_normal((dn["r_pad"], 4)), jnp.float32)
    folded = jnp.take(yd, dn["inv_perm"][l], axis=0)
    scattered = ref.scatter_rows_ref(yd, dn["perm"][l], dn["n_rows"])
    np.testing.assert_array_equal(np.asarray(folded), np.asarray(scattered))


def test_down_cols_precomposed_with_gateup_order():
    """End-to-end perm folding: the fused MLP (no scatter anywhere) must
    equal the dense pruned MLP."""
    cfg, params, sparse = _setup(row_tile=32)
    rng = np.random.default_rng(7)
    hn = jnp.asarray(rng.standard_normal((2, 3, cfg.d_model)), jnp.float32)
    bufs = jax.tree.map(lambda x: x[0], SM._scan_bufs(sparse))
    got = SM._fused_mlp(cfg, sparse, bufs, hn, "ref")
    want = SM._pruned_mlp(
        cfg, sparse,
        {n: sparse[f"{n}_pruned"][0] for n in ("w_gate", "w_up", "w_down")},
        hn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# 4) vectorized block gather == old fori_loop kernel
# --------------------------------------------------------------------------
@pytest.mark.parametrize("b,chunk_cols", [(1, 128), (8, 64), (16, 512)])
def test_vectorized_gather_matches_loop_kernel(b, chunk_cols):
    rng = np.random.default_rng(11)
    w = magnitude_prune(rng.standard_normal((128, 300)).astype(np.float32),
                        0.85)
    pack = pack_ell_chunked(w, chunk_cols=chunk_cols)
    vals = jnp.asarray(pack.values)
    cols = jnp.asarray(pack.cols, jnp.int32)
    x = jnp.asarray(rng.standard_normal((300, b)), jnp.float32)
    block = espim_spmv_batched_pallas(vals, cols, x,
                                      chunk_cols=pack.chunk_cols,
                                      block_r=128, block_l=32,
                                      gather="block")
    loop = espim_spmv_batched_pallas(vals, cols, x,
                                     chunk_cols=pack.chunk_cols,
                                     block_r=128, block_l=32, gather="loop")
    want = ref.espim_spmv_batched_chunked_ref(vals, cols, x, pack.chunk_cols)
    np.testing.assert_allclose(np.asarray(block), np.asarray(loop),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(block), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# 5) width-bucketed pack: round-trip property + padding guarantees
# --------------------------------------------------------------------------
@settings(max_examples=15, deadline=None)
@given(r=st.integers(2, 120), c=st.integers(1, 150), s=st.floats(0.0, 0.95),
       halves=st.integers(1, 2), layers=st.integers(1, 3),
       n_buckets=st.integers(1, 4), seed=st.integers(0, 999))
def test_bucketed_stack_roundtrip_property(r, c, s, halves, layers,
                                           n_buckets, seed):
    rng = np.random.default_rng(seed)
    mats = [[magnitude_prune(
        rng.standard_normal((r, c)).astype(np.float32), s)
        for _ in range(layers)] for _ in range(halves)]
    pack = pack_bucketed_stack(mats, row_tile=32, chunk_cols=64,
                               n_buckets=n_buckets)
    for l in range(layers):
        for h in range(halves):
            np.testing.assert_allclose(
                bucketed_stack_to_dense(pack, l, h), mats[h][l])
    assert sum(pack.bucket_rows) == pack.r_pad
    assert pack.nnz == sum(int((m != 0).sum()) for hh in mats for m in hh)
    # bucketing never pads worse than the single global width
    assert pack.plan.padded_slots <= pack.plan.single_bucket_slots


def test_bucketed_pad_frac_llama7b_shape():
    """Acceptance: on the full LLaMA-7B projection shape at the paper's
    90% sparsity, width bucketing brings pad_frac from the global-width
    ~15% to <= 8%."""
    rng = np.random.default_rng(0)
    w = magnitude_prune(rng.standard_normal((4096, 4096)).astype(np.float32),
                        0.9)
    pack = pack_bucketed_stack([[w]], row_tile=128, chunk_cols=4096,
                               n_buckets=4)
    single = 1 - pack.nnz / (pack.plan.single_bucket_slots * pack.n_chunks)
    assert single > 0.10          # the global-width layout wastes ~15%
    assert pack.pad_frac <= 0.08  # bucketing recovers it
    assert pack.pad_frac < single


def test_sparse_stats_reports_per_layer_and_per_projection():
    cfg, params, sparse = _setup(row_tile=32)
    stats = sparse_stats(sparse)
    for name in ("w_gate", "w_up", "w_down", "gateup", "down", "total"):
        assert name in stats, name
    for proj in ("gateup", "down"):
        per_layer = stats[proj]["pad_frac_per_layer"]
        assert len(per_layer) == cfg.n_layers
        assert stats[proj]["pad_frac"] <= (
            stats[proj]["single_bucket_pad_frac"] + 1e-9)


def test_non_gated_mlp_decode_matches_pruned_dense():
    """halves == 1 (nemotron: no gate projection, squared-ReLU)."""
    cfg, params, sparse = _setup(arch="nemotron-4-15b", sparsity=0.85)
    assert not sparse["gated"]
    pruned = jax.tree.map(lambda x: x, params)
    for name in ("w_up", "w_down"):
        pruned["layers"]["mlp"][name] = sparse[f"{name}_pruned"]
    toks = jax.random.randint(KEY, (2, 1), 0, cfg.vocab_size)
    cache_d = factory.init_cache(cfg, 2, 4)
    cache_s = factory.init_cache(cfg, 2, 4)
    lg_d, _ = factory.decode_step(cfg, pruned, cache_d, {"tokens": toks})
    lg_s, _ = decode_step_sparse(cfg, params, sparse, cache_s,
                                 {"tokens": toks})
    err = float(jnp.abs(lg_d - lg_s).max() / jnp.abs(lg_d).max())
    assert err < 5e-4, err


# --------------------------------------------------------------------------
# 6) prefill datapath flexibility (Section III-I): GEMM path == MV path
# --------------------------------------------------------------------------
def test_prefill_dense_path_matches_kernel_path():
    cfg, params, sparse = _setup()
    toks = jax.random.randint(KEY, (2, 4), 0, cfg.vocab_size)
    batch = {"tokens": toks, "n_valid": jnp.asarray([4, 4], jnp.int32)}
    cache_d = factory.init_cache(cfg, 2, 6)
    cache_k = factory.init_cache(cfg, 2, 6)
    lg_d, _ = prefill_chunk_sparse(cfg, params, sparse, cache_d, batch,
                                   proj_path="dense")
    lg_k, _ = prefill_chunk_sparse(cfg, params, sparse, cache_k, batch,
                                   proj_path="kernel")
    err = float(jnp.abs(lg_d - lg_k).max() / jnp.abs(lg_d).max())
    assert err < 5e-5, err


# --------------------------------------------------------------------------
# 7) env overrides for the dispatch (ESPIM_IMPL / ESPIM_FORCE_INTERPRET)
# --------------------------------------------------------------------------
def test_env_impl_override(monkeypatch):
    monkeypatch.delenv(ops.ENV_IMPL, raising=False)
    assert ops.provenance()["impl"] == "pallas"
    assert ops.provenance(impl="ref")["impl"] == "ref"
    monkeypatch.setenv(ops.ENV_IMPL, "ref")
    # the env pin wins over per-call arguments — that is its purpose
    assert ops.provenance(impl="pallas")["impl"] == "ref"

    # a plain (2-D) ELL pack rejects impl="pallas"; with the env pinned to
    # "ref" the same call must dispatch to the reference instead of raising
    rng = np.random.default_rng(1)
    w = magnitude_prune(rng.standard_normal((32, 64)).astype(np.float32),
                        0.8)
    from repro.core.sparse_format import pack_ell
    pack = pack_ell(w, row_tile=8)
    vals = jnp.asarray(pack.values)
    cols = jnp.asarray(pack.cols, jnp.int32)
    x = jnp.asarray(rng.standard_normal(64), jnp.float32)
    y = ops.espim_spmv(vals, cols, x, impl="pallas")
    assert y.shape == (pack.r_pad,)
    monkeypatch.delenv(ops.ENV_IMPL)
    with pytest.raises(ValueError, match="column-chunked"):
        ops.espim_spmv(vals, cols, x, impl="pallas")


def test_env_force_interpret(monkeypatch):
    monkeypatch.setenv(ops.ENV_INTERPRET, "1")
    assert ops.provenance()["pallas_interpret"] is True
    monkeypatch.setenv(ops.ENV_INTERPRET, "0")
    assert ops.provenance()["pallas_interpret"] is False
    monkeypatch.delenv(ops.ENV_INTERPRET)
    assert ops.provenance()["pallas_interpret"] == (not ops.on_tpu())
