"""The plain float32 reference against the program's own dense forward
pass, at small sizes on the CPU: the gated-SiLU family with tied
embeddings and the squared-ReLU family with an untied head, both with a
head_dim for which n_heads * head_dim != d_model, and the magnitude
pruning rule against the program's."""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tinybench
from benchlib import reference, weights

sys.path.insert(0, str(tinybench.REPO / "src"))

FAMILIES = {
    "granite-3-2b": dict(activation="silu", gated_mlp=True,
                         tie_embeddings=True),
    "nemotron-4-15b": dict(activation="relu2", gated_mlp=False,
                           tie_embeddings=False),
}


def _model(arch: str) -> dict:
    m = dict(tinybench.MODEL, n_layers=3, d_model=64, n_heads=4,
             n_kv_heads=2, head_dim=24, d_ff=96, vocab_size=300,
             param_dtype="float32", compute_dtype="float32",
             **FAMILIES[arch])
    return m


def _program(arch: str, m: dict):
    from repro.configs.registry import get_config
    cfg = get_config(arch).replace(
        **{k: m[k] for k in ("n_layers", "d_model", "n_heads", "n_kv_heads",
                             "head_dim", "d_ff", "vocab_size", "param_dtype",
                             "compute_dtype")}, remat="none")
    assert (cfg.activation, cfg.gated_mlp, cfg.tie_embeddings) == (
        m["activation"], m["gated_mlp"], m["tie_embeddings"])
    assert cfg.n_heads * cfg.hd != cfg.d_model
    return cfg


@pytest.mark.parametrize("sparsity", [0.0, 0.9])
@pytest.mark.parametrize("arch", sorted(FAMILIES))
def test_reference_matches_the_program_forward(arch, sparsity):
    from repro.core.pruning import magnitude_prune
    from repro.models import transformer as T
    m = _model(arch)
    cfg = _program(arch, m)
    params = weights.make_params(m, 11)
    if sparsity:
        for mod, names in reference.PROJECTIONS.items():
            for n in names:
                if n in params["layers"][mod]:
                    w = np.asarray(params["layers"][mod][n])
                    params["layers"][mod][n] = jnp.asarray(np.stack(
                        [magnitude_prune(w[l], sparsity)
                         for l in range(m["n_layers"])]))
    tokens = np.random.default_rng(0).integers(0, m["vocab_size"], (2, 40))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(T.forward(cfg, params, {"tokens": jnp.asarray(
            tokens, jnp.int32)}))[..., : m["vocab_size"]]
    idx = np.broadcast_to(np.arange(40), (2, 40))
    got = np.asarray(reference.forward_logits(m, 11, sparsity, "all",
                                              tokens, idx))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 2e-5 * scale


def test_pruning_rule_matches_the_program():
    from repro.core.pruning import magnitude_prune
    rng = np.random.default_rng(3)
    for shape in [(64, 96), (96, 64), (7, 13)]:
        # bf16-valued weights: many ties at the threshold
        w = np.asarray(jnp.asarray(rng.standard_normal(shape),
                                   jnp.bfloat16).astype(jnp.float32))
        for s in (0.5, 0.9):
            want = magnitude_prune(w, s)
            got = np.asarray(reference.prune(jnp.asarray(w), s))
            np.testing.assert_array_equal(got, want)


def test_one_layer_made_again_equals_the_whole_tree():
    m = dict(tinybench.MODEL, n_layers=3)
    tree = weights.make_params(m, 5)
    for layer in range(3):
        one = weights.layer_params(m, 5, layer)
        for mod, leaves in one.items():
            for name, w in leaves.items():
                np.testing.assert_array_equal(
                    np.asarray(w), np.asarray(tree["layers"][mod][name][layer]))
    assert tree["embed"].dtype == jnp.bfloat16
    assert tree["embed"].shape == (512, 128)


def test_served_gaps_are_zero_for_the_reference_own_greedy_tokens():
    m = _model("granite-3-2b")
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, m["vocab_size"], 9).tolist()
    seq = list(prompt)
    for _ in range(6):                      # greedy, one token at a time
        lg = np.asarray(reference.forward_logits(
            m, 3, 0.9, "all", np.asarray([seq]),
            np.asarray([[len(seq) - 1]])))
        seq.append(int(lg[0, 0].argmax()))
    out = seq[len(prompt):]
    gaps = reference.served_gaps(m, 3, 0.9, "all", [(prompt, out)], 24)
    assert gaps[0].shape == (6,)
    assert np.abs(gaps[0]).max() <= 1e-5
    wrong = list(out)
    wrong[2] = (wrong[2] + 1) % m["vocab_size"]
    gaps = reference.served_gaps(m, 3, 0.9, "all", [(prompt, wrong)], 24)
    assert gaps[0][2] > 1e-4


@pytest.mark.parametrize("bits", [8, 4])
def test_quantize_keeps_one_absmax_scale_per_group_of_outputs(bits):
    rng = np.random.default_rng(bits)
    w = np.asarray(reference.prune(jnp.asarray(
        rng.standard_normal((64, 256)), jnp.float32), 0.9))
    q = np.asarray(reference.quantize(jnp.asarray(w), bits))
    qmax = 2 ** (bits - 1) - 1
    for g in range(2):                      # two groups of 128 outputs
        cols = slice(128 * g, 128 * (g + 1))
        scale = np.abs(w[:, cols]).max() / qmax
        codes = q[:, cols] / scale
        np.testing.assert_allclose(codes, np.rint(codes), atol=1e-4)
        assert np.abs(q[:, cols] - w[:, cols]).max() <= scale / 2 * 1.0001
    assert ((q != 0) <= (w != 0)).all()      # pruned entries stay zero
    # a width that 128 does not divide falls back to gcd(128, out) groups
    assert reference.quantize(jnp.asarray(w[:, :96]), bits).shape == (64, 96)


def test_readings_put_each_variant_in_the_program_place():
    m = _model("granite-3-2b")
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, m["vocab_size"], 7).tolist()
    seq = list(prompt)
    for _ in range(8):
        lg = np.asarray(reference.forward_logits(
            m, 5, 0.9, "all", np.asarray([seq]),
            np.asarray([[len(seq) - 1]])))
        seq.append(int(lg[0, 0].argmax()))
    rows = [(prompt, seq[len(prompt):])]
    got = reference.readings(m, 5, 0.9, "all", rows, 20, {
        "same": {}, "control": {"bits": 4},
        "witness": {"bits": 8, "act": "bfloat16"}})
    assert set(got) == {"served", "same", "control", "witness"}
    assert np.abs(got["served"][0]).max() <= 1e-5
    assert np.abs(got["same"][0]).max() <= 1e-5
    for k in ("control", "witness"):
        assert got[k][0].shape == (8,) and (got[k][0] >= 0).all()
    st = reference.gap_stats([np.array([0.0, 0.3]), np.array([0.05])])
    assert st["widest_gap"] == 0.3
    assert st["mean_gap"] == pytest.approx(0.35 / 3)
    assert st["share_over_0.1"] == pytest.approx(1 / 3)
