"""Compile the serving path's Pallas kernels for a described TPU v5e.

Interpret mode on the CPU runs a kernel's arithmetic but not Mosaic's
rules (block tiling, gather forms, VMEM), so these tests lower each
kernel the granite-3-2b serving path launches, at its real bucket shapes,
with the TPU compiler for a v5e that is described, not attached.  The
topology is described inside a fixture (never at import), and the
persistent compilation cache is off around the compiles: an entry written
for a described chip cannot be read back here.

The last test checks, on the CPU, that the jitted sparse decode step
takes the packs as arguments: no pack-sized constant in its program.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.espim_spmv import LANE, espim_spmv_planes

B = 4            # decode slots
CHUNK_COLS = 512

# granite-3-2b bucket geometry at 90% sparsity, int8 packs
# (rows per half, halves, column chunks, slot width Lc)
INT8_BATCHED = [(1344, 1, 4, 72),      # qkv, d_model -> q|k|v
                (1696, 1, 16, 80)]     # down, d_ff -> d_model
INT8_GLU = [(4064, 2, 4, 80),          # gate+up halves, d_model -> d_ff
            (2112, 2, 4, 72)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)


def _compile(one_chip, rows, halves, k, lc, vdtype, epilogue=None):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rp = halves * (-(-rows // LANE) * LANE)
    lc_v = lc // 2 if vdtype == jnp.uint8 else lc
    args = [sds((k, lc_v, rp), vdtype), sds((k, lc, rp), jnp.int32),
            sds((k * CHUNK_COLS, B), jnp.float32)]
    if epilogue == "glu" and vdtype in (jnp.int8, jnp.uint8):
        args.append(sds((halves * rows,), jnp.float32))
    fn = functools.partial(espim_spmv_planes, chunk_cols=CHUNK_COLS,
                           rows=rows, halves=halves, epilogue=epilogue,
                           interpret=False)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows,halves,k,lc", INT8_BATCHED)
def test_int8_batched_compiles(one_chip, rows, halves, k, lc):
    _compile(one_chip, rows, halves, k, lc, jnp.int8)


@pytest.mark.parametrize("rows,halves,k,lc", INT8_GLU)
def test_int8_glu_compiles(one_chip, rows, halves, k, lc):
    _compile(one_chip, rows, halves, k, lc, jnp.int8, epilogue="glu")


def test_fp_batched_compiles(one_chip):
    _compile(one_chip, 992, 1, 4, 72, jnp.float32)     # attn_out bucket


def test_int4_batched_compiles(one_chip):
    _compile(one_chip, 1344, 1, 4, 80, jnp.uint8)      # qkv, Lc' = 80


def test_sparse_decode_step_takes_packs_as_arguments():
    """The engine's jitted decode step over int8 packs lowers with the
    pack planes as parameters: no constant in the program is as large as
    the smallest pack plane."""
    from repro.configs.registry import get_config
    from repro.core.sparse_model import sparsify_model
    from repro.models import factory
    from repro.serve.engine import ServeEngine

    cfg = get_config("granite-3-2b", reduced=True)
    params = factory.init_params(cfg, jax.random.PRNGKey(0))
    sparse = sparsify_model(cfg, params, 0.9, quant="int8")
    eng = ServeEngine(cfg, params, batch_slots=B, max_len=64, sparse=sparse)
    view = eng.cache.gather_view(np.zeros(B, np.int32))
    batch = {"tokens": jnp.zeros((B, 1), jnp.int32), "rng": None}
    text = eng._decode.lower(eng._step_params, view, batch).as_text()
    smallest = min(a.size for g in sparse["groups"].values()
                   for b in g["buckets"] for a in (b["q"], b["cols"]))
    sizes = [int(np.prod([int(d) for d in dims.split("x")[:-1]] or [1]))
             for dims in re.findall(r"stablehlo\.constant dense.*?: "
                                    r"tensor<([0-9a-z]+(?:x[0-9a-z]+)*)>",
                                    text)]
    assert sizes, "expected the lowered step to hold some constants"
    assert max(sizes) < smallest, (max(sizes), smallest)


def test_decode_step_keeps_its_names_for_the_trace_readers(one_chip,
                                                           monkeypatch):
    """The engine's decode step, compiled for a v5e with the native
    kernels, is still the XLA module ``jit_fn`` that launches
    ``espim_spmv_planes`` kernels, and its ops carry the named scopes of
    the cache update and the four pack groups in their op_name metadata
    (the device-trace readers find programs, kernels and scopes by these
    names)."""
    from repro.configs.registry import get_config
    from repro.core.sparse_model import sparsify_model
    from repro.models import factory
    from repro.serve.engine import ServeEngine

    monkeypatch.setenv("ESPIM_FORCE_INTERPRET", "0")
    cfg = get_config("granite-3-2b", reduced=True)
    params = factory.init_params(cfg, jax.random.PRNGKey(0))
    sparse = sparsify_model(cfg, params, 0.9, quant="int8")
    eng = ServeEngine(cfg, params, batch_slots=B, max_len=64, sparse=sparse,
                      impl="pallas")
    view = eng.cache.gather_view(np.zeros(B, np.int32))
    batch = {"tokens": jnp.zeros((B, 1), jnp.int32), "rng": None}

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    args = jax.tree.map(on_chip, (eng._step_params, view, batch))
    text = eng._decode.lower(*args).compile().as_text()
    assert re.match(r"HloModule jit_fn\b", text)
    assert re.search(r"^\s*%espim_spmv_planes[.\d]* = [^\n]*"
                     r"custom_call_target=\"tpu_custom_call\"", text, re.M)
    scopes = {part for name in re.findall(r'op_name="([^"]*)"', text)
              for part in name.split("/")}
    assert {"kv_cache", "espim.qkv", "espim.o", "espim.gateup",
            "espim.down"} <= scopes


# granite-3-2b's paged arenas in the benchmark's decode cell: 40 layers,
# 8 slots x 100 blocks of 16 rows, 8 KV heads of 64, bfloat16
ARENA = (40, 800, 16, 8, 64)


@pytest.mark.parametrize("program", ["kv_scatter_decode",
                                     "kv_scatter_chunk"])
def test_paged_cache_writes_arenas_in_place(one_chip, program):
    """The paged cache's per-tick writers, compiled for a v5e at
    granite-3-2b's arena shapes, hold no copy of an arena and alias each
    donated arena to its output: they write rows in place."""
    from repro.serve import paged_cache

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pages = {"k": sds(ARENA), "v": sds(ARENA)}
    if program == "kv_scatter_decode":
        view = (ARENA[0], 8, 1600) + ARENA[3:]
        args = (pages, {"k": sds(view), "v": sds(view)},
                sds((3, 8), jnp.int32))
    else:
        rows = (ARENA[0], 256) + ARENA[3:]        # one prefill chunk
        args = (pages, {"k": sds(rows), "v": sds(rows)},
                sds((17,), jnp.int32), sds((2,), jnp.int32))
    text = getattr(paged_cache, program).lower(*args).compile().as_text()
    assert re.match(rf"HloModule jit_{program}\b", text)
    arena = r"bf16\[" + ",".join(map(str, ARENA)) + r"\]"
    copies = re.findall(rf"= {arena}\{{[^}}]*\}} copy\(", text)
    assert copies == []
    aliased = {int(p) for p in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", text)}
    assert {0, 1} <= aliased          # parameters 0, 1: the two arenas
