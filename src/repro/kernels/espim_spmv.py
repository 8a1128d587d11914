"""ESPIM sparse MV as Pallas TPU kernels over the column-chunked ELL pack.

TPU adaptation of the paper's datapath (see DESIGN.md sections 2b/3).

Kernel plane layout.  A pack plane is stored on the host as
``(R_pad, K, Lc)`` — packed row, column chunk, ELL slot.  The kernels read
it as ``(K, Lc, Rp)`` (``kernel_planes``): the column chunk outermost, the
chunk's ELL slots on sublanes and the packed rows on lanes, each half of a
half-major pack padded to a lane multiple.  That makes every block a
legal Mosaic tile — ``(Lc, RT)`` with RT a multiple of 128 and Lc the full
slot width — whatever the plane dtype (f32, bf16, int8 codes or
nibble-packed uint8), and it makes the slot reduction a sublane sum whose
result lands lane-dense in the ``(B, RT)`` output block.

* The grid is ``(row_tile, col_chunk, slot_block)``: a step processes RT
  packed rows against ONE ``chunk_cols``-wide slab of the activation —
  the analogue of a bank's k-MAC group consuming one broadcast slice.  The
  activation rides in as ``(B, K * ccp)`` (``ccp`` = ``chunk_cols``
  rounded up to 128 lanes), and its BlockSpec selects slab k, so VMEM
  residency is bounded at ``B * ccp`` values however wide the matrix is.
* The (values, cols) blocks of the next grid step are DMA'd while the
  current one computes (Pallas grid pipelining) — the decoupled
  iFIFO/eFIFO prefetch.
* ``cols`` ids are *chunk-local*, so the per-cell select is an in-VMEM
  gather into the active slab.  Mosaic gathers along lanes within one
  128-lane vector (``jnp.take_along_axis`` over an ``(Lc, 128)`` operand):
  the slab row of batch column b is broadcast over the slot sublanes, the
  low 7 bits of the id pick the lane, and for slabs wider than 128 lanes
  the high bits select among the per-128-lane gathers — the VPU/XLU
  analogue of the paper's simplified 4x11 switch.  (A one-hot MXU "switch"
  was napkin-mathed and rejected: at 90% sparsity it costs ~16x the dense
  FLOPs — DESIGN.md.)
* ``gather="block"`` unrolls the batch columns; ``gather="loop"`` runs
  them in a ``fori_loop``, so code size does not grow with B.

The chunk padding slots carry value 0 and local col 0; they are the
statically scheduled stalls (SDDS dummy cells) and contribute nothing.

Kernels are validated in interpret mode on CPU against ``ref.py`` and
compiled for a described v5e in ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["espim_spmv_pallas", "espim_spmv_batched_pallas",
           "espim_spmv_batched_quant_pallas",
           "espim_spmv_batched_glu_pallas",
           "espim_spmv_batched_quant_glu_pallas",
           "espim_spmv_batched_res_pallas",
           "espim_spmv_planes", "kernel_planes", "pack_planes"]

LANE = 128
DEFAULT_BLOCK_R = 512


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# --------------------------------------------------------------------------
# Plane layout: host pack (..., H*Rg, K, Lc) <-> kernel (..., K, Lc', H*Rgp)
# --------------------------------------------------------------------------
def _xp(a):
    """numpy for host arrays (the offline upload path), jnp otherwise."""
    return np if isinstance(a, np.ndarray) else jnp


def _pad_rows(a, rows: int, halves: int, axis: int):
    """Zero-pad each of ``halves`` row blocks of ``rows`` along ``axis`` to
    a lane multiple."""
    axis = axis % a.ndim
    rgp = _round_up(rows, LANE)
    if rgp == rows:
        return a
    shp = a.shape
    a = a.reshape(shp[:axis] + (halves, rows) + shp[axis + 1:])
    pad = [(0, 0)] * a.ndim
    pad[axis + 1] = (0, rgp - rows)
    a = _xp(a).pad(a, pad)
    return a.reshape(shp[:axis] + (halves * rgp,) + shp[axis + 1:])


def _unpad_rows(a, rows: int, halves: int, axis: int):
    """Inverse of ``_pad_rows``."""
    axis = axis % a.ndim
    rgp = _round_up(rows, LANE)
    if rgp == rows:
        return a
    shp = a.shape
    a = a.reshape(shp[:axis] + (halves, rgp) + shp[axis + 1:])
    a = a[(slice(None),) * (axis + 1) + (slice(0, rows),)]
    return a.reshape(shp[:axis] + (halves * rows,) + shp[axis + 1:])


def _nibbles(v):
    """Sign-extended (low, high) nibbles of a uint8 array, as int32."""
    v = v.astype(np.int32)
    return (((v & 0xF) ^ 8) - 8, (((v >> 4) & 0xF) ^ 8) - 8)


def kernel_planes(values, cols, *, halves: int = 1):
    """Host-pack planes ``(..., H*Rg, K, Lc)`` -> kernel planes
    ``(..., K, Lc', H*Rgp)`` (numpy in, numpy out; jnp otherwise).

    Each half's rows are zero-padded to ``Rgp`` (a lane multiple); pad
    rows are empty.  A nibble-packed int4 value plane (width
    ``ceil(Lc/2)``, slot 2j in the low nibble of byte j) is re-paired for
    the sublane layout: ``Lc'`` is Lc rounded up to 16 and byte j of a
    row holds slots j (low nibble) and j + Lc'/2 (high nibble), so the
    kernel unpacks two aligned sublane halves.  Other planes keep
    ``Lc' = Lc``."""
    xp = _xp(values)
    lc = cols.shape[-1]
    rows = cols.shape[-3] // halves
    nibbles = values.shape[-1] != lc
    if nibbles:
        lo, hi = _nibbles(values)
        codes = xp.stack([lo, hi], axis=-1).reshape(
            values.shape[:-1] + (2 * values.shape[-1],))[..., :lc]
        pad = [(0, 0)] * (cols.ndim - 1) + [(0, _round_up(lc, 16) - lc)]
        values, cols = xp.pad(codes, pad), xp.pad(cols, pad)
    values = xp.moveaxis(_pad_rows(values, rows, halves, -3), -3, -1)
    cols = xp.moveaxis(_pad_rows(cols, rows, halves, -3), -3, -1)
    if nibbles:
        h = values.shape[-2] // 2
        values = ((values[..., :h, :] & 0xF)
                  | ((values[..., h:, :] & 0xF) << 4)).astype(np.uint8)
    return values, cols


def pack_planes(values, cols, *, rows: int, width: int, halves: int = 1):
    """Inverse of ``kernel_planes``: kernel planes -> ``(..., H*rows, K,
    width)``.  A nibble-packed plane comes back as int8 codes (the same
    codes the host plane nibble-packs)."""
    xp = _xp(values)
    if values.shape[-2] != cols.shape[-2]:
        values = xp.concatenate(_nibbles(values), axis=-2).astype(np.int8)
    values = _unpad_rows(values[..., :width, :], rows, halves, -1)
    cols = _unpad_rows(cols[..., :width, :], rows, halves, -1)
    return xp.moveaxis(values, -1, -3), xp.moveaxis(cols, -1, -3)


def _x_slabs(x, chunk_cols: int, n_chunks: int):
    """Activation (M, B) -> (B, K * ccp) f32, chunk k's slab at lanes
    [k*ccp, k*ccp + chunk_cols) (zeros beyond: they are never gathered)."""
    m, b = x.shape
    if m > n_chunks * chunk_cols:
        raise ValueError(
            f"x has {m} rows > n_chunks*chunk_cols = {n_chunks * chunk_cols}")
    ccp = _round_up(chunk_cols, LANE)
    x = jnp.pad(x.astype(jnp.float32), ((0, n_chunks * chunk_cols - m),
                                        (0, 0)))
    x = jnp.pad(x.reshape(n_chunks, chunk_cols, b),
                ((0, 0), (0, ccp - chunk_cols), (0, 0)))
    return x.reshape(n_chunks * ccp, b).T, ccp


def _lane_tile(rows_pad: int, block_r: int) -> int:
    """Largest lane multiple <= ``block_r`` (at least one lane vector)
    that divides ``rows_pad``."""
    rt = max(LANE, block_r - block_r % LANE)
    while rows_pad % rt:
        rt -= LANE
    return rt


# --------------------------------------------------------------------------
# Kernel bodies
# --------------------------------------------------------------------------
def _slot_blocks(v_ref, c_ref, gs, nibbles: bool):
    """(values f32, chunk-local ids) pairs of one 128-lane row group."""
    if not nibbles:
        return [(v_ref[:, gs].astype(jnp.float32), c_ref[:, gs])]
    h = v_ref.shape[0]
    lo, hi = _nibbles(v_ref[:, gs])
    return [(lo.astype(jnp.float32), c_ref[:h, gs]),
            (hi.astype(jnp.float32), c_ref[h:, gs])]


def _gather_dot(blocks, x_row, n_sub: int):
    """sum over slots of value * slab[id] -> (1, 128); ``x_row(s)`` is
    lanes [128 s, 128 s + 128) of the batch column's slab, (1, 128)."""
    out = None
    for vals, cols in blocks:
        lane = jnp.bitwise_and(cols, LANE - 1)
        g = None
        for s in range(n_sub):
            gs = jnp.take_along_axis(jnp.broadcast_to(x_row(s), cols.shape),
                                     lane, axis=1, mode="promise_in_bounds")
            g = gs if g is None else jnp.where(
                jnp.right_shift(cols, 7) == s, gs, g)
        p = jnp.sum(vals * g, axis=0, keepdims=True)
        out = p if out is None else out + p
    return out


def _first_step():
    return (pl.program_id(1) == 0) & (pl.program_id(2) == 0)


def _last_step():
    return ((pl.program_id(1) == pl.num_programs(1) - 1)
            & (pl.program_id(2) == pl.num_programs(2) - 1))


def _accumulate(v_ref, c_ref, x_ref, out_ref, lead: tuple, *, n_sub: int,
                nibbles: bool, loop: bool):
    """out_ref[lead + (:, row group)] += this block's partial sums."""
    n_b = x_ref.shape[0]
    for g in range(c_ref.shape[-1] // LANE):
        gs = pl.ds(g * LANE, LANE)
        blocks = _slot_blocks(v_ref, c_ref, gs, nibbles)
        idx = lead + (slice(None), gs)
        if loop:
            # batch columns in a fori_loop: the column is picked by a
            # row mask (Mosaic has no unaligned dynamic sublane load)
            rows = jax.lax.broadcasted_iota(jnp.int32, (n_b, LANE), 0)

            def body(bi, acc, blocks=blocks, rows=rows):
                def x_row(s):
                    xs = x_ref[:, pl.ds(s * LANE, LANE)]
                    return jnp.sum(jnp.where(rows == bi, xs, 0.0), axis=0,
                                   keepdims=True)
                return acc + jnp.where(rows == bi,
                                       _gather_dot(blocks, x_row, n_sub), 0.0)

            part = jax.lax.fori_loop(0, n_b, body,
                                     jnp.zeros((n_b, LANE), jnp.float32))
            out_ref[idx] = out_ref[idx] + part
            continue
        for bi in range(n_b):
            row = lead + (pl.ds(bi, 1), gs)
            out_ref[row] = out_ref[row] + _gather_dot(
                blocks, lambda s, bi=bi: x_ref[pl.ds(bi, 1),
                                               pl.ds(s * LANE, LANE)], n_sub)


def _spmv_kernel(v_ref, c_ref, x_ref, *rest, n_sub, nibbles, loop,
                 residual):
    """Plain / residual-epilogue step: out (B, RT) += the block's sums."""
    out_ref = rest[-1]

    @pl.when(_first_step())
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    _accumulate(v_ref, c_ref, x_ref, out_ref, (), n_sub=n_sub,
                nibbles=nibbles, loop=loop)
    if residual:
        res_ref = rest[0]

        @pl.when(_last_step())
        def _epilogue():
            out_ref[...] = out_ref[...] + res_ref[...]


def _glu_kernel(vg_ref, vu_ref, cg_ref, cu_ref, *rest, n_sub, nibbles,
                act, scaled):
    """Half-major gated step: gate and up blocks of the same packed rows
    accumulate into halves 0 and 1 of the (2, B, RT) out block; the last
    grid step dequantizes both (quantized planes: per-row scales AFTER
    the reduce), then rewrites half 0 with act(gate) * up — the unfused
    path's op order, in one launch (half 1 is scratch the wrapper
    drops)."""
    from repro.kernels.ref import epilogue_act
    out_ref = rest[-1]
    x_ref = rest[2] if scaled else rest[0]

    @pl.when(_first_step())
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    for h, (v_ref, c_ref) in enumerate(((vg_ref, cg_ref), (vu_ref, cu_ref))):
        _accumulate(v_ref, c_ref, x_ref, out_ref, (h,), n_sub=n_sub,
                    nibbles=nibbles, loop=False)

    @pl.when(_last_step())
    def _epilogue():
        gate, up = out_ref[0], out_ref[1]
        if scaled:
            gate, up = gate * rest[0][...], up * rest[1][...]
        out_ref[0] = epilogue_act(act)(gate) * up


# --------------------------------------------------------------------------
# Kernel-layout launch
# --------------------------------------------------------------------------
@functools.partial(
    jax.jit,
    static_argnames=("chunk_cols", "rows", "halves", "epilogue", "act",
                     "block_r", "block_l", "gather", "interpret"),
)
def espim_spmv_planes(values, cols, x, srow=None, residual=None, *,
                      chunk_cols: int, rows: int, halves: int = 1,
                      epilogue: str | None = None, act: str = "silu",
                      block_r: int = DEFAULT_BLOCK_R,
                      block_l: int | None = None, gather: str = "block",
                      interpret: bool = True) -> jnp.ndarray:
    """One SpMV launch over kernel planes (``kernel_planes``).

    ``values``/``cols``: ``(K, Lc', Rp)`` f32/bf16 values, int8 codes or
    re-paired nibble-packed uint8 (detected by its half width); ``x``:
    ``(M, B)``.  Returns ``(halves * rows, B)`` f32 in packed row order —
    the host-pack contract of ``ref.py`` — or, for ``epilogue="glu"``
    (``halves == 2``), act(gate) * up ``(rows, B)``, dequantized first by
    the per-row scales ``srow`` ``(2 * rows,)`` when given.
    ``epilogue="residual"`` adds ``residual`` ``(halves * rows, B)``
    (packed row order) at the last grid step.  Quantized planes without
    an epilogue return the code-domain accumulator.

    ``block_r`` caps the lane tile of packed rows (rounded to a lane
    multiple dividing each half's padded rows); ``block_l`` splits the
    slot axis (rounded to 32 sublanes; the full width by default);
    ``gather`` picks unrolled ("block") or looped ("loop") batch columns.
    """
    if gather not in ("block", "loop"):
        raise ValueError(f"unknown gather mode {gather!r}")
    if epilogue not in (None, "glu", "residual"):
        raise ValueError(f"unknown epilogue {epilogue!r}")
    if epilogue == "glu" and halves != 2:
        raise ValueError("the GLU epilogue needs a half-major gate+up pack "
                         f"(halves=2); got halves={halves}")
    k, lc, rp = cols.shape
    nibbles = values.shape[-2] != lc
    if nibbles and 2 * values.shape[-2] != lc:
        raise ValueError(f"nibble-packed values width {values.shape[-2]} "
                         f"does not match cols width {lc}")
    if rp != halves * _round_up(rows, LANE):
        raise ValueError(f"planes hold {rp} lanes; {halves} halves of "
                         f"{rows} rows pad to "
                         f"{halves * _round_up(rows, LANE)}")
    bl = lc
    if block_l is not None and not nibbles:
        bl = min(lc, _round_up(block_l, 32))
        if lc % bl:
            pad = ((0, 0), (0, _round_up(lc, bl) - lc), (0, 0))
            values, cols = jnp.pad(values, pad), jnp.pad(cols, pad)
            lc = cols.shape[1]
    bl_v = bl // 2 if nibbles else bl
    rgp = rp // halves
    rt = _lane_tile(rgp, block_r)
    xk, ccp = _x_slabs(x, chunk_cols, k)
    b = xk.shape[0]
    n_sub = ccp // LANE
    x_spec = pl.BlockSpec((b, ccp), lambda i, kk, j: (0, kk))
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"))

    def plane(width, shift=0):
        return pl.BlockSpec((None, width, rt),
                            lambda i, kk, j: (kk, j, i + shift))

    if epilogue == "glu":
        nh = rgp // rt
        ins = [plane(bl_v), plane(bl_v, nh), plane(bl), plane(bl, nh)]
        args = [values, values, cols, cols]
        if srow is not None:
            s = _pad_rows(srow.astype(jnp.float32), rows, 2, 0)[None, :]
            ins += [pl.BlockSpec((1, rt), lambda i, kk, j: (0, i)),
                    pl.BlockSpec((1, rt), lambda i, kk, j: (0, i + nh))]
            args += [s, s]
        out = pl.pallas_call(
            functools.partial(_glu_kernel, n_sub=n_sub, nibbles=nibbles,
                              act=act, scaled=srow is not None),
            grid=(nh, k, lc // bl),
            in_specs=ins + [x_spec],
            out_specs=pl.BlockSpec((2, b, rt), lambda i, kk, j: (0, 0, i)),
            out_shape=jax.ShapeDtypeStruct((2, b, rgp), jnp.float32),
            compiler_params=params,
            interpret=interpret,
        )(*args, xk)
        return out[0, :, :rows].T

    row_spec = pl.BlockSpec((b, rt), lambda i, kk, j: (0, i))
    ins = [plane(bl_v), plane(bl), x_spec]
    args = [values, cols, xk]
    if epilogue == "residual":
        if residual is None:
            raise ValueError("epilogue='residual' needs the residual "
                             "operand (packed row order)")
        ins.append(row_spec)
        args.append(_pad_rows(residual.astype(jnp.float32).T, rows,
                              halves, 1))
    out = pl.pallas_call(
        functools.partial(_spmv_kernel, n_sub=n_sub, nibbles=nibbles,
                          loop=gather == "loop",
                          residual=epilogue == "residual"),
        grid=(rp // rt, k, lc // bl),
        in_specs=ins,
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((b, rp), jnp.float32),
        compiler_params=params,
        interpret=interpret,
    )(*args)
    return _unpad_rows(out, rows, halves, 1).T


# --------------------------------------------------------------------------
# Host-pack-layout entry points: (R_pad, K, Lc) planes, re-laid per call
# --------------------------------------------------------------------------
def _check_chunked(values: jnp.ndarray, cols: jnp.ndarray) -> None:
    if values.ndim != 3 or cols.ndim != 3:
        raise ValueError(
            "kernels consume the column-chunked ELL layout (R_pad, "
            f"n_chunks, Lc); got values {values.shape}, cols {cols.shape}. "
            "Pack with pack_ell_chunked / chunk_pack.")


def _halves(r: int, halves: int) -> int:
    if r % halves:
        raise ValueError(
            f"GLU epilogue needs a half-major (2*Rg, ...) pack; got {r} rows")
    return r // halves


def _launch(values, cols, x, *, chunk_cols, halves=1, **kw):
    _check_chunked(values, cols)
    rows = _halves(cols.shape[0], halves)
    kv, kc = kernel_planes(values, cols, halves=halves)
    return espim_spmv_planes(kv, kc, x, chunk_cols=chunk_cols, rows=rows,
                             halves=halves, **kw)


def espim_spmv_pallas(values, cols, x, *, chunk_cols: int,
                      block_r: int = DEFAULT_BLOCK_R,
                      block_l: int | None = None,
                      interpret: bool = True) -> jnp.ndarray:
    """y_packed (R_pad,) f32 = chunked-ELL(values, cols) @ x (M,)."""
    return _launch(values, cols, x[:, None], chunk_cols=chunk_cols,
                   block_r=block_r, block_l=block_l,
                   interpret=interpret)[:, 0]


def espim_spmv_batched_pallas(values, cols, x, *, chunk_cols: int,
                              block_r: int = DEFAULT_BLOCK_R,
                              block_l: int | None = None,
                              interpret: bool = True,
                              gather: str = "block") -> jnp.ndarray:
    """y_packed (R_pad, B) f32 = chunked-ELL(values, cols) @ x (M, B)."""
    return _launch(values, cols, x, chunk_cols=chunk_cols, block_r=block_r,
                   block_l=block_l, gather=gather, interpret=interpret)


def espim_spmv_batched_quant_pallas(values, cols, scales, x, *,
                                    chunk_cols: int, group_rows: int,
                                    block_r: int = DEFAULT_BLOCK_R,
                                    block_l: int | None = None,
                                    interpret: bool = True) -> jnp.ndarray:
    """y_packed (R_pad, B) f32 = dequant(chunked-ELL codes) @ x (M, B).

    ``values`` is int8 codes (R_pad, K, Lc) or nibble-packed uint8
    (R_pad, K, ceil(Lc/2)); ``scales`` is one f32 per ``group_rows``
    packed rows, applied to the reduced (R_pad, B) accumulator."""
    y = _launch(values, cols, x, chunk_cols=chunk_cols, block_r=block_r,
                block_l=block_l, interpret=interpret)
    srow = jnp.repeat(scales, group_rows)[:cols.shape[0]]
    return y * srow[:, None]


def espim_spmv_batched_glu_pallas(values, cols, x, *, chunk_cols: int,
                                  act: str = "silu",
                                  block_r: int = DEFAULT_BLOCK_R,
                                  block_l: int | None = None,
                                  interpret: bool = True) -> jnp.ndarray:
    """act(gate) * up (Rg, B) f32 from a half-major (2*Rg, K, Lc) gate+up
    pack — the epilogue-fused gated-MLP launch."""
    return _launch(values, cols, x, chunk_cols=chunk_cols, halves=2,
                   epilogue="glu", act=act, block_r=block_r, block_l=block_l,
                   interpret=interpret)


def espim_spmv_batched_quant_glu_pallas(values, cols, srow, x, *,
                                        chunk_cols: int, act: str = "silu",
                                        block_r: int = DEFAULT_BLOCK_R,
                                        block_l: int | None = None,
                                        interpret: bool = True
                                        ) -> jnp.ndarray:
    """Quantized epilogue-fused gated launch: int8 codes or nibble-packed
    uint8, pre-expanded per-row f32 scales ``srow`` (2*Rg,); returns
    act(gate) * up (Rg, B) f32."""
    _check_chunked(values, cols)
    rows = _halves(cols.shape[0], 2)
    kv, kc = kernel_planes(values, cols, halves=2)
    return espim_spmv_planes(kv, kc, x, srow, chunk_cols=chunk_cols,
                             rows=rows, halves=2, epilogue="glu", act=act,
                             block_r=block_r, block_l=block_l,
                             interpret=interpret)


def espim_spmv_batched_res_pallas(values, cols, x, residual, *,
                                  chunk_cols: int,
                                  block_r: int = DEFAULT_BLOCK_R,
                                  block_l: int | None = None,
                                  interpret: bool = True) -> jnp.ndarray:
    """y_packed (R_pad, B) f32 = chunked-ELL @ x + residual, the residual
    add fused into the last grid step (``residual`` already in packed row
    order — the ``output="take"`` contract lets the caller permute it
    once, statically)."""
    _check_chunked(values, cols)
    kv, kc = kernel_planes(values, cols)
    return espim_spmv_planes(kv, kc, x, None, residual,
                             chunk_cols=chunk_cols, rows=cols.shape[0],
                             epilogue="residual", block_r=block_r,
                             block_l=block_l, interpret=interpret)
