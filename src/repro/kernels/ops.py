"""Public jit'd wrappers for the kernels package.

Dispatch policy: Pallas kernels run natively on TPU and in ``interpret=True``
mode elsewhere (this container is CPU-only; interpret mode executes the
kernel body in Python for correctness validation).  ``impl="ref"`` forces
the pure-jnp lowering — used by the tests and as the path inside large
jitted graphs where a Python-interpreted kernel would be wasteful.  For the
chunked layout the "ref" lowering of the batched op is itself the fused
per-chunk gather-accumulate (same schedule as the kernel, no
(R_pad, L, B) materialization).

Both the seed (R_pad, L) ELL layout and the column-chunked (R_pad, K, Lc)
layout are accepted; the array rank selects the family.  Only the chunked
family has Pallas kernels — the plain layout survives for the sharded
matvec path and lowers through the einsum reference.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sdds import KernelSchedule
from repro.core.sparse_format import ELLChunkedPack, ELLPack, chunk_pack
from repro.kernels import ref as _ref
from repro.kernels.dense_mv import dense_mv_pallas
from repro.kernels import espim_spmv as _k
from repro.kernels.espim_spmv import (espim_spmv_batched_glu_pallas,
                                      espim_spmv_batched_pallas,
                                      espim_spmv_batched_quant_glu_pallas,
                                      espim_spmv_batched_quant_pallas,
                                      espim_spmv_batched_res_pallas,
                                      espim_spmv_pallas)
from repro.telemetry.trace import get_tracer

__all__ = [
    "on_tpu",
    "espim_spmv",
    "espim_spmv_batched",
    "espim_spmv_batched_quant",
    "espim_spmv_planes",
    "dense_mv",
    "espim_matvec",
    "EspimWeights",
    "QuantEspimWeights",
    "pack_to_device",
    "Provenance",
    "provenance",
    "DEFAULT_CHUNK_COLS",
    "ENV_IMPL",
    "ENV_INTERPRET",
]

DEFAULT_CHUNK_COLS = 512

# Environment overrides for the dispatch policy, so CI and benches can pin
# the implementation explicitly instead of inferring it from the backend:
#   ESPIM_IMPL=ref|pallas        force the lowering everywhere (wins over
#                                per-call ``impl=`` arguments — that is the
#                                point: pin the whole process)
#   ESPIM_FORCE_INTERPRET=1|0    force Pallas interpret mode on (1) or off
#                                (0) regardless of the detected backend
ENV_IMPL = "ESPIM_IMPL"
ENV_INTERPRET = "ESPIM_FORCE_INTERPRET"


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: str | None) -> str:
    env = os.environ.get(ENV_IMPL, "").strip()
    if env:
        impl = env
    if impl is None:
        impl = "pallas"
    if impl not in ("pallas", "ref"):
        raise ValueError(f"unknown impl {impl!r}")
    return impl


def _interpret() -> bool:
    env = os.environ.get(ENV_INTERPRET, "").strip()
    if env:
        return env not in ("0", "false", "False")
    return not on_tpu()


@dataclasses.dataclass(frozen=True)
class Provenance:
    """Where a kernel call would run right now — recorded by the benches
    and trace headers so every result carries its backend/impl context.

    Before PR 7 this was a kwarg-sprawl dict rebuilt ad-hoc at each call
    site; now one frozen dataclass with a stable ``to_dict()`` (the dict
    shape the BENCH_*.json provenance blocks have carried since PR 2).

    ``quant`` names the value-plane encoding the caller is timing
    (none/int8/int4); ``attn`` names the attention projection datapath
    (dense = MLP-only packs, sparse = whole-layer fused QKV + O packs,
    sweep = both); ``packs`` maps a label to the bound pack fingerprint
    the run served (``core.integrity``), so a result is tied to the
    exact plane bytes.
    """
    backend: str
    impl: str
    quant: str
    attn: str
    pallas_interpret: bool
    packs: dict | None
    env: dict
    # the chosen kernel schedule (PR 10): ``None`` = pre-autotune caller;
    # else {"source": "default"|"search"|"cache", "tuned": bool,
    # "chunk_cols"/"block_r"/"block_l"/"gather", "epilogue": ...} — bench
    # rows and trace headers carry it so history windows can distinguish
    # tuned/fused runs from default-schedule ones
    schedule: dict | None = None

    @classmethod
    def collect(cls, impl: str | None = None, quant: str | None = None,
                attn: str | None = None, packs: dict | None = None,
                schedule: dict | None = None) -> "Provenance":
        return cls(
            backend=jax.default_backend(),
            impl=_resolve(impl),
            quant=quant or "none",
            attn=attn or "dense",
            pallas_interpret=_interpret(),
            packs=dict(packs) if packs else None,
            env={ENV_IMPL: os.environ.get(ENV_IMPL) or None,
                 ENV_INTERPRET: os.environ.get(ENV_INTERPRET) or None},
            schedule=dict(schedule) if schedule else None,
        )

    def to_dict(self) -> dict:
        """Stable key order, JSON-ready — byte-compatible with the dict
        ``provenance()`` has always returned."""
        return {
            "backend": self.backend,
            "impl": self.impl,
            "quant": self.quant,
            "attn": self.attn,
            "pallas_interpret": self.pallas_interpret,
            "packs": dict(self.packs) if self.packs else None,
            "schedule": dict(self.schedule) if self.schedule else None,
            "env": dict(self.env),
        }


def provenance(impl: str | None = None, quant: str | None = None,
               attn: str | None = None, packs: dict | None = None,
               schedule: dict | None = None) -> dict:
    """Backward-compatible functional form: ``Provenance.collect(...)
    .to_dict()`` (see the dataclass for field semantics)."""
    return Provenance.collect(impl=impl, quant=quant, attn=attn,
                              packs=packs, schedule=schedule).to_dict()


def _block_kw(schedule: KernelSchedule | None, gather: bool = False) -> dict:
    """Pallas block/gather kwargs from a tuned schedule (``None`` keeps
    the kernel defaults — the pre-autotune behaviour)."""
    if schedule is None:
        return {}
    kw = {"block_r": schedule.block_r, "block_l": schedule.block_l}
    if gather:
        kw["gather"] = schedule.gather
    return kw


def _check_chunk_cols(cols, x, chunk_cols) -> int:
    if chunk_cols is None:
        raise ValueError(
            "chunk_cols is required for the chunked (R_pad, K, Lc) layout; "
            f"got cols of shape {cols.shape}")
    cc = int(chunk_cols)
    n_chunks = cols.shape[1]
    if n_chunks > 1 and n_chunks * cc - x.shape[0] >= cc:
        # the last chunk would sit entirely past x: chunk_cols cannot be
        # the width this pack was built with (silent-corruption guard)
        raise ValueError(
            f"chunk_cols={cc} inconsistent with pack: {n_chunks} chunks x "
            f"{cc} cols span past x of length {x.shape[0]}")
    return cc


def _dispatch_spmv(values, cols, x, chunk_cols, impl,
                   plain_ref, chunked_ref, pallas_kernel,
                   pallas_kw: dict | None = None) -> jnp.ndarray:
    """Layout/impl dispatch shared by the (un)batched ops: plain
    (R_pad, L) packs lower through the reference only; chunked
    (R_pad, K, Lc) packs pick the Pallas kernel or the chunked ref."""
    impl = _resolve(impl)
    if values.ndim == 2:
        if impl == "pallas":
            raise ValueError(
                "the Pallas kernels consume the column-chunked layout; "
                "re-pack with pack_ell_chunked (plain ELL is ref-only)")
        return plain_ref(values, cols, x)
    cc = _check_chunk_cols(cols, x, chunk_cols)
    if impl == "ref":
        return chunked_ref(values, cols, x, cc)
    return pallas_kernel(values, cols, x, chunk_cols=cc,
                         interpret=_interpret(), **(pallas_kw or {}))


def espim_spmv(values, cols, x, *, chunk_cols: int | None = None,
               impl: str | None = None,
               schedule: KernelSchedule | None = None) -> jnp.ndarray:
    """ELL sparse MV -> (R_pad,) f32.

    Chunked layout: values/cols (R_pad, K, Lc) + ``chunk_cols``.
    Plain layout: values/cols (R_pad, L), reference lowering only.
    """
    return _dispatch_spmv(values, cols, x, chunk_cols, impl,
                          _ref.espim_spmv_ref, _ref.espim_spmv_chunked_ref,
                          espim_spmv_pallas, _block_kw(schedule))


def espim_spmv_batched(values, cols, x, *, chunk_cols: int | None = None,
                       impl: str | None = None,
                       schedule: KernelSchedule | None = None,
                       epilogue: str | None = None, act: str = "silu",
                       residual=None) -> jnp.ndarray:
    """Batched ELL sparse MV: x (M, B) -> (R_pad, B) f32 (see espim_spmv).

    ``schedule`` applies a tuned ``core.sdds.KernelSchedule``'s block and
    gather choices to the Pallas lowering (``chunk_cols`` stays the
    pack's — re-chunking is an offline transform, not a launch knob).

    ``epilogue`` fuses a decode epilogue into the launch (DESIGN.md §15):

    * ``"glu"`` — values/cols hold a half-major (2*Rg, K, Lc) gate+up
      group sharing one balance perm; returns act(gate) * up (Rg, B) in
      packed order (legal under the ``fuse="halves"`` contract).
    * ``"residual"`` — adds ``residual`` (R_pad, B), ALREADY in packed row
      order, at the kernel's last accumulate step (legal for
      ``output="take"`` groups: the add commutes with the static take
      when the caller permutes the residual once, offline).
    """
    if epilogue is None:
        return _dispatch_spmv(values, cols, x, chunk_cols, impl,
                              _ref.espim_spmv_batched_ref,
                              _ref.espim_spmv_batched_chunked_ref,
                              espim_spmv_batched_pallas,
                              _block_kw(schedule, gather=True))
    impl = _resolve(impl)
    if values.ndim != 3:
        raise ValueError(
            f"epilogue={epilogue!r} needs the column-chunked layout; got "
            f"values of shape {values.shape}")
    cc = _check_chunk_cols(cols, x, chunk_cols)
    if epilogue == "glu":
        if impl == "ref":
            return _ref.espim_spmv_batched_chunked_glu_ref(
                values, cols, x, cc, act)
        return espim_spmv_batched_glu_pallas(
            values, cols, x, chunk_cols=cc, act=act,
            interpret=_interpret(), **_block_kw(schedule))
    if epilogue == "residual":
        if residual is None:
            raise ValueError("epilogue='residual' needs the residual "
                             "operand (packed row order)")
        if impl == "ref":
            return _ref.espim_spmv_batched_chunked_ref(
                values, cols, x, cc) + residual
        return espim_spmv_batched_res_pallas(
            values, cols, x, residual, chunk_cols=cc,
            interpret=_interpret(), **_block_kw(schedule))
    raise ValueError(f"unknown epilogue {epilogue!r}")


def espim_spmv_batched_quant(values, cols, scales, x, *,
                             chunk_cols: int | None = None,
                             group_rows: int = 1,
                             impl: str | None = None,
                             schedule: KernelSchedule | None = None,
                             epilogue: str | None = None, act: str = "silu",
                             srow=None, residual=None) -> jnp.ndarray:
    """Quantized batched ELL sparse MV: int8 codes (or nibble-packed uint8
    — inferred from the width mismatch vs ``cols``) + one f32 scale per
    ``group_rows`` packed rows; x (M, B) -> (R_pad, B) f32.

    ``scales=None`` returns the UNSCALED code-domain accumulator — the
    fused serving path folds its per-row scales into one precomputed
    multiply per bucket instead of one repeat+multiply per launch.

    ``schedule`` applies a tuned schedule's block sizes to the Pallas
    lowering.  ``epilogue="glu"`` fuses dequant + act(gate)·up: the
    half-major (2*Rg, K, Lc) code plane accumulates in the code domain,
    the pre-expanded per-row scales ``srow`` (2*Rg,) dequantize both
    halves ONCE after the reduce, then the gated product — the exact op
    order of the unfused path, one launch.  ``epilogue="residual"`` adds
    the packed-order residual to the scaled output (op-level for the
    quant family — the scale multiply dominates the epilogue).

    Same dispatch policy as the fp ops (``ESPIM_IMPL`` pin wins); the
    plain (R_pad, L) layout lowers through the reference as a one-chunk
    plane.
    """
    impl = _resolve(impl)
    if epilogue == "glu":
        if srow is None:
            raise ValueError("epilogue='glu' needs srow (pre-expanded "
                             "per-row scales, half-major)")
        if cols.ndim != 3:
            raise ValueError(
                "epilogue='glu' needs the column-chunked layout; got "
                f"cols of shape {cols.shape}")
        cc = _check_chunk_cols(cols, x, chunk_cols)
        if impl == "ref":
            return _ref.espim_spmv_batched_chunked_quant_glu_ref(
                values, cols, srow, x, cc, act)
        return espim_spmv_batched_quant_glu_pallas(
            values, cols, srow, x, chunk_cols=cc, act=act,
            interpret=_interpret(), **_block_kw(schedule))
    if epilogue == "residual":
        if residual is None:
            raise ValueError("epilogue='residual' needs the residual "
                             "operand (packed row order)")
        y = espim_spmv_batched_quant(
            values, cols, scales, x, chunk_cols=chunk_cols,
            group_rows=group_rows, impl=impl, schedule=schedule)
        if scales is None and srow is not None:
            y = y * srow[:, None]
        return y + residual
    if epilogue is not None:
        raise ValueError(f"unknown epilogue {epilogue!r}")
    if scales is None and impl != "ref":
        # unit scales through the kernel's own scaling path (exact)
        scales = jnp.ones(1, jnp.float32)
        group_rows = cols.shape[0]
    if cols.ndim == 2:
        if impl == "pallas":
            raise ValueError(
                "the Pallas kernels consume the column-chunked layout; "
                "re-pack with pack_ell_chunked (plain ELL is ref-only)")
        return _ref.espim_spmv_batched_chunked_quant_ref(
            values[:, None, :], cols[:, None, :], scales, x,
            x.shape[0], group_rows)
    cc = _check_chunk_cols(cols, x, chunk_cols)
    if impl == "ref":
        return _ref.espim_spmv_batched_chunked_quant_ref(
            values, cols, scales, x, cc, group_rows)
    return espim_spmv_batched_quant_pallas(
        values, cols, scales, x, chunk_cols=cc, group_rows=group_rows,
        interpret=_interpret(), **_block_kw(schedule))


def espim_spmv_planes(values, cols, x, *, chunk_cols: int, rows: int,
                      width: int, halves: int = 1,
                      epilogue: str | None = None, act: str = "silu",
                      srow=None, impl: str | None = None) -> jnp.ndarray:
    """One bucket launch over kernel-layout planes (``_to_device`` of
    ``core.sparse_model``; ``espim_spmv.kernel_planes``): x (M, B) ->
    (halves * rows, B) f32 in packed row order, or act(gate) * up
    (rows, B) for ``epilogue="glu"``.  int8 / uint8 planes are quantized
    codes: the result is the code-domain accumulator, except that the GLU
    epilogue dequantizes by ``srow`` (halves * rows,) before the gate.

    ``width`` is the host pack's slot width Lc.  The reference lowering
    reads the planes back into the host layout (``pack_planes``) and runs
    the same ``ref.py`` function as the host-layout ops, so its result is
    the one those ops give on the host pack."""
    impl = _resolve(impl)
    quant = jnp.dtype(values.dtype) in (jnp.dtype(jnp.int8),
                                        jnp.dtype(jnp.uint8))
    if impl == "pallas":
        return _k.espim_spmv_planes(
            values, cols, x, srow if epilogue == "glu" else None,
            chunk_cols=chunk_cols, rows=rows, halves=halves,
            epilogue=epilogue, act=act, interpret=_interpret())
    v, c = _k.pack_planes(values, cols, rows=rows, width=width,
                          halves=halves)
    if quant:
        if epilogue == "glu":
            return _ref.espim_spmv_batched_chunked_quant_glu_ref(
                v, c, srow, x, chunk_cols, act)
        return _ref.espim_spmv_batched_chunked_quant_ref(
            v, c, None, x, chunk_cols, 1)
    if epilogue == "glu":
        return _ref.espim_spmv_batched_chunked_glu_ref(v, c, x, chunk_cols,
                                                       act)
    return _ref.espim_spmv_batched_chunked_ref(v, c, x, chunk_cols)


def dense_mv(w, x, *, impl: str | None = None) -> jnp.ndarray:
    """Dense MV (Newton-analogue path)."""
    if _resolve(impl) == "ref":
        return _ref.dense_mv_ref(w, x)
    return dense_mv_pallas(w, x, interpret=_interpret())


# --------------------------------------------------------------------------
# High-level packed-weights API
# --------------------------------------------------------------------------
class EspimWeights:
    """Device-resident column-chunked ESPIM pack of one weight matrix
    (W @ x semantics, W of shape (n_out, n_in))."""

    def __init__(self, values, cols, perm, n_rows: int, n_cols: int,
                 chunk_cols: int):
        self.values = values          # (R_pad, K, Lc)
        self.cols = cols              # (R_pad, K, Lc) int32, chunk-local
        self.perm = perm              # (R_pad,) int32, -1 = pad row
        self.n_rows = n_rows
        self.n_cols = n_cols
        self.chunk_cols = chunk_cols

    def tree_flatten(self):
        return ((self.values, self.cols, self.perm),
                (self.n_rows, self.n_cols, self.chunk_cols))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


jax.tree_util.register_pytree_node(
    EspimWeights,
    lambda w: w.tree_flatten(),
    lambda aux, ch: EspimWeights.tree_unflatten(aux, ch),
)


class QuantEspimWeights:
    """Device-resident column-chunked pack with a quantized value plane
    (repro.quant): int8 codes or nibble-packed uint8 + per-row-group
    scales; indices and perm identical to ``EspimWeights``."""

    def __init__(self, values, cols, perm, scales, n_rows: int, n_cols: int,
                 chunk_cols: int, group_rows: int, bits: int):
        self.values = values          # (R_pad, K, Lc) i8 | (R_pad, K, Lc/2) u8
        self.cols = cols              # (R_pad, K, Lc) int32, chunk-local
        self.perm = perm              # (R_pad,) int32, -1 = pad row
        self.scales = scales          # (R_pad // group_rows,) f32
        self.n_rows = n_rows
        self.n_cols = n_cols
        self.chunk_cols = chunk_cols
        self.group_rows = group_rows
        self.bits = bits

    def tree_flatten(self):
        return ((self.values, self.cols, self.perm, self.scales),
                (self.n_rows, self.n_cols, self.chunk_cols, self.group_rows,
                 self.bits))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


jax.tree_util.register_pytree_node(
    QuantEspimWeights,
    lambda w: w.tree_flatten(),
    lambda aux, ch: QuantEspimWeights.tree_unflatten(aux, ch),
)


def pack_to_device(pack: ELLPack | ELLChunkedPack, dtype=jnp.float32,
                   chunk_cols: int = DEFAULT_CHUNK_COLS,
                   quant=None, verify: bool = True,
                   autotune: bool = False, tune: dict | None = None
                   ) -> EspimWeights | QuantEspimWeights:
    """Move an offline pack onto the device arrays the kernels consume.

    A plain ELLPack is run through the SDDS chunk pass first (with
    ``chunk_cols``); an ELLChunkedPack is uploaded as-is.  ``quant``
    ("int8" | "int4" | a ``repro.quant.QuantSpec``) quantizes the value
    plane on the way up (or reuses an already-attached ``pack.qplane``)
    and returns ``QuantEspimWeights``.

    ``autotune=True`` asks ``repro.autotune`` for a schedule first: a
    plan-cache hit (keyed by the pack's plan-free fingerprint + launch
    context) skips the search entirely; a miss benchmarks the cost-ranked
    candidates and persists the winner.  The tuned ``chunk_cols`` replaces
    the argument for the chunk pass, and the ``TunedPlan`` rides on the
    returned weights as a non-pytree ``.schedule`` attribute so serving
    code and bench provenance can report it.  ``tune`` forwards extra
    ``autotune_pack`` kwargs (``b``, ``max_candidates``, ``iters``,
    ``cache``, ...).

    ``verify=True`` (default) runs ``core.integrity.verify_pack`` on the
    host pack before upload: bounds validation always, plus a fingerprint
    recompute when the builders recorded one — corruption between build
    and upload raises ``PackIntegrityError`` here instead of gathering
    garbage at decode.
    """
    tr = get_tracer()
    with tr.span("pack.to_device", cat="pack",
                 args={"quant": getattr(quant, "bits", quant) or "none",
                       "verify": verify, "autotune": autotune}):
        plan = None
        if autotune:
            from repro.autotune import autotune_pack, default_cache
            kw = dict(tune or {})
            kw.setdefault("cache", default_cache())
            with tr.span("pack.autotune", cat="pack"):
                plan = autotune_pack(pack, quant=quant, **kw)
            if isinstance(pack, ELLPack):
                chunk_cols = plan.schedule.chunk_cols
        w = _pack_to_device(pack, dtype, chunk_cols, quant, verify, tr)
        w.schedule = plan          # aux metadata, invisible to the pytree
        return w


def _pack_to_device(pack, dtype, chunk_cols, quant, verify, tr):
    if verify:
        from repro.core.integrity import verify_pack
        with tr.span("pack.verify", cat="pack"):
            verify_pack(pack)
    if isinstance(pack, ELLPack):
        pack = chunk_pack(pack, chunk_cols)
    if quant is None:
        return EspimWeights(
            values=jnp.asarray(pack.values, dtype=dtype),
            cols=jnp.asarray(pack.cols, dtype=jnp.int32),
            perm=jnp.asarray(np.asarray(pack.perm), dtype=jnp.int32),
            n_rows=pack.n_rows,
            n_cols=pack.n_cols,
            chunk_cols=pack.chunk_cols,
        )
    from repro.quant import QuantSpec, default_spec, quantize_pack
    spec = quant if isinstance(quant, QuantSpec) else default_spec(quant)
    plane = pack.qplane
    # reuse the attached plane only when it was produced by this exact
    # spec — a same-bits plane with different calib/group/err_bound would
    # silently serve the wrong encoding
    if plane is None or plane.spec != spec:
        plane = quantize_pack(pack, spec)
    return QuantEspimWeights(
        values=jnp.asarray(plane.device_codes()),
        cols=jnp.asarray(pack.cols, dtype=jnp.int32),
        perm=jnp.asarray(np.asarray(pack.perm), dtype=jnp.int32),
        scales=jnp.asarray(plane.scales),
        n_rows=pack.n_rows,
        n_cols=pack.n_cols,
        chunk_cols=pack.chunk_cols,
        group_rows=plane.group_rows,
        bits=plane.bits,
    )


def espim_matvec(w: EspimWeights | QuantEspimWeights, x: jnp.ndarray, *,
                 impl: str | None = None) -> jnp.ndarray:
    """y (n_rows,) or (n_rows, B) = W @ x with packed-row unscatter."""
    if x.ndim not in (1, 2):
        raise ValueError(f"x must be 1-D or 2-D, got {x.shape}")
    if isinstance(w, QuantEspimWeights):
        xb = x[:, None] if x.ndim == 1 else x
        yp = espim_spmv_batched_quant(w.values, w.cols, w.scales, xb,
                                      chunk_cols=w.chunk_cols,
                                      group_rows=w.group_rows, impl=impl)
        yp = yp[:, 0] if x.ndim == 1 else yp
    elif x.ndim == 1:
        yp = espim_spmv(w.values, w.cols, x, chunk_cols=w.chunk_cols,
                        impl=impl)
    else:
        yp = espim_spmv_batched(w.values, w.cols, x,
                                chunk_cols=w.chunk_cols, impl=impl)
    return _ref.scatter_rows_ref(yp, w.perm, w.n_rows)
