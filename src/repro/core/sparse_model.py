"""ESPIM-format sparse serving of a whole dense-family LM.

The paper's deployment (Section IV): take a trained model, magnitude-prune
the projection matrices, and serve MV decode from the compressed format.
ESPIM's format and SDDS scheduling are projection-agnostic — the paper
applies fine-grained interleaving, balance permutation and decoupled
value/index planes to EVERY MV of the decode step — so the offline
pipeline here is a projection-generic **pack-group compiler**
(``sparsify_model``): a list of declarative ``PackGroupSpec``s
(repro.core.sdds) is compiled, group by group, into width-bucketed
layer-stacked packs (prune -> fuse -> balance -> chunk -> width-bucket ->
[quantize]), and the decode step runs every per-token MV — q/k/v/o AND
gate/up/down — through the packed kernels.

The default decoder-layer group set (DESIGN.md section 10):

* ``qkv``: q, k, v row-concatenated into ONE pack under one balance perm
  (one SpMV launch per bucket for all three projections; per-projection
  row counts may differ — GQA).  Output contract ``take``: one static
  ``jnp.take`` by ``inv_perm`` restores logical row order, because RoPE
  pairs head dims positionally and the KV cache stores logical head rows.
* ``attn_out``: the O projection, feeding the residual (``take``).
* ``gateup``: gate+up as shared-perm *halves* — ``silu(gate) * up`` runs
  directly in packed order (output contract ``folded``).
* ``down``: column ids pre-composed offline with the gateup packed order
  (``compose_with="gateup"``), output restored by one ``take``.

The decode datapath is fully fused (DESIGN.md section 8): one
``jax.lax.scan`` over the layer stack, packs padded to uniform per-bucket
shapes, activations kept in ``(features, B)`` layout between launches.
``sparsify_mlps`` survives as a thin MLP-only preset of
``sparsify_model`` (attention stays dense — the pre-PR5 behavior).

Quantized serving (``quant="int8"|"int4"``, DESIGN.md section 9): only
the packs' *value planes* are re-encoded (repro.quant) — per-bucket-row-
group scales ride the layer scan as one more stacked leaf and the fused
SpMV launches dispatch to the quantized kernels; cols/perms/plans and the
whole datapath shape are untouched.  The pruned dense copies are replaced
by the *dequantized* reconstructions, so the GEMM prefill path and every
parity test see exactly the weights the quantized kernels compute with.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import integrity
from repro.core.pruning import magnitude_prune
from repro.core.sdds import (PackGroupSpec, decoder_layer_groups,
                             validate_group_specs)
from repro.core.sparse_format import (BucketedStackedPack,
                                      bucketed_stack_to_dense,
                                      compose_cols_with_pack, pack_group,
                                      projection_padded_slots)
from repro.kernels import ops
from repro.kernels.espim_spmv import kernel_planes, pack_planes
from repro.models import transformer as T

__all__ = ["sparsify_model", "sparsify_mlps", "pruned_param_tree",
           "projection_arrays", "bucket_planes", "decode_step_sparse",
           "prefill_chunk_sparse", "sparse_stats", "verify_sparse"]

# the standard decoder-layer projections NOT covered by a group still
# stream their dense bytes every decode token — sparse_stats charges them
_DENSE_MODULES = ("attn", "mlp")


def _kernel_bucket(values, cols, valid, halves: int) -> dict:
    """One bucket's host planes (L, H*Rg, K, Lc) -> the kernels' plane
    layout (L, K, Lc', H*Rgp) (``espim_spmv.kernel_planes``), with the
    host ``valid`` mask carried into the same layout (pad slots False)."""
    v, c = kernel_planes(np.asarray(values),
                         np.asarray(cols, np.int32), halves=halves)
    ok = kernel_planes(np.asarray(valid), np.asarray(valid),
                       halves=halves)[0]
    extra = c.shape[-2] - ok.shape[-2]        # int4: slots padded to 16
    if extra:
        ok = np.pad(ok, [(0, 0)] * (ok.ndim - 2) + [(0, extra), (0, 0)])
    return {"values": jnp.asarray(v), "cols": jnp.asarray(c),
            "valid": np.ascontiguousarray(ok)}


def _to_device(pack: BucketedStackedPack) -> dict:
    """BucketedStackedPack -> the jnp dict the serving step consumes.

    Bucket planes upload in the kernels' layout (L, K, Lc', H*Rgp) —
    column chunk, ELL slot, packed row (each half lane-padded); the
    reference lowering reads them back through ``ops.espim_spmv_planes``.
    ``valid`` masks (same layout), nnz stats and the host
    QuantizedValuePlanes stay host-side (stats/tests only); quantized
    packs upload per-bucket codes (``q``) + pre-expanded per-row scales
    (``srow``, (L, H*Rg) in packed row order, stacked over layers like
    every other scan leaf) in place of the fp ``values``, and record
    static ``quant`` meta (bits / effective group_rows / storage family)
    per bucket."""
    if pack.qplanes is None:
        buckets = [_kernel_bucket(b["values"], b["cols"], b["valid"],
                                  pack.halves) for b in pack.buckets]
        quant_meta = None
    else:
        # quantized serving never touches the fp plane: upload ONLY the
        # codes and the per-row scales (expanded offline so the fused
        # path folds the whole dequant into ONE multiply per bucket) —
        # uploading the fp32 values just to drop them would transiently
        # hold 4-8x the quantized footprint on device
        buckets = []
        for b, plane in zip(pack.buckets, pack.qplanes):
            kb = _kernel_bucket(plane.device_codes(), b["cols"], b["valid"],
                                pack.halves)
            kb["q"] = kb.pop("values")
            kb["srow"] = jnp.asarray(plane.row_scales())
            buckets.append(kb)
        quant_meta = tuple(
            {"bits": p.bits, "group_rows": p.group_rows, "storage": p.storage}
            for p in pack.qplanes)
    g = {
        "halves": pack.halves,
        "n_rows": pack.n_rows,
        "n_cols": pack.n_cols,
        "r_pad": pack.r_pad,
        "chunk_cols": pack.chunk_cols,
        "n_chunks": pack.n_chunks,
        "bucket_rows": pack.bucket_rows,
        "widths": pack.widths,
        "buckets": buckets,
        "perm": jnp.asarray(pack.perm, jnp.int32),
        "inv_perm": jnp.asarray(pack.inv_perm, jnp.int32),
        "nnz": pack.nnz,
        "nnz_per_layer": np.asarray(pack.nnz_per_layer),
        "nnz_per_half": np.asarray(pack.nnz_per_half),
        "padded_per_layer": pack.padded_slots_per_layer,
        "plan": pack.plan,
        "quant": quant_meta,
        "qplanes": pack.qplanes,
    }
    # fingerprint the *device* form — kernel-layout planes, nibble-packed
    # quant codes, expanded srow scales and int32 perms differ byte-wise
    # from the host pack, so the build-time pack fingerprint cannot stand
    # in for the upload check
    g["plane_fingerprints"], g["fingerprint"] = _group_fingerprint(g)
    return g


def bucket_planes(g: dict, gi: int) -> tuple:
    """Bucket ``gi`` of a serving group back in the host-pack layout:
    (values or int8 codes, cols), each (L, H*Rg, K, Lc)."""
    b = g["buckets"][gi]
    return pack_planes(b["values"] if "values" in b else b["q"], b["cols"],
                       rows=g["bucket_rows"][gi], width=g["widths"][gi],
                       halves=g["halves"])


def _group_fingerprint(g: dict) -> tuple[dict, str]:
    """Per-plane digests + bound digest over exactly the arrays the jitted
    decode gathers (plus the host valid masks and the SDDS plan meta)."""
    planes = {}
    for gi, b in enumerate(g["buckets"]):
        for nm in ("values", "q", "cols", "srow", "valid"):
            if nm in b:
                planes[f"b{gi}.{nm}"] = np.asarray(b[nm])
    planes["perm"] = np.asarray(g["perm"])
    planes["inv_perm"] = np.asarray(g["inv_perm"])
    meta = {
        "halves": g["halves"], "n_rows": g["n_rows"], "n_cols": g["n_cols"],
        "r_pad": g["r_pad"], "chunk_cols": g["chunk_cols"],
        "bucket_rows": list(g["bucket_rows"]), "widths": list(g["widths"]),
        "quant": ([dict(q) for q in g["quant"]] if g["quant"] else None),
        "plan": integrity.plan_fingerprint(g["plan"]),
    }
    fps = integrity.fingerprint_planes(planes)
    return fps, integrity.bind_fingerprint(fps, meta)


def _validate_group(name: str, g: dict) -> None:
    """Bounds-validate one serving group's device planes: chunk-local
    column ids against the gather domain, perm/inv_perm consistency, and
    quantized planes against their scale-group layout."""
    err = integrity.PackIntegrityError
    cc, n_cols = g["chunk_cols"], g["n_cols"]
    for gi, b in enumerate(g["buckets"]):
        cols = np.asarray(b["cols"])                 # (L, K, Lc', H*Rgp)
        valid = np.asarray(b["valid"], bool)
        what = f"group {name!r} bucket {gi}"
        if cols.shape != valid.shape:
            raise err(f"{what}: cols/valid shape mismatch")
        k = cols.shape[-3]
        lim = np.minimum(cc, n_cols - np.arange(k) * cc)
        lim = lim.reshape((1,) * (cols.ndim - 3) + (k, 1, 1))
        if (valid & ((cols < 0) | (cols >= lim))).any():
            raise err(f"{what}: index plane out of bounds for input dim "
                      f"{n_cols} (chunk_cols={cc})")
        if "values" in b:
            if not bool(np.isfinite(np.asarray(b["values"])).all()):
                raise err(f"{what}: non-finite entries in the value plane")
        if "srow" in b:
            srow = np.asarray(b["srow"])
            if not bool(np.isfinite(srow).all()):
                raise err(f"{what}: non-finite quant scales")
            rows = g["halves"] * g["bucket_rows"][gi]
            if srow.shape != (cols.shape[0], rows):
                raise err(f"{what}: srow scale layout {srow.shape} does not "
                          f"cover the packed rows {(cols.shape[0], rows)}")
            qm = g["quant"][gi]
            if rows % max(1, qm["group_rows"]):
                raise err(f"{what}: rows not divisible by scale "
                          f"group_rows={qm['group_rows']}")
            q = np.asarray(b["q"])
            if qm["storage"] == "nib4":
                want = cols.shape[:-2] + (cols.shape[-2] // 2,
                                          cols.shape[-1])
                if q.dtype != np.uint8 or q.shape != want:
                    raise err(f"{what}: nibble-packed codes layout "
                              f"{q.dtype}{q.shape} != uint8{want}")
            elif q.dtype != np.int8 or q.shape != cols.shape:
                raise err(f"{what}: int8 codes layout {q.dtype}{q.shape} "
                          f"diverges from the index plane {cols.shape}")
    integrity.validate_perm_layers(f"group {name!r}", g["perm"],
                                   g["inv_perm"], g["n_rows"])


def verify_sparse(sparse: dict) -> dict:
    """The serving-side upload check (engine init, benches): every group's
    device planes are bounds-validated and re-fingerprinted against the
    digests ``sparsify_model`` recorded.  Raises ``PackIntegrityError``
    naming the group and diverging planes; returns ``{group: digest}``."""
    out = {}
    for name, g in sparse.get("groups", {}).items():
        _validate_group(name, g)
        fps, bound = _group_fingerprint(g)
        recorded = g.get("fingerprint")
        if recorded is not None and recorded != bound:
            diverged = integrity.diverging_planes(
                {"planes": g.get("plane_fingerprints", {})}, {"planes": fps})
            raise integrity.PackIntegrityError(
                f"group {name!r}: device plane fingerprint mismatch "
                f"(diverged: {diverged or ['<meta/schedule>']}) — the pack "
                "was corrupted after build or paired with the wrong "
                "schedule")
        out[name] = bound
    return out


def _dequantized_projs(pack: BucketedStackedPack, offsets: dict,
                       upstream: BucketedStackedPack | None) -> dict:
    """Reconstruct the dense (L, in, out) matrices a quantized group
    actually encodes: dequantize each bucket plane, unscatter, slice each
    projection's rows, and (for composed groups) map the columns back to
    the logical order — these replace the pruned copies so the dense
    prefill datapath (Section III-I) and the parity tests run the *same*
    effective weights as the quantized kernels."""
    deq = dataclasses.replace(pack, buckets=[
        dict(b, values=plane.dequantize())
        for b, plane in zip(pack.buckets, pack.qplanes)])
    out = {}
    for name, (hf, r0, r1) in offsets.items():
        mats = []
        for l in range(pack.n_layers):
            m = bucketed_stack_to_dense(deq, l, hf)[r0:r1]
            if upstream is not None:
                m = m[:, upstream.inv_perm[l]]       # back to logical cols
            mats.append(m.T)                         # (in, out)
        out[name] = np.stack(mats)
    return out


def _uncovered_dense_bytes(params: dict, covered: set) -> int:
    """Per-token weight bytes of the standard decoder projections NOT
    compiled into a pack group (stacked 2-D weights only; biases/norms are
    negligible).  This is what an MLP-only deployment still streams
    densely for attention every decode token."""
    total = 0
    for module in _DENSE_MODULES:
        sub = params.get("layers", {}).get(module, {})
        for name, w in sub.items():
            if (module, name) in covered or np.ndim(w) != 3:
                continue
            total += int(np.size(w)) * jnp.dtype(w.dtype).itemsize
    return total


def _resolve_specs(cfg: ModelConfig, projections) -> dict:
    if projections == "all":
        specs = decoder_layer_groups(cfg.gated_mlp, attn=True, mlp=True)
    elif projections == "mlp":
        specs = decoder_layer_groups(cfg.gated_mlp, attn=False, mlp=True)
    elif projections == "attn":
        specs = decoder_layer_groups(cfg.gated_mlp, attn=True, mlp=False)
    elif isinstance(projections, str):
        raise ValueError(f"unknown projections preset {projections!r} "
                         "(all | mlp | attn | explicit PackGroupSpec list)")
    else:
        specs = tuple(projections)
    by_name = validate_group_specs(specs)
    # the fused decode runtime drives each module through its canonical
    # group names and projection sets — enforce the coupling HERE so a
    # custom spec list that the runtime cannot serve (or, worse, would
    # silently bypass, running attention from the unpruned params while
    # the stats claim it is packed) fails at build, not at trace
    runtime = {"attn": {"qkv": {"wq", "wk", "wv"}, "attn_out": {"wo"}},
               "mlp": {"gateup": ({"w_gate", "w_up"} if cfg.gated_mlp
                                  else {"w_up"}),
                       "down": {"w_down"}}}
    for module, req in runtime.items():
        covering = {s.name: set(s.projections) for s in by_name.values()
                    if s.module == module}
        if covering and covering != req:
            raise ValueError(
                f"the fused decode runtime serves {module} via groups "
                f"{ {n: sorted(p) for n, p in req.items()} }; "
                f"got { {n: sorted(p) for n, p in covering.items()} }")
    return by_name


def sparsify_model(cfg: ModelConfig, params: dict, sparsity: float, *,
                   projections="all",
                   row_tile: int = 128,
                   chunk_cols: int = ops.DEFAULT_CHUNK_COLS,
                   n_buckets: int = 4,
                   quant: str | None = None,
                   quant_spec=None) -> dict:
    """Offline pack-group compiler: prune + fuse + pack (+ quantize) the
    decoder layer's projections per a declarative group-spec list.

    ``projections``: ``"all"`` (default — fused QKV + O + gate/up + down:
    the whole decoder layer serves from the compressed format),
    ``"mlp"``/``"attn"`` presets (the uncovered side runs dense from the
    layer params), or an explicit ``PackGroupSpec`` tuple.

    Returns the serving dict: per-group device packs under ``"groups"``
    (also aliased at the top level by group name), pruned dense copies
    per projection (``"pruned"`` + ``"<name>_pruned"`` aliases) for the
    GEMM prefill path and verification, and the compiled ``"specs"``.

    ``quant`` ("int8" | "int4"; or pass an explicit
    ``repro.quant.QuantSpec`` via ``quant_spec``) re-encodes every group's
    value planes per bucket row group and swaps the pruned dense copies
    for their dequantized reconstructions — decode then serves from the
    narrow codes while the GEMM prefill path stays weight-consistent.
    """
    quant = None if quant in (None, "none") else quant
    by_name = _resolve_specs(cfg, projections)
    n_layers = cfg.n_layers

    qspec = None
    if quant is not None or quant_spec is not None:
        from repro.quant import QuantSpec, default_spec
        qspec = (quant_spec if isinstance(quant_spec, QuantSpec)
                 else default_spec(quant))
        quant = quant or f"int{qspec.bits}"

    # ---- prune every covered projection ---------------------------------
    pruned: dict = {}
    dtypes: dict = {}
    for spec in by_name.values():
        sub = params["layers"].get(spec.module, {})
        missing = [n for n in spec.projections if n not in sub]
        if missing:
            raise ValueError(
                f"params missing {spec.module} projection(s) {missing} "
                f"for group {spec.name!r} (gated_mlp={cfg.gated_mlp})")
        for name in spec.projections:
            w = np.asarray(sub[name], np.float32)        # (L, in, out)
            pruned[name] = np.stack([magnitude_prune(w[l], sparsity)
                                     for l in range(n_layers)])
            dtypes[name] = sub[name].dtype

    # ---- compile the groups in spec order -------------------------------
    host_packs: dict = {}
    groups: dict = {}
    for spec in by_name.values():
        # rows of the packed matrix are W^T's rows (the output dim)
        mats = {n: [pruned[n][l].T for l in range(n_layers)]
                for n in spec.projections}
        proj_nnz = {n: np.asarray([(pruned[n][l] != 0).sum()
                                   for l in range(n_layers)], np.int64)
                    for n in spec.projections}
        upstream = host_packs.get(spec.compose_with)
        if upstream is not None:
            mats = {n: compose_cols_with_pack(ms, upstream)
                    for n, ms in mats.items()}
        pack, offsets = pack_group(mats, fuse=spec.fuse, row_tile=row_tile,
                                   chunk_cols=chunk_cols,
                                   n_buckets=n_buckets)
        if qspec is not None:
            from repro.quant import quantize_bucketed_stack
            quantize_bucketed_stack(pack, qspec)
            # the dequantized matrices are the weights decode actually
            # applies: make them the pruned copies (prefill GEMMs +
            # parity references)
            for name, arr in _dequantized_projs(pack, offsets,
                                                upstream).items():
                pruned[name] = arr
        host_packs[spec.name] = pack
        g = _to_device(pack)
        g.update({
            "name": spec.name,
            "module": spec.module,
            "projections": tuple(spec.projections),
            "fuse": spec.fuse,
            "output": spec.output,
            "compose_with": spec.compose_with,
            "row_offsets": offsets,
            "proj_nnz": proj_nnz,
            "proj_padded": projection_padded_slots(pack, offsets),
        })
        groups[spec.name] = g

    covered = {(s.module, n) for s in by_name.values()
               for n in s.projections}
    out: dict = {
        "format": "espim-packgroups/v3",
        "sparsity": sparsity,
        "gated": bool(cfg.gated_mlp),
        "quant": quant or "none",
        "attn_sparse": "qkv" in groups,
        "mlp_sparse": "gateup" in groups,
        "specs": tuple(by_name.values()),
        "groups": groups,
        "dense_proj_bytes": _uncovered_dense_bytes(params, covered),
        # cast on the host: a device-side cast would hold each f32 stack
        # (2.7 GB for one granite-3-2b MLP projection) on the device
        "pruned": {n: jnp.asarray(np.asarray(w).astype(dtypes[n]))
                   for n, w in pruned.items()},
    }
    if qspec is not None:
        out["quant_spec"] = qspec
    # one model-level digest binding every group's device fingerprint —
    # what provenance records and what a restored sparse dict verifies
    out["fingerprint"] = integrity.bind_fingerprint(
        {n: g["fingerprint"] for n, g in groups.items()},
        meta={"format": out["format"], "sparsity": sparsity,
              "quant": out["quant"]})
    for name, g in groups.items():             # legacy top-level aliases
        out[name] = g
    for name, w in out["pruned"].items():
        out[f"{name}_pruned"] = w
    return out


def sparsify_mlps(cfg: ModelConfig, params: dict, sparsity: float,
                  row_tile: int = 128,
                  chunk_cols: int = ops.DEFAULT_CHUNK_COLS,
                  n_buckets: int = 4,
                  quant: str | None = None,
                  quant_spec=None) -> dict:
    """MLP-only preset of ``sparsify_model``: gate+up fused halves + the
    perm-composed down projection; attention stays on the dense path (the
    pre-PR5 serving mode, kept for the attn=dense benchmark dimension)."""
    return sparsify_model(cfg, params, sparsity, projections="mlp",
                          row_tile=row_tile, chunk_cols=chunk_cols,
                          n_buckets=n_buckets, quant=quant,
                          quant_spec=quant_spec)


def pruned_param_tree(params: dict, sparse: dict) -> dict:
    """A params tree with every covered projection's weights replaced by
    the sparse dict's pruned (or dequantized) copies — the dense
    reference model the parity tests and smoke benches decode with."""
    pruned = jax.tree.map(lambda x: x, params)
    for module in _DENSE_MODULES:
        sub = params.get("layers", {}).get(module, {})
        for name in sub:
            if name in sparse["pruned"]:
                pruned["layers"][module][name] = sparse["pruned"][name]
    return pruned


# --------------------------------------------------------------------------
# Fused runtime path
# --------------------------------------------------------------------------
def _scan_bufs(sparse: dict):
    """The per-layer arrays threaded through the layer scan, one entry per
    pack group (everything else about the packs is static geometry closed
    over by the step).  Quantized packs thread (codes, cols, scales)
    triples — the stacked (L, G) scales are just one more scan leaf;
    ``take``-output groups also thread their (L, n_rows) ``inv_perm``."""

    def bufs(g):
        if g["quant"] is not None:
            b = [(b["q"], b["cols"], b["srow"]) for b in g["buckets"]]
        else:
            b = [(b["values"], b["cols"]) for b in g["buckets"]]
        entry = {"bufs": b}
        if g["output"] == "take":
            entry["inv"] = g["inv_perm"]
        return entry

    return {name: bufs(g) for name, g in sparse["groups"].items()}


def _bucket_spmv(pack: dict, buf: tuple, g: int, xt: jnp.ndarray,
                 impl: str, epilogue: str | None = None,
                 act: str = "silu") -> jnp.ndarray:
    """One bucket's SpMV launch, fp or quantized per the pack's meta.
    Quantized launches return the code-domain accumulator and dequantize
    with one multiply by the pre-expanded per-row scales.

    ``epilogue="glu"`` fuses act(gate)·up into the launch (half-major
    gate+up bucket, DESIGN.md §15): the fused lowerings replay the exact
    op order of the unfused path — dequant-once then gate — so the output
    is bit-identical on the reference path, in one launch instead of
    three ops."""
    kw = dict(chunk_cols=pack["chunk_cols"], rows=pack["bucket_rows"][g],
              width=pack["widths"][g], halves=pack["halves"], impl=impl)
    if pack["quant"] is not None:
        codes, cols, srow = buf
        if epilogue == "glu":
            return ops.espim_spmv_planes(codes, cols, xt, srow=srow,
                                         epilogue="glu", act=act, **kw)
        return ops.espim_spmv_planes(codes, cols, xt, **kw) * srow[:, None]
    vals, cols = buf
    return ops.espim_spmv_planes(vals, cols, xt, epilogue=epilogue, act=act,
                                 **kw)


def _group_apply(pack: dict, gb: dict, xt: jnp.ndarray, impl: str) -> list:
    """All of one group's bucket launches -> per-bucket packed outputs."""
    return [_bucket_spmv(pack, buf, g, xt, impl)
            for g, buf in enumerate(gb["bufs"])]


def _group_take(gb: dict, parts: list) -> jnp.ndarray:
    """Concatenate bucket outputs and restore logical row order with the
    group's one static ``take`` (the ``output="take"`` contract)."""
    yp = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    return jnp.take(yp, gb["inv"], axis=0)


@jax.named_scope("espim.qkv")
def _fused_qkv(cfg: ModelConfig, sparse: dict, bufs: dict, attn_p: dict,
               hn: jnp.ndarray, impl: str):
    """The fused QKV pack: hn (B, T, D) -> q (B, T, H, hd), k/v
    (B, T, KV, hd) in *logical* head order.

    One SpMV launch per bucket computes all three projections; the single
    static ``take`` by ``inv_perm`` unscatters the packed rows so RoPE's
    positional head-dim pairing and the KV-cache writes see exactly the
    rows the dense path produces.  QKV biases (qwen-style) are added
    post-take — biases are never packed."""
    g = sparse["groups"]["qkv"]
    gb = bufs["qkv"]
    b, t = hn.shape[0], hn.shape[1]
    xt = hn.reshape(-1, hn.shape[-1]).T.astype(jnp.float32)   # (D, B*T)
    y = _group_take(gb, _group_apply(g, gb, xt, impl))        # (rows, B*T)

    def cut(name: str, n_heads: int) -> jnp.ndarray:
        _, r0, r1 = g["row_offsets"][name]
        seg = y[r0:r1]
        bias = attn_p.get("b" + name[1])                      # wq -> bq
        if bias is not None:
            seg = seg + bias.astype(jnp.float32)[:, None]
        return seg.T.reshape(b, t, n_heads, cfg.hd).astype(hn.dtype)

    return (cut("wq", cfg.n_heads), cut("wk", cfg.n_kv_heads),
            cut("wv", cfg.n_kv_heads))


@jax.named_scope("espim.o")
def _fused_o(cfg: ModelConfig, sparse: dict, bufs: dict,
             out_h: jnp.ndarray, impl: str) -> jnp.ndarray:
    """The packed O projection: attention heads (B, T, H, hd) -> residual
    contribution (B, T, D) via one bucketed SpMV + the static take."""
    g = sparse["groups"]["attn_out"]
    gb = bufs["attn_out"]
    b, t = out_h.shape[0], out_h.shape[1]
    xt = out_h.reshape(b * t, -1).T.astype(jnp.float32)       # (H*hd, B*T)
    y = _group_take(gb, _group_apply(g, gb, xt, impl))        # (D, B*T)
    return y.T.reshape(b, t, -1).astype(out_h.dtype)


@jax.named_scope("espim.qkv")
def _pruned_qkv(cfg: ModelConfig, px: dict, attn_p: dict, hn: jnp.ndarray):
    """Dense-path QKV from the pruned copies (GEMM prefill, Section
    III-I): same matrices the packs hold, applied as GEMMs; biases come
    from the layer params (they are never pruned)."""
    p = {"wq": px["wq"], "wk": px["wk"], "wv": px["wv"]}
    for bn in ("bq", "bk", "bv"):
        if bn in attn_p:
            p[bn] = attn_p[bn]
    return T._qkv(cfg, p, hn)


def _fused_mlp(cfg: ModelConfig, sparse: dict, bufs: dict, hn: jnp.ndarray,
               impl: str, epilogue: bool = True) -> jnp.ndarray:
    """One layer's MLP through the fused packs.

    hn (B, T, d_model) -> (B, T, d_model).  Decode runs T=1 (the hot
    path); chunked prefill feeds T=chunk tokens — the kernels see B*T
    columns either way, and x stays in (in, B*T) layout throughout.

    ``epilogue=True`` (default) folds act(gate)·up into the gate+up SpMV
    launch itself (the ``fuse="halves"`` contract makes this legal: both
    halves share one balance perm, so the product is an in-kernel
    elementwise at a fixed row offset).  ``epilogue=False`` keeps the
    op-level epilogue as the parity reference — the two are bit-identical
    by construction.
    """
    from repro.models.layers import act_fn
    act = act_fn(cfg.activation)
    gu = sparse["groups"]["gateup"]
    dn = sparse["groups"]["down"]
    b, t = hn.shape[0], hn.shape[1]
    xt = hn.reshape(-1, hn.shape[-1]).T.astype(jnp.float32)   # (in, B*T)

    with jax.named_scope("espim.gateup"):
        parts = []
        if sparse["gated"] and epilogue:
            for g, buf in enumerate(bufs["gateup"]["bufs"]):
                parts.append(_bucket_spmv(gu, buf, g, xt, impl,
                                          epilogue="glu", act=cfg.activation))
        else:
            for yp, rg in zip(_group_apply(gu, bufs["gateup"], xt, impl),
                              gu["bucket_rows"]):
                if sparse["gated"]:
                    # gate rows and up rows of the bucket share packed
                    # order: the product needs no unscatter (act(0)*0 == 0
                    # on pad rows)
                    parts.append(act(yp[:rg]) * yp[rg:])
                else:
                    parts.append(act(yp))
        inter = (parts[0] if len(parts) == 1
                 else jnp.concatenate(parts, axis=0))

    with jax.named_scope("espim.down"):
        y = _group_take(bufs["down"],
                        _group_apply(dn, bufs["down"], inter, impl))
        return y.T.reshape(b, t, -1).astype(hn.dtype)         # (B, T, D)


def _pruned_mlp(cfg: ModelConfig, sparse: dict, wl: dict, hn: jnp.ndarray
                ) -> jnp.ndarray:
    """The flexible *dense* datapath (Section III-I) over the pruned
    copies: the same matrices the packs hold, applied as GEMMs.  Prefill
    is compute-bound GEMM work where the MXU/BLAS path wins; the packs own
    the memory-bound single-token MV decode."""
    from repro.models import layers as L
    if sparse["gated"]:
        return L.mlp_gated(hn, wl["w_gate"], wl["w_up"], wl["w_down"],
                           cfg.activation)
    return L.mlp_relu2(hn, wl["w_up"], wl["w_down"], cfg.activation)


def projection_arrays(sparse: dict, proj_path: str = "kernel") -> dict:
    """The per-layer projection arrays the layer scan threads: the pack
    buffers for the kernel path, the pruned dense copies for the GEMM
    path.  Jitted serving steps take this pytree as an argument
    (``proj=``), so the weights stay device buffers and never become
    constants of the compiled program; everything else about the packs
    is static geometry the step closes over."""
    if proj_path == "kernel":
        return _scan_bufs(sparse)
    if proj_path != "dense":
        raise ValueError(f"unknown proj_path {proj_path!r}")
    return dict(sparse["pruned"])


def _layer_stack(cfg: ModelConfig, params: dict, sparse: dict, cache: dict,
                 h, attn_step, attn_core, impl: str, unroll: bool,
                 proj_path: str = "kernel", epilogue: bool = True,
                 proj: dict | None = None):
    """Shared layer loop for decode/prefill: scan by default; ``unroll``
    keeps the per-layer Python loop as the parity reference.

    ``attn_step`` is the whole-attention closure used when the sparse
    dict does not cover attention (dense weights from the layer params);
    ``attn_core`` is the projection-free middle (RoPE + cache +
    attention) wrapped by the packed QKV / O groups when it does.  The
    MLP is symmetric: uncovered (``projections="attn"``) it runs dense
    from the layer params on both proj paths.

    ``proj`` is ``projection_arrays(sparse, proj_path)`` passed in by a
    jitted caller; ``None`` reads it from ``sparse`` (closure constants).
    """
    attn_sparse = sparse.get("attn_sparse", False)
    mlp_sparse = sparse.get("mlp_sparse", "gateup" in sparse["groups"])

    def body(h, xs):
        lp, kc, vc, px = xs
        hn = T._norm(cfg, lp["ln1"], h)
        if attn_sparse:
            if proj_path == "kernel":
                q, k, v = _fused_qkv(cfg, sparse, px, lp["attn"], hn, impl)
            else:
                q, k, v = _pruned_qkv(cfg, px, lp["attn"], hn)
            with jax.named_scope("attention"):
                a_h, kc, vc = attn_core(q, k, v, kc, vc)
            if proj_path == "kernel":
                a = _fused_o(cfg, sparse, px, a_h, impl)
            else:
                from repro.models import layers as L
                b, t = hn.shape[0], hn.shape[1]
                with jax.named_scope("espim.o"):
                    a = L.dense(a_h.reshape(b, t, -1), px["wo"])
        else:
            a, kc, vc, _, _ = attn_step(lp, hn, kc, vc)
        h = h + a
        hn = T._norm(cfg, lp["ln2"], h)
        if not mlp_sparse:
            h = h + T.mlp_apply(cfg, lp["mlp"], hn)
        elif proj_path == "kernel":
            h = h + _fused_mlp(cfg, sparse, px, hn, impl, epilogue=epilogue)
        else:
            h = h + _pruned_mlp(cfg, sparse, px, hn)
        return h, (kc, vc)

    if proj is None:
        proj = projection_arrays(sparse, proj_path)
    xs = (params["layers"], cache["k"], cache["v"], proj)
    if unroll:
        k_new, v_new = [], []
        for i in range(cfg.n_layers):
            h, (kc, vc) = body(h, jax.tree.map(lambda x: x[i], xs))
            k_new.append(kc)
            v_new.append(vc)
        return h, jnp.stack(k_new), jnp.stack(v_new)
    h, (k_new, v_new) = jax.lax.scan(body, h, xs)
    return h, k_new, v_new


def decode_step_sparse(cfg: ModelConfig, params: dict, sparse: dict,
                       cache: dict, batch: dict, impl: str = "ref",
                       unroll: bool = False, epilogue: bool = True,
                       proj: dict | None = None):
    """transformer.decode_step with ESPIM-format projections — every
    per-token MV runs through the packed kernels when ``sparse`` covers
    the whole layer (``sparsify_model``), or just the MLPs when it was
    built by the ``sparsify_mlps`` preset (dense attention).

    ``epilogue=True`` (default) runs the gate+up MLP buckets with the
    act(gate)·up epilogue fused into the SpMV launch; ``epilogue=False``
    is the bit-identical unfused reference (tests assert the parity).
    ``proj``: ``projection_arrays(sparse)``, as a jit argument."""
    tokens = batch["tokens"]
    h = T.embed_tokens(cfg, params, tokens)

    def attn_step(lp, hn, kc, vc):
        return T.attn_decode_apply(cfg, lp["attn"], hn, kc, vc, cache["len"])

    def attn_core(q, k, v, kc, vc):
        out, kc, vc, _, _ = T.attn_decode_core(cfg, q, k, v, kc, vc,
                                               cache["len"])
        return out, kc, vc

    h, k_new, v_new = _layer_stack(cfg, params, sparse, cache, h, attn_step,
                                   attn_core, impl, unroll,
                                   epilogue=epilogue, proj=proj)
    logits = T.logits_from_hidden(cfg, params, h)
    new_cache = {"k": k_new, "v": v_new, "len": cache["len"] + 1}
    return logits, new_cache


def prefill_chunk_sparse(cfg: ModelConfig, params: dict, sparse: dict,
                         cache: dict, batch: dict, impl: str = "ref",
                         unroll: bool = False, proj_path: str = "dense",
                         epilogue: bool = True, proj: dict | None = None):
    """transformer.prefill_chunk for the ESPIM-format engine: a C-token
    chunk lands at cache["len"]..  Same contract as
    ``factory.prefill_chunk``.

    ``proj_path`` picks the projection datapath — the paper's flexible
    dense/sparse configuration (Section III-I) applied per serving phase:
    ``"dense"`` (default) runs the GEMM-shaped chunk through the pruned
    dense copies (bit-identical matrices, compute-bound phase) for every
    covered projection — attention included when the group set covers it;
    ``"kernel"`` feeds the fused packs with B*C columns (the MV datapath,
    used by the parity tests and on PIM-like backends).  ``proj``:
    ``projection_arrays(sparse, proj_path)``, as a jit argument."""
    tokens = batch["tokens"]
    start = cache["len"]
    n_valid = batch.get("n_valid")
    if n_valid is None:
        n_valid = jnp.full_like(start, tokens.shape[1])
    h = T.embed_tokens(cfg, params, tokens)

    def attn_step(lp, hn, kc, vc):
        return T.attn_prefill_apply(cfg, lp["attn"], hn, kc, vc, start)

    def attn_core(q, k, v, kc, vc):
        out, kc, vc, _, _ = T.attn_prefill_core(cfg, q, k, v, kc, vc, start)
        return out, kc, vc

    h, k_new, v_new = _layer_stack(cfg, params, sparse, cache, h, attn_step,
                                   attn_core, impl, unroll,
                                   proj_path=proj_path, epilogue=epilogue,
                                   proj=proj)
    logits = T.logits_from_hidden(cfg, params, h)
    new_cache = {"k": k_new, "v": v_new, "len": start + n_valid}
    return logits, new_cache


# --------------------------------------------------------------------------
# Stats
# --------------------------------------------------------------------------
def _plane_bytes(p: dict) -> tuple:
    """(value_bytes_total, index_bytes_total, per-layer value bytes) for a
    pack dict: fp32 planes cost 4 bytes/slot; quantized planes use the
    packed accounting (codes at their group's bit width + scales + the
    int4 fallback map).  The index plane is int32 and quant-invariant —
    the paper's value/index decoupling in byte form."""
    n_layers = len(p["nnz_per_layer"])
    index_total = 4 * p["padded_per_layer"] * n_layers
    if p["qplanes"] is not None:
        per_layer = np.sum([pl.value_bytes_by_lead() for pl in p["qplanes"]],
                           axis=0)
        return int(per_layer.sum()), index_total, [int(b) for b in per_layer]
    per = 4 * p["padded_per_layer"]
    return per * n_layers, index_total, [per] * n_layers


def _pack_stats(p: dict) -> dict:
    n_layers = len(p["nnz_per_layer"])
    padded = p["padded_per_layer"] * n_layers
    vbytes, ibytes, vbytes_layer = _plane_bytes(p)
    return {
        "nnz": int(p["nnz"]),
        "padded_slots": int(padded),
        "pad_frac": 1 - p["nnz"] / padded,
        "pad_frac_per_layer": [
            1 - int(n) / p["padded_per_layer"]
            for n in p["nnz_per_layer"]
        ],
        "bucket_rows": list(p["bucket_rows"]),
        "bucket_widths": list(p["widths"]),
        "single_bucket_pad_frac": 1 - p["nnz"] / max(
            1, p["plan"].single_bucket_slots * p["n_chunks"]
            * p["halves"] * n_layers),
        "value_plane_bytes": vbytes,
        "index_plane_bytes": ibytes,
        "value_plane_bytes_per_layer": vbytes_layer,
        "bits_per_nnz": 8.0 * vbytes / max(1, int(p["nnz"])),
        "bits_per_nnz_per_layer": [
            8.0 * b / max(1, int(n))
            for b, n in zip(vbytes_layer, p["nnz_per_layer"])
        ],
    }


def _proj_stats(g: dict, group_stats: dict, proj: str) -> dict:
    """Per-projection stats inside a group.  nnz and padded slots are
    exact (the balance perm scatters a projection's rows across width
    buckets — ``projection_padded_slots`` walks ``inv_perm``); the
    quantized value plane is attributed by padded-slot share (scale
    groups can straddle projections)."""
    n_layers = len(g["nnz_per_layer"])
    nnz_l = g["proj_nnz"][proj]
    padded_l = g["proj_padded"][proj]
    nnz, padded = int(nnz_l.sum()), int(padded_l.sum())
    share = padded / max(1, g["padded_per_layer"] * n_layers)
    vbytes = (int(round(group_stats["value_plane_bytes"] * share))
              if g["qplanes"] is not None else 4 * padded)
    return {
        "nnz": nnz,
        "padded_slots": padded,
        "pad_frac": 1 - nnz / max(1, padded),
        "pad_frac_per_layer": [1 - int(n) / max(1, int(p))
                               for n, p in zip(nnz_l, padded_l)],
        "value_plane_bytes": vbytes,
        "index_plane_bytes": 4 * padded,
        "bits_per_nnz": 8.0 * vbytes / max(1, nnz),
    }


def sparse_stats(sparse: dict) -> dict:
    """Aggregate + per-group + per-projection + per-layer padding AND
    byte-plane stats for every compiled pack group.

    Group entries carry the pack-level figures (padding is a property of
    the fused pack); each projection additionally reports its own exact
    nnz/padded split under its original name (``w_gate``, ``wq``, ...).
    ``value_plane_bytes`` / ``index_plane_bytes`` / ``bits_per_nnz``
    report the stored (possibly quantized) format — the bytes a decode
    token streams across the pin per layer/projection.

    ``total.bytes_per_token`` is the WHOLE-MODEL per-token projection
    traffic: the packed planes plus the dense bytes of every standard
    decoder projection the group set does not cover
    (``dense_proj_bytes_per_token`` — attention, in an MLP-only
    deployment).  Before PR 5 this silently reported the MLP-only packed
    totals as if they were the model."""
    out: dict = {"quant": sparse.get("quant", "none"),
                 "attn_sparse": sparse.get("attn_sparse", False)}
    tot_nnz = tot_padded = tot_value = tot_index = 0
    for name, g in sparse["groups"].items():
        gs = _pack_stats(g)
        out[name] = gs
        for proj in g["projections"]:
            out[proj] = _proj_stats(g, gs, proj)
        n_layers = len(g["nnz_per_layer"])
        tot_nnz += g["nnz"]
        tot_padded += g["padded_per_layer"] * n_layers
        tot_value += gs["value_plane_bytes"]
        tot_index += gs["index_plane_bytes"]
    dense_bytes = int(sparse.get("dense_proj_bytes", 0))
    out["total"] = {
        "nnz": int(tot_nnz),
        "padded_slots": int(tot_padded),
        "pad_frac": 1 - tot_nnz / max(1, tot_padded),
        "value_plane_bytes": int(tot_value),
        "index_plane_bytes": int(tot_index),
        "bits_per_nnz": 8.0 * tot_value / max(1, tot_nnz),
        # every decode token streams each layer's planes once — plus the
        # dense weights of any projection left outside the group set
        "packed_bytes_per_token": int(tot_value + tot_index),
        "dense_proj_bytes_per_token": dense_bytes,
        "bytes_per_token": int(tot_value + tot_index + dense_bytes),
    }
    return out
