"""Partitioning rules: param/batch/cache pytrees -> PartitionSpec trees.

Strategy (DESIGN.md section 5):
  * TP on ``model`` for head/ffn/vocab dims (column-parallel up/QKV,
    row-parallel down/out projections, EP for MoE experts);
  * FSDP on ``data`` for the non-TP weight dim (XLA all-gathers per layer
    inside the scan — ZeRO-3 with overlap);
  * batch dims on ``('pod', 'data')`` when the pod axis exists;
  * every rule degrades gracefully: an axis is only used if the dim is
    divisible by its mesh extent (e.g. qwen2.5's 40 heads shard on the flat
    5120 feature dim; granite's 49155 vocab shards via the padded table).

Optimizer state inherits the param spec leaf-for-leaf.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig

__all__ = [
    "batch_axes", "mesh_axis_size", "param_pspecs", "batch_pspecs",
    "cache_pspecs", "paged_cache_pspecs", "sparse_pack_pspecs", "named",
    "logical_to_sharding",
]


def mesh_axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


def batch_axes(mesh: Mesh):
    """The composed data-parallel axis: ('pod','data') on multi-pod."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _fit(mesh: Mesh, dim: int, axis):
    """axis if dim divides by its extent, else None (replicate)."""
    if axis is None:
        return None
    return axis if dim % mesh_axis_size(mesh, axis) == 0 else None


def _spec(mesh: Mesh, shape, axes):
    """Build a PartitionSpec, dropping axes that do not divide."""
    return P(*(_fit(mesh, d, a) for d, a in zip(shape, axes)))


# Rules match on exact leaf names / path suffixes (NOT substrings: "u" is a
# real RWKV leaf and must not swallow "w_up").  Leading layer-stack dims are
# never sharded (the scan slices them).
_ROW_PARALLEL = ("w_down", "out_proj", "attn/wo", "self_attn/wo",
                 "cross_attn/wo", "tm/wo", "cm/wv")
_REPLICATED_LEAVES = {"w", "b", "a_log", "d_skip", "dt_bias", "mix", "w0",
                      "u", "conv_b", "norm_w", "ln_x", "router"}


def _param_rule(path: str, shape, mesh: Mesh, fsdp: bool, tp):
    dp = "data" if fsdp else None
    nd = len(shape)
    leaf = path.rsplit("/", 1)[-1]
    stacked = "layers/" in path  # leading dim is the scan axis

    def tail(*axes):
        return _spec(mesh, shape, (None,) * (nd - len(axes)) + tuple(axes))

    if leaf in _REPLICATED_LEAVES:
        return P(*(None,) * nd)
    # head-structured weights never take the wide TP axis: splitting a
    # head_dim across devices turns every QK/PV contraction into a
    # partial-sum all-reduce of full score tensors (refuted iter 4,
    # EXPERIMENTS.md Perf)
    headed = any(k in path for k in
                 ("attn/", "tm/", "mamba/", "conv_w"))
    wtp = "model" if headed else tp
    # an axis may appear once per spec: FSDP yields to a wide TP that
    # already uses 'data'
    wide_uses_data = isinstance(wtp, (tuple, list)) and "data" in wtp
    dpw = None if wide_uses_data else dp
    tp_uses_data = isinstance(tp, (tuple, list)) and "data" in tp
    dpt = None if tp_uses_data else dp
    # MoE experts: EP on 'model'; the FFN dim takes 'data' — via FSDP on
    # d_model when training, via TP on d_ff when serving (fsdp=False), so
    # expert weights never sit replicated across the data axis
    if "moe/w_gate" in path or "moe/w_up" in path:    # (L, E, D, F)
        return tail("model", dp, None if fsdp else "data")
    if "moe/w_down" in path:                          # (L, E, F, D)
        return tail("model", None if fsdp else "data", dp)
    if path.endswith("pos_embed") or path.endswith("embed"):  # (V|S, D)
        return tail(tp, None)  # vocab-sharded: logits stay V-sharded
    if path.endswith("lm_head"):                      # (D, V)
        return tail(dpt, tp)
    if "conv_w" in path:                              # (L, K, C)
        return tail(None, wtp)
    if any(path.endswith(k) or f"{k}/" in path for k in _ROW_PARALLEL):
        return tail(wtp, dpw)                         # (L, F_in, D)
    if nd >= 3 or (nd == 2 and not stacked):          # column-parallel default
        return tail(dpw, wtp)
    if nd == 2:                                       # stacked bias (L, F)
        return tail(wtp)
    return P(*(None,) * nd)                           # scalars / 1-D


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_pspecs(params_or_shapes, mesh: Mesh, fsdp: bool = True,
                 tp="model"):
    """PartitionSpec tree matching a params (or eval_shape) pytree.

    ``tp`` is the tensor-parallel axis (or axis tuple).  Serving uses
    ``tp=('data','model')`` — "2D TP": decode is a pin-bandwidth-bound MV
    (the paper's workload), so every chip becomes an ESPIM "bank" holding a
    weight slice and the per-device weight stream shrinks by the data-axis
    extent; the idle batch axis costs nothing (hillclimb iter 4).
    MoE experts stay on 'model' (EP) in either mode.
    """
    def leaf_spec(path, leaf):
        return _param_rule(_path_str(path), leaf.shape, mesh, fsdp, tp)

    return jax.tree_util.tree_map_with_path(leaf_spec, params_or_shapes)


def serve_param_pspecs(params_or_shapes, mesh: Mesh,
                       global_batch: int | None = None):
    """Decode-time param layout: no FSDP, TP over (data x model).

    At global_batch == 1 (long-context single-stream decode) the
    contraction dim additionally shards over 'data': partial-sum outputs
    are KBs, so XLA picks psum over weight all-gathers and the per-device
    weight stream drops by the data extent (hillclimb iter 8)."""
    tp = tuple(a for a in ("data", "model") if a in mesh.axis_names)
    fsdp = global_batch == 1
    return param_pspecs(params_or_shapes, mesh, fsdp=fsdp, tp=tp)


def batch_pspecs(batch_tree, mesh: Mesh):
    """Shard every leading batch dim over ('pod','data') when divisible."""
    ba = batch_axes(mesh)

    def leaf_spec(leaf):
        nd = len(leaf.shape)
        if nd == 0:
            return P()
        if leaf.shape[0] == 3 and nd == 3:  # positions3 (3, B, S)
            return _spec(mesh, leaf.shape, (None, ba, None))
        return _spec(mesh, leaf.shape, (ba,) + (None,) * (nd - 1))

    return jax.tree_util.tree_map(leaf_spec, batch_tree)


def cache_pspecs(cache_tree, mesh: Mesh):
    """Decode caches: (L, B, S, KV, hd) and friends.

    B -> ('pod','data') when divisible; heads -> 'model' when divisible,
    else the sequence/state dim picks up 'model' (length-sharded cache with
    partial-softmax collectives).
    """
    ba = batch_axes(mesh)

    def leaf_spec(path, leaf):
        name = _path_str(path)
        nd = len(leaf.shape)
        if nd <= 1:
            return P(*(None,) * nd)
        if name.endswith("len"):
            return P(None)
        if nd == 5 or name.endswith("_scale"):
            # (L, B, S, KV, hd) kv cache / (L, B, H, K, V) wkv state /
            # (L, B, S, KV) int8-cache scales — same layout logic
            l_, b, s, kv = leaf.shape[:4]
            b_ax = _fit(mesh, b, ba)
            kv_ax = _fit(mesh, kv, "model")
            # sequence parallelism over whatever is left: idle batch axes
            # (B=1 long-context) and, when heads cannot shard, 'model'
            leftover = [a for a in ("pod", "data")
                        if a in mesh.axis_names and b_ax is None]
            if kv_ax is None and "model" in mesh.axis_names:
                leftover.append("model")
            s_ax = _fit(mesh, s, tuple(leftover)) if leftover else None
            axes = (None, b_ax, s_ax, kv_ax) + ((None,) if nd == 5 else ())
            return P(*axes)
        if nd == 4:  # (L, B, K-1, C) conv state
            axes = [None, _fit(mesh, leaf.shape[1], ba), None,
                    _fit(mesh, leaf.shape[3], "model")]
            return P(*axes)
        if nd >= 2:
            return _spec(mesh, leaf.shape,
                         (None, ba) + (None,) * (nd - 2))
        return P(*(None,) * nd)

    return jax.tree_util.tree_map_with_path(leaf_spec, cache_tree)


def paged_cache_pspecs(pages_tree, mesh: Mesh):
    """Block-pool KV arenas: (Lx, num_blocks, block_size, KV[, hd]).

    A block is the paging unit, so it must live wholly on one shard: the
    *blocks* axis shards over the batch axes (pages of concurrent slots
    spread across the data-parallel devices — the slot -> block-table
    indirection is position-free, so any block placement is legal), KV
    heads take 'model' as in ``cache_pspecs``, and the intra-block
    sequence axis is never split.  Block tables are host-side numpy and
    need no spec.
    """
    ba = batch_axes(mesh)

    def leaf_spec(leaf):
        nd = len(leaf.shape)
        if nd < 4:
            return P(*(None,) * nd)
        axes = (None, _fit(mesh, leaf.shape[1], ba), None,
                _fit(mesh, leaf.shape[3], "model")) + (None,) * (nd - 4)
        return P(*axes)

    return jax.tree_util.tree_map(leaf_spec, pages_tree)


def sparse_pack_pspecs(sparse: dict, mesh: Mesh):
    """PartitionSpecs for the device arrays of a ``sparsify_model`` dict.

    The packed-row dim is the paper's bank dim: each device holds a
    contiguous packed row range of every bucket (values/codes, cols and
    the per-row ``srow`` scales shard together on it, when divisible by
    'model'), the dense activation stays replicated (the ICI broadcast),
    and the per-bucket SpMV runs bank-local.  ``perm``/``inv_perm`` are
    replicated — the static output ``take`` is a cross-bank gather the
    compiler lays out.  Layer-stack and chunk dims are never split (the
    scan slices the former; a chunk is one VMEM slab).

    Returns ``{group: {"buckets": [...], "perm": P, "inv_perm": P}}``
    matching the jnp leaves of ``sparse["groups"]``.
    """
    def bucket_spec(b):
        # packed rows are the minor (lane) dim of the kernel planes
        # (L, K, Lc, H*Rgp) and of the (L, H*Rg) scales
        out = {}
        for key in ("values", "q", "cols", "srow"):
            if key in b:
                shape = b[key].shape
                out[key] = P(*(None,) * (len(shape) - 1),
                             _fit(mesh, shape[-1], "model"))
        return out

    return {
        name: {
            "buckets": [bucket_spec(b) for b in g["buckets"]],
            "perm": P(None, None),
            "inv_perm": P(None, None),
        }
        for name, g in sparse["groups"].items()
    }


def named(mesh: Mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def logical_to_sharding(tree, specs, mesh: Mesh):
    """Device-put a pytree according to a spec tree."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs)
