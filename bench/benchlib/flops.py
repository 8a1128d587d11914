"""Operations and bytes, computed from shapes.

* ``token_flops``: the operations one token requires of the model, at the
  position it is processed: 2 x every kept projection weight (density
  from the configuration, not from the packed format), 2 x the LM head,
  and attention over the token's context (QK^T and PV, 2 x 2 x heads x
  head_dim x context, per layer).  The same count whatever implements
  it, so a change of kernel or format cannot raise it.
* ``espim_step_bytes``: the bytes one decode step's ESPIM launches must
  move: every value (or code), index and scale plane of every bucket of
  every group and layer as stored, plus each launch's x and output in
  float32, at the step's batch width.
"""
from __future__ import annotations

import numpy as np

from benchlib import weights as W

__all__ = ["projection_params", "token_flops", "espim_step_bytes",
           "espim_step_ops"]


def projection_params(m: dict) -> dict:
    """{(module, name): weights per layer} of the decoder projections."""
    return {k: int(np.prod(s)) for k, s in W.layer_shapes(m).items()
            if len(s) == 2}


def token_flops(m: dict, sparsity: float, projections: str,
                context: int) -> float:
    """Required operations of one token whose attention sees ``context``
    positions (itself included)."""
    kept = 0.0
    for (mod, _), n in projection_params(m).items():
        covered = projections == "all" or projections == mod
        kept += n * ((1.0 - sparsity) if covered else 1.0)
    per_layer = 2.0 * kept + 4.0 * int(m["n_heads"]) * W.head_dim(m) \
        * int(context)
    head = 2.0 * int(m["d_model"]) * int(m["vocab_size"])
    return int(m["n_layers"]) * per_layer + head


def _launches(sparse: dict):
    """(group, bucket dict, output rows, input cols, n_layers) per bucket
    launch of one decode step."""
    for name, g in sparse["groups"].items():
        glu = name == "gateup" and sparse["gated"]
        for gi, b in enumerate(g["buckets"]):
            plane = b["q"] if "q" in b else b["values"]
            rows = g["bucket_rows"][gi] * (1 if glu else g["halves"])
            yield b, plane, rows, g["n_cols"], plane.shape[0]


def espim_step_bytes(sparse: dict, batch: int) -> int:
    total = 0
    for b, plane, rows, cols, layers in _launches(sparse):
        total += int(plane.nbytes) + int(b["cols"].nbytes)
        if "srow" in b:
            total += int(b["srow"].nbytes)
        total += layers * (cols + rows) * batch * 4
    return total


def espim_step_ops(sparse: dict, batch: int) -> int:
    """Multiply-adds x 2 over every stored slot of every plane."""
    total = 0
    for b, plane, rows, cols, layers in _launches(sparse):
        total += 2 * int(np.prod(b["cols"].shape)) * batch
    return total
