"""The dense family: a decoder of grouped-query attention and an MLP in
every layer, every projection pruned and packed.

A family module gives the harness what depends on the layers a
configuration's ``model.family`` names: the seeded weights, the plain
float32 reference with its control and witness variants, and the
operation and byte counts.  The harness loads
``bench/families/<family>.py`` and calls only these five names:

* ``make_params(m, seed)``: the served weight tree, on the device;
* ``readings(m, seed, sparsity, projections, rows, max_len, variants)``:
  per served token, the gap below the reference's best logit;
* ``token_flops(m, sparsity, projections, context)``: required
  operations of one token at a context length;
* ``espim_step_bytes(sparse, batch)`` and ``espim_step_ops(sparse,
  batch)``: what one decode step's ESPIM launches move and compute.
"""
from benchlib.flops import espim_step_bytes, espim_step_ops, token_flops
from benchlib.reference import readings
from benchlib.weights import make_params

__all__ = ["make_params", "readings", "token_flops", "espim_step_bytes",
           "espim_step_ops"]
