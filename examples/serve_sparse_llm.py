"""The paper's deployment scenario: serve an LM whose projections were
magnitude-pruned and packed into the ESPIM format, through the production
serving stack — paged KV cache, chunked prefill, and a latency-aware
scheduler — and compare the sparse projections' outputs against the
dense-pruned reference.

``--quant {none,int8,int4}`` (default: the config's serving preset,
int8 for llama7b-espim) re-encodes the packs' value planes (DESIGN.md
section 9) and prints the measured weight-bytes/token reduction.
``--sparse-attn`` serves the WHOLE decoder layer from the format — the
fused QKV + O pack groups (DESIGN.md section 10) on top of the MLP packs
— and prints the dense-attention vs whole-layer bytes/token delta.
``--trace out.json`` records every engine phase (scheduler / prefill /
decode / host sync) as nested spans and writes a Perfetto/Chrome trace —
open it at https://ui.perfetto.dev — plus a per-phase breakdown on
stdout (DESIGN.md section 12).

Run:  PYTHONPATH=src python examples/serve_sparse_llm.py \
          [--quant int4] [--sparse-attn] [--trace out.json]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.core.espim_linear import ESPIMGroupLinear
from repro.core.pruning import magnitude_prune
from repro.core.sparse_model import sparse_stats, sparsify_model
from repro.kernels import ops
from repro.models import factory
from repro.serve.engine import Request, ServeEngine
from repro.telemetry.timeline import format_timeline, timelines_from_tracer
from repro.telemetry.trace import Tracer, phase_breakdown

SPARSITY = 0.9

cfg = get_config("llama7b-espim", reduced=True)
ap = argparse.ArgumentParser()
ap.add_argument("--quant", choices=("none", "int8", "int4"),
                default=cfg.espim_quant,
                help="value-plane encoding for the packed projections "
                     f"(default: the config preset, {cfg.espim_quant})")
ap.add_argument("--sparse-attn", action="store_true",
                help="pack q/k/v/o too (fused QKV + O groups) and serve "
                     "every per-token MV from the compressed format")
ap.add_argument("--trace", default=None, metavar="PATH",
                help="write a Perfetto/Chrome trace of the serving run "
                     "(open at https://ui.perfetto.dev); .jsonl paths get "
                     "the plain event-log format instead")
ap.add_argument("--autotune", action="store_true",
                help="tune the SDDS kernel schedule on the model's own "
                     "layer-0 gate matrix (searched, then re-tuned off the "
                     "warm plan cache), serve a second engine under the "
                     "tuned chunking, and print the tok/s delta vs the "
                     "default schedule")
args = ap.parse_args()
QUANT = args.quant
tracer = Tracer(enabled=args.trace is not None)
params = factory.init_params(cfg, jax.random.PRNGKey(0))

# --- flexible dense/sparse projections (Section III-I) ---------------------
# Pack layer 0's q/k/v as ONE fused group (shared balance perm, one SpMV
# launch for all three) and verify each output against its dense-pruned
# reference — the PackGroup contract as a standalone layer.
print(f"packing layer-0 q/k/v as one fused group at {SPARSITY:.0%} "
      f"sparsity:")
rng = np.random.default_rng(0)
named = {name: np.asarray(params["layers"]["attn"][name][0], np.float32).T
         for name in ("wq", "wk", "wv")}
group = ESPIMGroupLinear.from_dense(named, prune_sparsity=SPARSITY)
x = rng.standard_normal(cfg.d_model).astype(np.float32)
ys = group(jnp.asarray(x), impl="ref")
for name, w in named.items():
    ref = magnitude_prune(w, SPARSITY) @ x
    print(f"  {name}: max err vs dense-pruned = "
          f"{np.abs(np.asarray(ys[name]) - ref).max():.2e} "
          f"(one launch for all of {'/'.join(group.names)})")

# --- production serving: paged cache + chunked prefill + scheduler ---------
# A mixed-length trace: short chat-like prompts interleaved with long ones.
# The shortest-prompt-first policy admits the short prompts ahead of the
# long ones (lower mean TTFT); chunked prefill turns each long prompt into
# ceil(len/chunk) jitted calls; all slots share one block-pool KV arena.
# ``--quant`` serves decode from int8/int4 value planes (section 9): same
# packs, same schedules, narrow codes + per-row-group scales.
# ``--sparse-attn`` compiles the fused QKV + O groups too (section 10) so
# decode runs EVERY per-token MV through the packed kernels.
proj = "all" if args.sparse_attn else "mlp"
sparse = sparsify_model(cfg, params, SPARSITY, projections=proj,
                        quant=QUANT)
st_all = sparse_stats(sparse)
st = st_all["total"]
if args.sparse_attn:
    # the delta the flag buys: whole-layer packed vs MLP-only (which still
    # streams every dense attention byte per decode token).  No second
    # packing pass: the MLP-only baseline is the gateup+down planes of
    # THIS pack plus the dense q/k/v/o bytes.
    attn_w = params["layers"]["attn"]
    attn_dense = sum(int(np.size(attn_w[n])) * attn_w[n].dtype.itemsize
                     for n in ("wq", "wk", "wv", "wo"))
    mlp_only = attn_dense + sum(
        st_all[g]["value_plane_bytes"] + st_all[g]["index_plane_bytes"]
        for g in ("gateup", "down"))
    print(f"\nsparse-attn: whole-model weight bytes/token "
          f"{mlp_only} (MLP packs + {attn_dense} dense attention bytes) "
          f"-> {st['bytes_per_token']} all-packed "
          f"({mlp_only / st['bytes_per_token']:.2f}x smaller)")
if QUANT != "none":
    # the fp baseline needs no second packing pass: fp32 values cost 4
    # bytes/slot — exactly the quant-invariant int32 index plane's size
    fp_bytes = (2 * st["index_plane_bytes"]
                + st["dense_proj_bytes_per_token"])
    fp_bits = 8.0 * st["index_plane_bytes"] / st["nnz"]
    print(f"\nquant={QUANT}: weight bytes/token "
          f"{fp_bytes} -> {st['bytes_per_token']} "
          f"({fp_bytes / st['bytes_per_token']:.2f}x smaller; value plane "
          f"{st['bits_per_nnz']:.1f} bits/nnz vs fp {fp_bits:.1f})")

# --- per-shape schedule autotuning (DESIGN.md section 15) ------------------
# Search the legal schedule space for the model's own layer-0 gate matrix
# (cost-ranked, top-k measured), then tune again: the second call must be
# a pure fingerprint-keyed cache hit — zero candidate benchmarks.
tuned_plan = None
if args.autotune:
    from repro.autotune import (PlanCache, autotune_pack,
                                reset_search_stats, search_stats)
    from repro.core.sparse_format import pack_ell

    w0 = magnitude_prune(
        np.asarray(params["layers"]["mlp"]["w_gate"][0], np.float32).T,
        SPARSITY)
    pack = pack_ell(w0)
    qmode = None if QUANT == "none" else QUANT
    plan_cache = PlanCache()
    reset_search_stats()
    tuned_plan = autotune_pack(pack, b=1, quant=qmode, cache=plan_cache)
    searched = dict(search_stats)
    cached_plan = autotune_pack(pack, b=1, quant=qmode, cache=plan_cache)
    p = tuned_plan.to_provenance()
    print(f"\nautotune ({w0.shape[0]}x{w0.shape[1]} gate matrix, "
          f"quant={QUANT}):")
    print(f"  searched: chunk_cols={p['chunk_cols']} block_r={p['block_r']} "
          f"block_l={p['block_l']} gather={p['gather']} "
          f"({p['candidates']} candidates measured, best "
          f"{p['best_us']:.1f}us, cache key {p['cache_key'][:12]}...)")
    print(f"  re-tuned: source={cached_plan.source} "
          f"({search_stats['benchmarks'] - searched['benchmarks']} "
          f"benchmarks — the warm plan cache skips the search entirely)")

prompt_lens = [3, 40, 2, 56, 5, 24, 4, 12]
prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
           for n in prompt_lens]

eng = ServeEngine(cfg, params, batch_slots=4, max_len=96, sparse=sparse,
                  paged=True, block_size=16, prefill_chunk=16,
                  policy="sjf", tracer=tracer)
reqs = [Request(rid=rid, prompt=p, max_new_tokens=12)
        for rid, p in enumerate(prompts)]
for r in reqs:
    eng.submit(r)
t0 = time.time()
stats = eng.run()
dt = time.time() - t0
lat = stats.latency_summary()
print(f"\nserved {stats.requests_completed} requests / "
      f"{stats.tokens_generated} tokens in {dt:.1f}s "
      f"({stats.tokens_generated / dt:.1f} tok/s on "
      f"{jax.devices()[0].platform}; "
      f"{stats.prefill_chunks} prefill chunks + {stats.decode_steps} "
      f"decode steps, slot occupancy {stats.slot_occupancy:.0%})")
print(f"TTFT p50/p95 = {lat['ttft_s']['p50']:.3f}/"
      f"{lat['ttft_s']['p95']:.3f}s, "
      f"TPOT p50 = {lat['tpot_s']['p50'] * 1e3:.1f}ms, "
      f"queue delay p95 = {lat['queue_delay_s']['p95']:.3f}s "
      f"(sjf over {len(reqs)} mixed-length prompts, "
      f"arena {eng.cache.num_blocks} x {eng.cache.block_size}-token "
      f"blocks)")

if tuned_plan is not None:
    # serve the SAME trace again with the packs chunked under the tuned
    # schedule — the tok/s delta the search bought (identical tokens: a
    # schedule is a performance knob, never a semantics knob)
    sparse_t = sparsify_model(cfg, params, SPARSITY, projections=proj,
                              quant=QUANT,
                              chunk_cols=tuned_plan.schedule.chunk_cols)
    eng_t = ServeEngine(cfg, params, batch_slots=4, max_len=96,
                        sparse=sparse_t, paged=True, block_size=16,
                        prefill_chunk=16, policy="sjf")
    for rid, pr in enumerate(prompts):
        eng_t.submit(Request(rid=rid, prompt=pr, max_new_tokens=12))
    t0 = time.time()
    stats_t = eng_t.run()
    dt_t = time.time() - t0
    tok_s = stats.tokens_generated / dt
    tok_s_t = stats_t.tokens_generated / dt_t
    print(f"\nautotuned engine (chunk_cols="
          f"{tuned_plan.schedule.chunk_cols} vs default "
          f"{ops.DEFAULT_CHUNK_COLS}): {tok_s_t:.1f} tok/s vs "
          f"{tok_s:.1f} default "
          f"({(tok_s_t / max(tok_s, 1e-9) - 1) * 100:+.1f}%)")

if args.trace:
    prov = ops.provenance(impl=eng.impl, quant=QUANT,
                          attn="sparse" if args.sparse_attn else "dense")
    if args.trace.endswith(".jsonl"):
        tracer.write_jsonl(args.trace, provenance=prov)
    else:
        tracer.write_chrome_trace(args.trace, provenance=prov)
    bd = phase_breakdown(tracer, parent="engine.step")
    phases = ", ".join(f"{k} {v['frac']:.0%}"
                       for k, v in sorted(bd["phases"].items(),
                                          key=lambda kv: -kv[1]["frac"]))
    print(f"\ntrace: {len(tracer.spans())} spans -> {args.trace} "
          f"(open at https://ui.perfetto.dev)\n"
          f"engine.step breakdown ({bd['coverage']:.0%} of "
          f"{bd['wall_us'] / 1e3:.1f}ms step wall): {phases}")
    # per-request timelines (DESIGN.md §14): the same trace, folded into
    # one lifecycle strip per request — q=queued, p=prefill, d=decode,
    # .=resident-but-waiting
    print("\nper-request timelines:")
    tls = timelines_from_tracer(tracer)
    for rid in sorted(tls):
        print(format_timeline(tls[rid]))
