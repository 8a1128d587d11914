#!/usr/bin/env python3
"""Chip smoke test: serve granite-3-2b at full width and depth on one TPU
through the native ESPIM Pallas kernels, and check what comes out.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py              # all 40 layers
    python chip_smoke.py --layers 2   # a shallower run, for debugging

It drives the normal serving entry point (``repro.launch.serve``) with
random weights from a seed, in one process, through these phases:

* init    — random bf16 params on the device;
* pack    — prune every decoder projection to 90% sparsity, compile the
            pack groups (fused QKV, O, gate+up, down), quantize the value
            planes to int8 and upload them (host CPU work, done once);
* engine  — wait for every buffer, verify the packs, allocate the cache;
* compile — one warm-up request compiles the prefill chunk and the
            decode step;
* serve   — 8 seeded requests (prompts of 16-256 tokens, 32 new tokens
            each), 4 slots, greedy, paged KV cache;
* parity  — the Pallas kernels against the jnp reference over the same
            device packs: every bucket launch of layer 0's four groups
            (max |diff| / max |ref| <= 1e-5), and one whole decode step's
            logits twice: with the serving bf16 activations (reported:
            bf16 rounding makes this about as far apart as any two f32
            summation orders), and with f32 activations around the same
            packs and launches (<= 2e-2).  Argmax agreement is printed,
            not gated.

Every phase prints its host-clock seconds and the device's
``peak_bytes_in_use``.  The script exits non-zero, without a result
line, when JAX finds no TPU or a device kind it does not know, when a
kernel-selection override (``ESPIM_IMPL``, ``ESPIM_FORCE_INTERPRET``) is
set, when the engine would not run the native kernels, when any request
ends other than completed on the sparse path, or when parity is out of
bounds.  Its last line is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# device kinds this run is sized for: granite-3-2b's params, pruned
# copies and int8 packs take about 12 GB of one v5e's 16 GiB of HBM
KNOWN_KINDS = ("TPU v5 lite",)

ARGS = ["--arch", "granite-3-2b", "--espim-sparsity", "0.9",
        "--quant", "int8", "--projections", "all", "--requests", "8",
        "--min-prompt", "16", "--max-prompt", "256",
        "--max-new-tokens", "32", "--slots", "4", "--max-len", "320",
        "--prefill-chunk", "64", "--seed", "0"]
LAUNCH_TOL = 1e-5
LOGITS_TOL = 2e-2


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", flush=True)
    sys.exit(1)


def peak_bytes() -> int:
    import jax
    return int(jax.devices()[0].memory_stats().get("peak_bytes_in_use", 0))


def phase(name: str, fn):
    t = time.perf_counter()
    out = fn()
    print(f"phase {name}: {time.perf_counter() - t:.3f} s, "
          f"peak_bytes_in_use {peak_bytes()}", flush=True)
    return out


def rel_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def check_stats(stats, n_req: int, n_tok: int) -> None:
    states = stats.latency_summary()["states"]
    bad = {k: getattr(stats, k) for k in (
        "quarantines", "retries", "degraded_tokens", "requests_degraded",
        "requests_shed", "requests_failed", "requests_cancelled",
        "requests_deadline_expired", "degraded_to_dense")
        if getattr(stats, k)}
    if bad or states != {"completed": n_req} \
            or stats.requests_completed != n_req \
            or stats.tokens_generated != n_tok:
        fail(f"serving did not complete cleanly: states={states} "
             f"completed={stats.requests_completed} "
             f"tokens={stats.tokens_generated} (want {n_req}/{n_tok}) "
             f"faults={bad}")


def launch_parity(sparse: dict, slots: int, seed: int) -> float:
    """Every bucket launch of layer 0, Pallas vs reference, same x."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.sparse_model import projection_arrays
    from repro.kernels import ops
    rng = np.random.default_rng(seed)
    proj = projection_arrays(sparse)
    worst = 0.0
    for name, g in sparse["groups"].items():
        x = jnp.asarray(rng.standard_normal((g["n_cols"], slots)),
                        jnp.float32)
        glu = name == "gateup" and sparse["gated"]
        for gi, buf in enumerate(proj[name]["bufs"]):
            planes = [a[0] for a in buf]
            kw = dict(chunk_cols=g["chunk_cols"], rows=g["bucket_rows"][gi],
                      width=g["widths"][gi], halves=g["halves"],
                      epilogue="glu" if glu else None)
            if g["quant"] is not None:
                kw["srow"] = planes.pop()
            out = {impl: np.asarray(ops.espim_spmv_planes(
                *planes, x, impl=impl, **kw)) for impl in ("pallas", "ref")}
            err = rel_err(out["pallas"], out["ref"])
            worst = max(worst, err)
            print(f"parity launch {name}[{gi}] planes "
                  f"{tuple(planes[1].shape)} {planes[0].dtype} "
                  f"epilogue={kw['epilogue']}: rel_err {err:.3e}",
                  flush=True)
    return worst


def f32_step_params(params: dict) -> dict:
    """What a decode step reads when the packs cover every projection —
    embedding, norms, attention biases — cast to f32.  The dense
    projection weights the packs replace are left out (in f32 they would
    not fit next to the serving state)."""
    import jax
    import jax.numpy as jnp
    layers = params["layers"]
    small = {k: v for k, v in params.items() if k != "layers"}
    small["layers"] = {
        "ln1": layers["ln1"], "ln2": layers["ln2"],
        "attn": {k: v for k, v in layers["attn"].items()
                 if k.startswith("b")}}
    return jax.tree.map(lambda a: a.astype(jnp.float32), small)


def step_parity(cfg, params, sparse: dict, slots: int, max_len: int,
                seed: int, f32: bool) -> tuple:
    """One decode step's logits, Pallas vs reference, over a cache of
    random K/V rows at random lengths.

    ``f32=False`` runs the serving dtype (bf16 activations between the
    launches); ``f32=True`` carries the activations, cache and the
    remaining dense matmuls in f32 at full matmul precision around the
    same packs and launches, so that only the kernels' f32 summation
    order separates the two paths."""
    import contextlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import sparse_model
    from repro.models import factory
    precision = contextlib.nullcontext()
    if f32:
        cfg = cfg.replace(param_dtype="float32", compute_dtype="float32")
        params = f32_step_params(params)
        precision = jax.default_matmul_precision("highest")
    key = jax.random.PRNGKey(seed)
    kk, kv = jax.random.split(key)
    cache = factory.init_cache(cfg, slots, max_len)
    cache["k"] = jax.random.normal(kk, cache["k"].shape, cache["k"].dtype)
    cache["v"] = jax.random.normal(kv, cache["v"].shape, cache["v"].dtype)
    rng = np.random.default_rng(seed)
    cache["len"] = jnp.asarray(rng.integers(1, max_len - 1, size=slots),
                               jnp.int32)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(slots, 1)), jnp.int32)}
    proj = sparse_model.projection_arrays(sparse)
    logits = {}
    for impl in ("pallas", "ref"):
        fn = jax.jit(lambda p, x, c, b, impl=impl:
                     sparse_model.decode_step_sparse(
                         cfg, p, sparse, c, b, impl=impl, proj=x)[0])
        with precision:
            out = fn(params, proj, cache, batch)
        logits[impl] = np.asarray(out[:, 0, :cfg.vocab_size], np.float32)
    err = rel_err(logits["pallas"], logits["ref"])
    agree = float((logits["pallas"].argmax(-1)
                   == logits["ref"].argmax(-1)).mean())
    return err, agree


def main(argv: list) -> None:
    for var in ("ESPIM_IMPL", "ESPIM_FORCE_INTERPRET"):
        if os.environ.get(var):
            fail(f"{var} is set; the smoke test runs the default dispatch")
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device {json.dumps(device)}", flush=True)
    if dev.platform != "tpu":
        fail(f"no TPU: JAX runs on {dev.platform}")
    if dev.device_kind not in KNOWN_KINDS:
        fail(f"unknown device kind {dev.device_kind!r} "
             f"(known: {sorted(KNOWN_KINDS)})")

    from repro.launch import serve
    from repro.serve.engine import Request
    args = serve.build_parser().parse_args(ARGS + argv)
    print(f"compile cache {serve.enable_compile_cache()}", flush=True)
    t = time.perf_counter()
    built = serve.build(args)
    for name, s in built["seconds"].items():
        print(f"phase {name}: {s:.3f} s", flush=True)
    print(f"build total: {time.perf_counter() - t:.3f} s, "
          f"peak_bytes_in_use {peak_bytes()}", flush=True)
    cfg, eng, sparse = built["cfg"], built["engine"], built["sparse"]
    if args.layers is not None:
        print(f"NOTE: depth cut to {cfg.n_layers} layers", flush=True)
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model},"
          f" d_ff {cfg.d_ff}, vocab {cfg.vocab_size}", flush=True)

    prov = serve.provenance(built["impl"])
    print("provenance " + json.dumps(prov), flush=True)
    if prov["impl"] != "pallas" or prov["pallas_interpret"]:
        fail("the engine would not run the native Pallas kernels")

    def warm_up():
        eng.submit(Request(rid=args.requests, prompt=list(range(1, 17)),
                           max_new_tokens=2))
        check_stats(eng.run(), 1, 2)
        eng.reset_stats()

    phase("compile", warm_up)

    def serve_all():
        reqs = serve.make_requests(cfg, args)
        for r in reqs:
            eng.submit(r)
        t0 = time.perf_counter()
        stats = eng.run()
        dt = time.perf_counter() - t0
        n_tok = args.requests * args.max_new_tokens
        check_stats(stats, args.requests, n_tok)
        lat = stats.latency_summary()
        print(f"served {stats.requests_completed}/{args.requests} requests,"
              f" {stats.tokens_generated} tokens, prompts "
              f"{sum(len(r.prompt) for r in reqs)} tokens, in {dt:.3f} s "
              f"host clock ({stats.tokens_generated / dt:.1f} tok/s), "
              f"{stats.decode_steps} decode steps, {stats.prefill_chunks} "
              f"prefill chunks; quarantines {stats.quarantines}, retries "
              f"{stats.retries}, degraded tokens {stats.degraded_tokens}; "
              f"ttft_s {json.dumps(lat['ttft_s'])}", flush=True)

    phase("serve", serve_all)

    worst = phase("parity_launches",
                  lambda: launch_parity(sparse, args.slots, args.seed + 2))
    print(f"parity launches: worst rel_err {worst:.3e} (bound {LAUNCH_TOL})",
          flush=True)
    def step(f32: bool):
        return step_parity(cfg, built["params"], sparse, args.slots,
                           args.max_len, args.seed + 3, f32)

    # bf16 rounding between 40 layers turns any f32 reordering inside a
    # launch into logit differences of the order of the bound, so the
    # serving-dtype step is reported and the f32 step is gated
    err, agree = phase(f"parity_step_{cfg.compute_dtype}", lambda: step(False))
    print(f"parity step logits, {cfg.compute_dtype} activations: rel_err "
          f"{err:.3e} (not gated), argmax agreement {agree:.3f}", flush=True)
    err, agree = phase("parity_step_float32", lambda: step(True))
    print(f"parity step logits, float32 activations: rel_err {err:.3e} "
          f"(bound {LOGITS_TOL}), argmax agreement {agree:.3f}", flush=True)
    if not worst <= LAUNCH_TOL:
        fail(f"launch parity {worst:.3e} > {LAUNCH_TOL}")
    if not err <= LOGITS_TOL:
        fail(f"step logits parity {err:.3e} > {LOGITS_TOL}")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
