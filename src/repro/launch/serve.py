"""Serving launcher: batched continuous decoding, optionally from ESPIM
sparse weights (the paper's deployment scenario) through the Pallas
kernels.

``python -m repro.launch.serve --arch granite-3-2b --reduced
    --requests 8 --espim-sparsity 0.9 --quant int8``

Weights are random from ``--seed``; nothing is downloaded.  With
``--espim-sparsity`` the decoder projections are pruned and compiled into
ESPIM pack groups (``sparsify_model``) and the engine runs them through
the Pallas kernels (natively on a TPU, in interpret mode elsewhere).
Before serving, one ``provenance`` line names the device, the kernel
lowering and whether Pallas runs interpreted.

The launcher keeps JAX's persistent compilation cache in
``$JAX_COMPILATION_CACHE_DIR`` when that is set, else in
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import time

import jax
import numpy as np

from repro.configs.registry import get_config
from repro.core.sparse_model import sparsify_model
from repro.kernels import ops
from repro.models import factory
from repro.serve.engine import Request, ServeEngine

__all__ = ["build_parser", "enable_compile_cache", "provenance", "build",
           "make_requests", "main"]

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="the arch's small same-family smoke config")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to this many layers")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--espim-sparsity", type=float, default=0.0,
                    help="prune + pack the projections (0: dense engine)")
    ap.add_argument("--quant", choices=("none", "int8", "int4"),
                    default="none", help="value-plane encoding of the packs")
    ap.add_argument("--projections", choices=("all", "mlp"), default="all",
                    help="which decoder projections serve from the packs")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--min-prompt", type=int, default=4)
    ap.add_argument("--max-prompt", type=int, default=4)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    return ap


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` (which JAX reads itself) or, when that
    is unset, at ``<checkout>/.jax_cache``.  Returns the directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def provenance(impl: str) -> dict:
    """Where the serving step runs: the device as JAX reports it, the
    kernel lowering the engine resolves ``impl`` to, and whether Pallas
    runs interpreted."""
    dev = jax.devices()[0]
    prov = ops.provenance(impl=impl)
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices()), "impl": prov["impl"],
            "pallas_interpret": prov["pallas_interpret"],
            "env": prov["env"]}


def build(args) -> dict:
    """Config, random params, packs (when ``--espim-sparsity``) and the
    engine, each phase timed on the host clock: ``{"cfg", "params",
    "sparse", "engine", "impl", "seconds": {phase: s}}``.  ``pack``
    covers prune + pack + quantize + the upload of the packs and pruned
    copies; ``engine`` covers the wait for every buffer to be resident,
    the engine's load-time pack verification and its cache allocation."""
    secs = {}
    t = time.perf_counter()
    cfg = get_config(args.arch, reduced=args.reduced)
    if args.layers is not None:
        cfg = cfg.replace(n_layers=args.layers)
    params = factory.init_params(cfg, jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    secs["init"] = time.perf_counter() - t

    sparse, impl = None, "ref"
    if args.espim_sparsity > 0:
        t = time.perf_counter()
        sparse = sparsify_model(cfg, params, args.espim_sparsity,
                                projections=args.projections,
                                quant=args.quant)
        secs["pack"] = time.perf_counter() - t
        impl = "pallas"

    t = time.perf_counter()
    if sparse is not None:
        jax.block_until_ready((sparse["pruned"], [
            b for g in sparse["groups"].values() for b in g["buckets"]]))
    engine = ServeEngine(cfg, params, batch_slots=args.slots,
                         max_len=args.max_len, temperature=args.temperature,
                         sparse=sparse, impl=impl,
                         prefill_chunk=args.prefill_chunk, seed=args.seed)
    secs["engine"] = time.perf_counter() - t
    return {"cfg": cfg, "params": params, "sparse": sparse,
            "engine": engine, "impl": impl, "seconds": secs}


def make_requests(cfg, args, rid0: int = 0) -> list:
    """``args.requests`` seeded requests with prompt lengths uniform in
    [min_prompt, max_prompt]."""
    rng = np.random.default_rng(args.seed + 1)
    reqs = []
    for i in range(args.requests):
        n = int(rng.integers(args.min_prompt, args.max_prompt + 1))
        prompt = rng.integers(0, cfg.vocab_size, size=n).tolist()
        reqs.append(Request(rid=rid0 + i, prompt=prompt,
                            max_new_tokens=args.max_new_tokens))
    return reqs


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    built = build(args)
    print("provenance " + json.dumps(provenance(built["impl"])), flush=True)
    eng = built["engine"]
    for req in make_requests(built["cfg"], args):
        eng.submit(req)
    t0 = time.perf_counter()
    stats = eng.run()
    dt = time.perf_counter() - t0
    print(f"completed {stats.requests_completed} requests, "
          f"{stats.tokens_generated} tokens in {dt:.2f}s "
          f"({stats.tokens_generated / max(dt, 1e-9):.1f} tok/s on "
          f"{jax.devices()[0].platform}, {stats.steps} engine steps, "
          f"compile included)")


if __name__ == "__main__":
    main()
