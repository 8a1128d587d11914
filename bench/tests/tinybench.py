"""A tiny benchmark layout for the CPU tests: the same harness, a
granite-family configuration cut to a few layers of small width, and the
two traffic mixes at toy lengths, written under a temporary root that
links the program's sources and the family modules."""
from __future__ import annotations

import json
import os
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))

MODEL = {"family": "dense", "n_layers": 2, "d_model": 128, "n_heads": 4,
         "n_kv_heads": 2, "head_dim": 48, "d_ff": 256, "vocab_size": 500,
         "activation": "silu", "gated_mlp": True, "tie_embeddings": True,
         "rope_theta": 10000.0, "norm": "rmsnorm", "norm_eps": 1e-05,
         "qkv_bias": False, "param_dtype": "bfloat16",
         "compute_dtype": "bfloat16", "kv_cache_dtype": "bfloat16"}
OVERRIDES = {k: MODEL[k] for k in ("n_layers", "d_model", "n_heads",
                                   "n_kv_heads", "head_dim", "d_ff",
                                   "vocab_size")}
MIXES = {
    "backlog": {"loop": "closed", "block": 8,
                "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.6,
                           "min": 4, "max": 40},
                "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                           "min": 3, "max": 12}},
    "poisson": {"loop": "open", "block": 8,
                "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                           "min": 8, "max": 40},
                "output": {"dist": "uniform", "min": 2, "max": 6}},
}


def make(root: pathlib.Path, quant: str = "int8", limits: dict | None = None,
         rate: float = 20.0,
         families: pathlib.Path | None = None) -> pathlib.Path:
    """Write the layout under ``root``; returns ``root``.  ``families``
    is the directory of family modules the layout links (the benchmark's
    own by default)."""
    (root / "bench" / "configs").mkdir(parents=True, exist_ok=True)
    (root / "bench" / "cells").mkdir(exist_ok=True)
    (root / "bench" / "traffic").mkdir(exist_ok=True)
    if not (root / "src").exists():
        os.symlink(REPO / "src", root / "src")
    if not (root / "bench" / "families").exists():
        os.symlink(families or BENCH / "families",
                   root / "bench" / "families")
    conf = {"name": "tiny", "arch": "granite-3-2b", "overrides": OVERRIDES,
            "model": MODEL, "reduced": [], "sparsity": 0.9, "quant": quant,
            "projections": "all", "weight_seed": 7}
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(conf))
    check = {"sample": 32, "min_tokens": 8,
             "limits": limits or {"mean_gap": 0.5}}
    cells = {"tiny.backlog": {"slots": 2, "max_len": 64, "prefill_chunk": 16,
                              "check": check},
             "tiny.poisson": {"slots": 2, "max_len": 64, "prefill_chunk": 16,
                              "rate_rps": rate, "check": check}}
    for name, c in cells.items():
        (root / "bench" / "cells" / f"{name}.json").write_text(json.dumps(c))
    for name, mx in MIXES.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mx))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [
        {"name": n, "config": "tiny", "traffic": n.split(".")[1],
         "chips": 1, "why": "test"} for n in cells]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.poisson"]
    if all(m["name"] != "ttft_p95_s" for m in spec["end_to_end"]):
        spec["end_to_end"].append(
            {"name": "ttft_p95_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock",
             "workloads": ["tiny.poisson"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
