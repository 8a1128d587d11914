"""Whole runs of the harness on the CPU at a tiny size, with the look for
a chip skipped: a sound run is correct, the control (the reference with
int4 weights in the program's place, one step below the int8 the
configuration states) and a token altered where the engine emits it are
not, and without a TPU the entry point exits non-zero with no result line.

A family module the test puts in place of the dense one is the one the
harness calls; ``_check_model`` compares every field of the program's
config that the file states; readers get the program's spans and
counters over the profiled half.

Every request the window served is compared (some 30, 125-280 tokens).
The tiny cell's limit on the mean gap (0.001) lies above the tiny sound
runs' readings (0 to 0.0001 on seeds 1-8 of both mixes) and below the
int4 control's (0.0044 to 0.014 on the same seeds).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

import tinybench
from benchlib import harness

LIMITS = {"mean_gap": 0.001}


def _run(tmp_path, name, seed, *, fault=None, trace=False, control=False):
    root = tinybench.make(tmp_path / "tiny", limits=LIMITS)
    cell = harness.Cell(root, name)
    return harness.run(cell, seed, 1.5, trace, time.perf_counter(),
                       require_tpu=False, impl="ref", fault=fault,
                       cache_dir=root / "bench" / ".cache", control=control)


@pytest.mark.parametrize("name", ["tiny.backlog", "tiny.poisson"])
def test_a_sound_run_is_correct(tmp_path, name):
    out = _run(tmp_path, name, 3)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    spec = json.loads((tinybench.REPO / "BENCHMARK.json").read_text())
    want = {m["name"] for m in spec["end_to_end"] if "workloads" not in m}
    if name == "tiny.poisson":
        want.add("ttft_p95_s")
    assert set(out["metrics"]) == want
    assert out["device"]["platform"] == "cpu"


def test_a_traced_run_reads_the_program_spans(tmp_path):
    out = _run(tmp_path, "tiny.backlog", 4, trace=True)
    assert out["correct"], out["checks"]
    assert {"kv_copy_ms", "engine_host_ms", "mfu"} <= set(out["metrics"])
    # no device plane on the CPU: the device-trace metrics stay silent
    assert "device_idle" not in out["metrics"]
    assert out["device"]["window_s"] > 0


@pytest.mark.parametrize("seed", [1, 2])
def test_the_int4_control_is_not_correct(tmp_path, seed):
    out = _run(tmp_path, "tiny.backlog", seed, control=True)
    assert out["correct"], out["checks"]
    assert not out["control"]["correct"], out["control"]
    # the witness of the program's own rounding reads as a sound run does
    assert out["witness"]["correct"], out["witness"]
    assert list(out)[-1] == "checks"


def _alter_tokens(eng):
    """Every request's third served token comes out one id off."""
    emit, vocab = eng._emit_token, eng.cfg.vocab_size

    def bad(i, tok):
        if len(eng.slots[i].req.output) == 2:
            tok = (tok + 1) % vocab
        emit(i, tok)
    eng._emit_token = bad


@pytest.mark.parametrize("name", ["tiny.backlog", "tiny.poisson"])
def test_an_altered_token_is_not_correct(tmp_path, name):
    out = _run(tmp_path, name, 3, fault=_alter_tokens)
    assert not out["correct"]
    assert out["checks"]["mean_gap"]["value"] > LIMITS["mean_gap"]


def test_without_a_tpu_the_entry_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    spec = json.loads((tinybench.REPO / "BENCHMARK.json").read_text())
    cmd = [sys.executable, str(tinybench.BENCH / "run.py"), "--workload",
           spec["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
           "--trace", "0"]
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=300, cwd=tinybench.REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_an_unknown_device_kind_is_refused():
    class Dev:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    class Jax:
        @staticmethod
        def devices():
            return [Dev()]
    peaks = json.loads((tinybench.BENCH / "peaks.json").read_text())
    with pytest.raises(harness.RunError, match="peaks table"):
        harness.check_device(Jax, peaks, 1)
    Dev.device_kind = "TPU v5 lite"
    assert harness.check_device(Jax, peaks, 1) is not None
    with pytest.raises(harness.RunError, match="4 chips"):
        harness.check_device(Jax, peaks, 4)


RECORDER = '''"""The benchmark's dense family, each call also written to calls.log."""
import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location("real_dense", {real!r})
_real = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_real)
LOG = pathlib.Path(__file__).with_name("calls.log")


def _recorded(name):
    fn = getattr(_real, name)

    def call(*args, **kwargs):
        with open(LOG, "a") as f:
            f.write(name + "\\n")
        return fn(*args, **kwargs)
    return call


for _name in _real.__all__:
    globals()[_name] = _recorded(_name)
'''
FAMILY_NAMES = {"make_params", "readings", "token_flops",
                "espim_step_bytes", "espim_step_ops"}


def test_the_family_module_the_configuration_names_is_the_one_called(
        tmp_path):
    fams = tmp_path / "families"
    fams.mkdir()
    (fams / "dense.py").write_text(RECORDER.format(
        real=str(tinybench.BENCH / "families" / "dense.py")))
    root = tinybench.make(tmp_path / "tiny", limits=LIMITS, families=fams)
    out = harness.run(harness.Cell(root, "tiny.backlog"), 3, 1.5, False,
                      time.perf_counter(), require_tpu=False, impl="ref",
                      cache_dir=root / "bench" / ".cache")
    assert out["correct"], out["checks"]
    assert set((fams / "calls.log").read_text().split()) == FAMILY_NAMES


def test_a_family_without_a_module_is_refused(tmp_path):
    root = tinybench.make(tmp_path / "tiny", limits=LIMITS)
    path = root / "bench" / "configs" / "tiny.json"
    conf = json.loads(path.read_text())
    conf["model"]["family"] = "moe"
    path.write_text(json.dumps(conf))
    with pytest.raises(harness.RunError, match="bench/families/moe.py"):
        harness.Cell(root, "tiny.backlog")


def _tiny_config():
    sys.path.insert(0, str(tinybench.REPO / "src"))
    from repro.configs.registry import get_config
    return get_config("granite-3-2b").replace(**tinybench.OVERRIDES)


@pytest.mark.parametrize("key", ["capacity_factor", "n_experts",
                                 "experts_per_token"])
def test_check_model_compares_every_config_field_the_file_states(key):
    cfg = _tiny_config()
    m = dict(tinybench.MODEL)
    harness._check_model(cfg, m)
    m[key] = getattr(cfg, key)
    harness._check_model(cfg, m)
    m[key] = getattr(cfg, key) * 2 + 1
    with pytest.raises(harness.RunError, match=key):
        harness._check_model(cfg, m)


def test_check_model_still_requires_its_keys():
    m = dict(tinybench.MODEL)
    del m["rope_theta"]
    with pytest.raises(harness.RunError, match="missing.*rope_theta"):
        harness._check_model(_tiny_config(), m)


def test_window_counters_are_counter_rises_and_gauges_at_the_end():
    sys.path.insert(0, str(tinybench.REPO / "src"))
    from repro.telemetry import metrics as tm
    reg = tm.Registry({"model": "tiny", "impl": "ref"})
    tokens = reg.counter("serve_tokens_total")
    depth = reg.gauge("serve_queue_depth")
    blocks = reg.gauge("serve_arena_blocks", state="free")
    step = reg.histogram("serve_step_seconds", phase="decode")
    tokens.inc(5)
    depth.set(3)
    blocks.set(40)
    start = harness.registry_values(reg)
    tokens.inc(7)
    late = reg.counter("serve_retries_total")
    late.inc(2)
    depth.set(1)
    step.observe(0.1)
    got = harness.window_counters(start, harness.registry_values(reg))
    assert got == {"serve_tokens_total": 7, "serve_retries_total": 2,
                   "serve_queue_depth": 1.0,
                   'serve_arena_blocks{state="free"}': 40.0}


def test_readers_get_the_program_spans_and_counters_of_the_profiled_half(
        tmp_path, monkeypatch):
    seen = []
    load = harness._load_reader

    def spying(path):
        mod = load(path)
        if path.parent.name == "metrics":
            read = mod.read

            def spy(ctx):
                seen.append(ctx)
                return read(ctx)
            mod.read = spy
        return mod
    monkeypatch.setattr(harness, "_load_reader", spying)
    out = _run(tmp_path, "tiny.backlog", 5, trace=True)
    assert out["correct"], out["checks"]
    ctx = seen[0]
    w = ctx["window_ns"]
    names = {n for n, _, _ in ctx["program"]}
    assert {"engine.step", "decode.step", "cache.gather"} <= names
    assert all(w[0] <= t and t + d <= w[1] for _, t, d in ctx["program"])
    assert not any(n.startswith("bench.") for n in names)
    # one engine.step span per tick of the profiled half, each counted
    ticks = [e for e in ctx["program"] if e[0] == "engine.step"]
    c = ctx["counters"]
    assert c["serve_tokens_total"] > 0
    assert c["serve_tokens_total"] <= len(ticks) * 2
    assert "serve_queue_depth" in c
    # the fenced second half still gives the tracer's own spans
    assert ctx["spans"]
