"""Whole runs of the harness on the CPU at a tiny size, with the look for
a chip skipped: a sound run is correct, the control (the reference with
int4 weights in the program's place, one step below the int8 the
configuration states) and a token altered where the engine emits it are
not, and without a TPU the entry point exits non-zero with no result line.

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
