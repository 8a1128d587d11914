"""The reduction from a profiler trace to metrics, on a small trace
recorded on a TPU v5e (``data/decode_trace.json.gz``: a few decode steps
of the decode-backlog cell in the extracted form that
``trace_reduce.extract`` returns, cut from the profile a ``--trace 1`` run
leaves under ``bench/.cache/trace/<cell>``, which
``trace_reduce.extract(trace_reduce.find_xplane(dir))`` reads), checked
against plain re-computations; and ``extract`` itself on a profile
recorded on the CPU around a few annotations, the program's apart from
the benchmark's.
"""
from __future__ import annotations

import gzip
import importlib.util
import json
import re

import pytest

import tinybench
from benchlib import trace_reduce as R

DATA = tinybench.BENCH / "tests" / "data" / "decode_trace.json.gz"


@pytest.fixture(scope="module")
def tr():
    with gzip.open(DATA, "rt") as f:
        return json.load(f)


def _reader(name):
    path = tinybench.BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ops(tr):
    (lines,) = tr["device"].values()
    return lines[R.OPS]


def _sweep_busy(ops, w):
    """Covered length by a sweep over +1/-1 endpoint events."""
    ev = []
    for _, t, d in ops:
        a, b = max(t, w[0]), min(t + d, w[1])
        if b > a:
            ev += [(a, 1), (b, -1)]
    ev.sort(key=lambda e: (e[0], -e[1]))
    depth, last, tot = 0, None, 0.0
    for x, s in ev:
        if depth > 0:
            tot += x - last
        depth += s
        last = x
    return tot


def test_the_recording_holds_one_chip_and_the_window(tr):
    assert list(tr["device"]) == ["/device:TPU:0"]
    w = R.window(tr)
    assert w[1] > w[0]
    names = {h[0] for h in tr["host"]}
    assert {"bench.window", "bench.step"} <= names


def test_busy_time_is_the_union_of_op_intervals(tr):
    w = R.window(tr)
    want = _sweep_busy(_ops(tr), w)
    assert R.busy_ns(tr, w) == pytest.approx(want, rel=1e-12)
    assert 0 < want < w[1] - w[0]


def test_device_idle_reader(tr):
    w = R.window(tr)
    got = _reader("device_idle").read({"trace": tr, "window_ns": w})
    assert got == pytest.approx(
        100 * (1 - _sweep_busy(_ops(tr), w) / (w[1] - w[0])), rel=1e-12)


def test_decode_step_reader_averages_whole_program_runs(tr):
    w = R.window(tr)
    rd = _reader("decode_step_ms")
    (lines,) = tr["device"].values()
    runs = [e for e in lines[R.MODULES] if re.search(rd.PROGRAM, e[0])
            and e[1] >= w[0] and e[1] + e[2] <= w[1]]
    assert runs
    got = rd.read({"trace": tr, "window_ns": w})
    assert got == pytest.approx(sum(e[2] for e in runs) / len(runs) / 1e6)


def test_espim_roofline_counts_kernels_inside_decode_runs(tr):
    w = R.window(tr)
    rd = _reader("espim_roofline")
    (lines,) = tr["device"].values()
    runs = [e for e in lines[R.MODULES] if re.search(rd.DECODE, e[0])
            and e[1] >= w[0] and e[1] + e[2] <= w[1]]
    kern = sum(d for n, t, d in lines[R.OPS] if re.search(rd.KERNEL, n)
               and any(r[1] <= t and t + d <= r[1] + r[2] for r in runs))
    assert kern > 0
    peak = {"hbm_bytes_s": 819e9, "bf16_flops": 197e12}
    step_bytes, step_ops = 1_000_000_000, 10_000_000
    got = rd.read({"trace": tr, "window_ns": w, "peak": peak,
                   "espim_step_bytes": step_bytes,
                   "espim_step_ops": step_ops})
    want = 100 * len(runs) * step_bytes / 819e9 / (kern / 1e9)
    assert got == pytest.approx(want, rel=1e-9)


def test_top_ops_sum_each_op_in_the_window(tr):
    w = R.window(tr)
    top = R.top_ops(tr, w, n=10)
    assert len(top) <= 10
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    name, secs = top[0]
    want = sum(min(t + d, w[1]) - max(t, w[0]) for n, t, d in _ops(tr)
               if n == name and t + d > w[0] and t < w[1])
    assert secs == pytest.approx(want / 1e9, rel=1e-12)


def test_idle_gaps_add_up_to_the_idle_time(tr):
    w = R.window(tr)
    gaps = R.idle_gaps(tr, w, n=100)
    idle = (w[1] - w[0]) - _sweep_busy(_ops(tr), w)
    assert sum(s for _, s in gaps) == pytest.approx(idle / 1e9, rel=1e-9)
    for name, _ in gaps:
        assert re.fullmatch(r"(bench\.\w+|none) \(\d+ gaps\)", name)


def test_union_merges_overlaps_and_touching_intervals():
    assert R.union([(5, 6), (1, 3), (2, 4), (4, 4.5)]) == [[1, 4.5], [5, 6]]


PROGRAM_SPANS = ("engine.step", "decode.step", "cache.gather")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A profile recorded here on the CPU, as ``extract`` reads it: two
    benchmark steps, each around the program's nested spans and a jitted
    call, inside the benchmark's window."""
    import jax
    import jax.numpy as jnp
    logdir = tmp_path_factory.mktemp("profile")
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(logdir))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench.step"), \
                        jax.profiler.TraceAnnotation("engine.step"), \
                        jax.profiler.TraceAnnotation("decode.step"):
                    with jax.profiler.TraceAnnotation("cache.gather"):
                        f(x).block_until_ready()
                    float(f(x))
    finally:
        jax.profiler.stop_trace()
    return R.extract(R.find_xplane(str(logdir)))


def test_extract_keeps_the_program_spans_apart(recorded):
    prog = recorded["program"]
    assert sorted(n for n, _, _ in prog) == sorted(PROGRAM_SPANS * 2)
    assert sorted(n for n, _, _ in recorded["host"]) == [
        "bench.step", "bench.step", "bench.window"]
    # no accelerator here: no device plane
    assert recorded["device"] == {}
    w = R.window(recorded)
    assert R.events_in(prog, w) == prog


def test_program_spans_take_the_form_of_the_recorded_chip_trace(recorded):
    with gzip.open(DATA.with_name("decode_trace_program.json.gz"),
                   "rt") as f:
        chip = json.load(f)["program"]
    for got in (recorded["program"], chip):
        assert got and all(
            len(e) == 3 and isinstance(e[0], str)
            and all(isinstance(v, float) for v in e[1:]) and e[2] > 0
            for e in got)
    # nested as opened: each cache.gather inside a decode.step inside an
    # engine.step inside a bench.step
    prog = recorded["program"]

    def inside(outer, inner):
        return outer[1] <= inner[1] and inner[1] + inner[2] <= \
            outer[1] + outer[2]
    steps = [e for e in recorded["host"] if e[0] == "bench.step"]
    for g in (e for e in prog if e[0] == "cache.gather"):
        (d,) = [e for e in prog if e[0] == "decode.step" and inside(e, g)]
        (s,) = [e for e in prog if e[0] == "engine.step" and inside(e, d)]
        assert any(inside(b, s) for b in steps)


@pytest.mark.parametrize("name, kept", [
    ("engine.step", True), ("cache.scatter", True),
    ("decode.launch_degraded", True), ("dot_general.1", False),
    ("end: dot_general.1", False), ("$numpy asarray", False),
    ("PjitFunction(fn)", False), ("jit_fn", False)])
def test_which_host_events_count_as_program_spans(name, kept):
    assert bool(R.PROGRAM_SPAN.fullmatch(name)) is kept
