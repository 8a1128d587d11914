"""The reduction from a profiler trace to metrics, on a small trace
recorded on a TPU v5e (``data/decode_trace.json.gz``: a few decode steps
of the decode-backlog cell in the extracted form that
``trace_reduce.extract`` returns, cut from the profile a ``--trace 1`` run
leaves under ``bench/.cache/trace/<cell>``, which
``trace_reduce.extract(trace_reduce.find_xplane(dir))`` reads), checked
against plain re-computations.
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
