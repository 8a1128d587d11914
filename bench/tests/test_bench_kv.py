"""The paged KV cache's readers, ``kv_device_ms`` and ``kv_rebuild_share``:
on hand-built traces, on the older recorded trace (whose cache programs
carry the names from before the ``jit_kv_*`` ones, so both read
nothing), and on a trace recorded on a TPU v5e with the program's spans
in it (``data/decode_trace_program.json.gz``: four ticks of the
decode-backlog cell, two of them rebuilding the view, in the form
``trace_reduce.extract`` returns, with the program's ``TraceAnnotation``s
beside it under ``program``)."""
from __future__ import annotations

import gzip
import importlib.util
import json

import pytest

import tinybench
from benchlib import trace_reduce as R

DATA = tinybench.BENCH / "tests" / "data"
MS = 1e6


def _reader(name):
    path = tinybench.BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load(name):
    with gzip.open(DATA / name, "rt") as f:
        return json.load(f)


def _trace(modules):
    """One chip whose XLA Modules line holds ``modules`` ([name, start
    ms, duration ms]) and a window of [10, 100] ms."""
    mods = [[n, t * MS, d * MS] for n, t, d in modules]
    return {"trace": {"device": {"/device:TPU:0": {R.OPS: [],
                                                   R.MODULES: mods}},
                      "host": []},
            "window_ns": (10 * MS, 100 * MS)}


TICKS = [  # three decode ticks in the window, the second one rebuilding
    ["jit_kv_scatter_decode(1)", 5, 2],        # straddles the window start
    ["jit_fn(7)", 12, 10],
    ["jit_kv_scatter_decode(1)", 23, 2],
    ["jit_kv_gather_view(2)", 26, 4],
    ["jit_fn(7)", 31, 10],
    ["jit_kv_scatter_decode(1)", 42, 2],
    ["jit__lambda(3)", 45, 6],                 # a prefill chunk
    ["jit_kv_scatter_chunk(4)", 52, 3],
    ["jit_fn(7)", 56, 10],
    ["jit_kv_scatter_decode(1)", 99, 2],       # straddles the window end
]


def test_kv_device_ms_adds_the_cache_programs_per_decode_tick():
    got = _reader("kv_device_ms").read(_trace(TICKS))
    assert got == pytest.approx((2 + 4 + 2 + 3) / 3)


def test_kv_rebuild_share_counts_gathers_per_decode_tick():
    assert _reader("kv_rebuild_share").read(_trace(TICKS)) == \
        pytest.approx(100 / 3)
    no_rebuild = [m for m in TICKS if not m[0].startswith("jit_kv_gather")]
    assert _reader("kv_rebuild_share").read(_trace(no_rebuild)) == 0.0


@pytest.mark.parametrize("name", ["kv_device_ms", "kv_rebuild_share"])
def test_the_kv_readers_read_nothing_without_their_programs(name):
    rd = _reader(name)
    assert rd.read({}) is None
    assert rd.read(_trace([m for m in TICKS if m[0].startswith("jit_fn")])) \
        is None
    # a program whose cache programs carry their older names
    assert rd.read({"trace": _load("decode_trace.json.gz"),
                    "window_ns": R.window(_load("decode_trace.json.gz"))}) \
        is None


def test_kv_rebuild_share_needs_only_some_cache_program():
    """A program that keeps any ``jit_kv_*`` program but no longer runs
    the view gather reads 0 rebuilds, not nothing."""
    only_chunks = [m for m in TICKS
                   if m[0].startswith(("jit_fn", "jit_kv_scatter_chunk"))]
    assert _reader("kv_rebuild_share").read(_trace(only_chunks)) == 0.0


@pytest.fixture(scope="module")
def rec():
    return _load("decode_trace_program.json.gz")


def _spans(rec, name, w):
    return R.events_in([s for s in rec["program"] if s[0] == name], w)


def _starts_in(events, span):
    return [e for e in events if span[1] <= e[1] < span[1] + span[2]]


def test_each_rebuild_runs_the_gather_program_once(rec):
    """The premise of kv_rebuild_share: each decode tick runs the decode
    program once and the gather program at most once, and the ticks that
    run the gather are the slow kind of inter-token gap (about 125 ms
    against 88 ms)."""
    w = R.window(rec)
    rd = _reader("kv_rebuild_share")
    assert list(rec["device"]) == ["/device:TPU:0"]
    ticks = _spans(rec, "decode.step", w)
    steps = R.module_events(rec, w, rd.DECODE)
    gathers = R.module_events(rec, w, rd.GATHER)
    assert len(ticks) >= 3 and len(steps) == len(ticks)
    assert 0 < len(gathers) < len(ticks)
    rebuilt = []
    for t in ticks:
        assert len(_starts_in(steps, t)) == 1
        rebuilt.append(len(_starts_in(gathers, t)))
    assert max(rebuilt) == 1 and sum(rebuilt) == len(gathers)
    for t, n in zip(ticks, rebuilt):
        assert (t[2] > 100 * MS) == bool(n)
    got = rd.read({"trace": rec, "window_ns": w})
    assert got == pytest.approx(100 * len(gathers) / len(ticks))


def test_kv_device_ms_on_the_recording(rec):
    w = R.window(rec)
    (lines,) = rec["device"].values()
    inside = [e for e in lines[R.MODULES]
              if e[1] >= w[0] and e[1] + e[2] <= w[1]]
    kv = sum(d for n, _, d in inside if n.startswith("jit_kv_"))
    steps = [e for e in inside if e[0].startswith("jit_fn(")]
    assert kv > 0 and steps
    got = _reader("kv_device_ms").read({"trace": rec, "window_ns": w})
    assert got == pytest.approx(kv / len(steps) / MS)
