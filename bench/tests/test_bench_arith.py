"""The benchmark's arithmetic on the CPU: traffic, percentiles, window
accounting, the pack-cache key and the operation count."""
from __future__ import annotations

import json
import time

import numpy as np
import pytest

import tinybench  # noqa: F401  (puts bench/ on the path)
from benchlib import flops, packcache, stats
from benchlib.traffic import Traffic, exp_gaps, grid

BENCH = tinybench.BENCH


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


# an open loop at the lengths of a long-prompt mix, arriving 4 a second
_OPEN = {"loop": "open", "block": 8,
         "prompt": {"dist": "lognormal", "median": 768, "sigma": 0.5,
                    "min": 128, "max": 1536},
         "output": {"dist": "uniform", "min": 16, "max": 64}}
_OPEN_CELL = {"slots": 8, "max_len": 1664, "prefill_chunk": 256,
              "rate_rps": 4.0}


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 98765432109])
def test_every_seed_offers_the_same_work(seed):
    mix, cell = _OPEN, _OPEN_CELL
    a = Traffic(mix, cell, 49155, seed)
    b = Traffic(mix, cell, 49155, seed + 1)
    n = mix["block"]
    ra = [a.next() for _ in range(2 * n)]
    rb = [b.next() for _ in range(2 * n)]
    for blk in range(2):
        sl = slice(blk * n, (blk + 1) * n)
        assert sorted(len(r["prompt"]) for r in ra[sl]) == \
            sorted(len(r["prompt"]) for r in rb[sl])
        assert sorted(r["max_new"] for r in ra[sl]) == \
            sorted(r["max_new"] for r in rb[sl])
    # the same lengths and arrivals in the same order; other token ids
    assert [(len(r["prompt"]), r["max_new"], r["due_s"]) for r in ra] == \
        [(len(r["prompt"]), r["max_new"], r["due_s"]) for r in rb]
    assert [r["prompt"] for r in ra] != [r["prompt"] for r in rb]
    assert all(0 <= t < 49155 for r in ra for t in r["prompt"])
    again = Traffic(mix, cell, 49155, seed)
    assert [again.next()["prompt"] for _ in range(3)] == \
        [r["prompt"] for r in ra[:3]]


def test_open_loop_due_times_follow_the_rate():
    mix, cell = _OPEN, dict(_OPEN_CELL, rate_rps=5.0)
    tr = Traffic(mix, cell, 100, 3)
    due = [tr.next()["due_s"] for _ in range(mix["block"] * 4)]
    assert all(b > a for a, b in zip(due, due[1:]))
    # the stratified exponential gaps average 1/rate over each block
    assert due[-1] / len(due) == pytest.approx(
        exp_gaps(0.2, mix["block"]).mean(), rel=1e-12)
    assert exp_gaps(0.2, 4096).mean() == pytest.approx(0.2, rel=2e-3)


@pytest.mark.parametrize("mixname", ["decode_backlog", "open"])
def test_length_grids_stay_in_bounds(mixname):
    mix = _OPEN if mixname == "open" else _mix(mixname)
    for key in ("prompt", "output"):
        g = grid(mix[key], mix["block"])
        assert g.min() >= mix[key]["min"] and g.max() <= mix[key]["max"]
        if mix[key]["dist"] == "lognormal":
            assert np.median(g) == pytest.approx(mix[key]["median"], rel=0.1)


class _Sched:
    def __init__(self):
        self.pending = []

    @property
    def queue_depth(self):
        return len(self.pending)

    @property
    def has_pending(self):
        return bool(self.pending)


class _Slot:
    def __init__(self, req):
        self.req, self.phase, self.pos = req, "decode", len(req.prompt)


class _FakeEngine:
    """Admits into ``slots`` slots and emits one token per slot per step."""

    def __init__(self, slots):
        self.scheduler = _Sched()
        self.slots = [None] * slots

    def submit(self, req):
        self.scheduler.pending.append(req)
        return True

    def step(self):
        for i, s in enumerate(self.slots):
            if s is None and self.scheduler.pending:
                self.slots[i] = _Slot(self.scheduler.pending.pop(0))
        for i, s in enumerate(self.slots):
            if s is not None:
                s.req.output.append(1)
                if len(s.req.output) >= s.req.max_new_tokens:
                    s.req.done = True
                    self.slots[i] = None


class _Req:
    def __init__(self, rid, prompt, max_new_tokens):
        self.rid, self.prompt = rid, prompt
        self.max_new_tokens, self.output, self.done = max_new_tokens, [], False


def _client(slots=4, loop="decode_backlog", cell=None):
    import jax

    from benchlib.harness import Client
    mix = _mix(loop)
    cell = cell or {"slots": slots, "max_len": 1600, "prefill_chunk": 256}
    eng = _FakeEngine(slots)
    tr = Traffic(mix, cell, 1000, 1)
    return Client(jax, eng, _Req, tr, stats.TokenLog(), lambda c: 1.0), eng


def test_backlog_keeps_its_queue_depth():
    drv, eng = _client(slots=4)
    depths = []
    for _ in range(1100):
        while eng.scheduler.queue_depth < drv.traffic.backlog:
            drv.submit(drv.traffic.next(), time.perf_counter())
        depths.append(eng.scheduler.queue_depth)
        drv.step()
    assert drv.traffic.backlog == 4
    assert min(depths) == 4
    assert all(s is not None for s in eng.slots)
    assert any(r.done for r in drv.reqs.values())


def test_generator_lateness_is_submit_minus_due():
    drv, _ = _client()
    due = time.perf_counter() - 0.25
    drv.submit(drv.traffic.next(), due)
    assert 0.25 <= drv.lateness[-1] < 0.5
    assert drv.log.due[0] == due


def test_client_stamps_every_token_once():
    drv, eng = _client(slots=2)
    for _ in range(3):
        drv.submit(drv.traffic.next(), time.perf_counter())
    for _ in range(50):
        drv.step()
    for rid, req in drv.reqs.items():
        assert len(drv.log.stamps[rid]) == len(req.output)


def test_the_sample_takes_requests_still_in_service_and_the_longest():
    from benchlib.harness import _sample
    drv, eng = _client(slots=2)
    for _ in range(4):
        drv.submit(drv.traffic.next(), time.perf_counter())
    t0 = time.perf_counter()
    for _ in range(40):
        drv.step()
    t1 = time.perf_counter()
    served = [r for r in drv.reqs if drv.log.stamps[r]]
    live = [r for r in served if not drv.reqs[r].done]
    assert live                               # some still in service
    pick = _sample(drv, {}, t0, t1, 99, 5)
    assert sorted(pick) == sorted(served)
    longest = max(served, key=lambda r: len(drv.reqs[r].output))
    assert _sample(drv, {}, t0, t1, 1, 5) == [longest]
    # a request that ended other than completed is never drawn
    assert longest not in _sample(drv, {longest: "cancelled"}, t0, t1, 99, 5)
    # nor one with no token inside the window
    assert _sample(drv, {}, t1, t1 + 1.0, 99, 5) == []


def test_percentile_is_an_exact_nearest_rank_sample():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile([3.5], 95) == 3.5
    assert stats.percentile([5, 1, 4, 2, 3], 95) == 5
    assert stats.percentile([5, 1, 4, 2, 3], 40) == 2
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_window_accounting_of_tokens_gaps_and_ttft():
    log = stats.TokenLog()
    log.offer(1, 0.5)
    log.stamp(1, 1, 1.0)        # first token, before the window
    log.stamp(1, 1, 2.0)
    log.stamp(1, 1, 3.0)
    log.offer(2, 2.5)
    log.stamp(2, 1, 3.0)
    log.stamp(2, 1, 5.5)        # after the window
    assert log.tokens(1.5, 4.0) == 3
    assert sorted(log.gaps(1.5, 4.0)) == [1.0]
    assert sorted(log.gaps(0.0, 10.0)) == [1.0, 1.0, 2.5]
    assert log.ttfts([1, 2]) == [0.5, 0.5]


def test_pack_cache_key_follows_every_source_file(tmp_path):
    src = tmp_path / "src"
    (src / "pkg").mkdir(parents=True)
    (src / "pkg" / "a.py").write_text("x = 1\n")
    (src / "b.py").write_text("y = 2\n")
    d0 = packcache.source_digest(src)
    k0 = packcache.cache_key(b"{}", 1, d0)
    (src / "pkg" / "a.py").write_text("x = 3\n")
    d1 = packcache.source_digest(src)
    assert d1 != d0
    assert packcache.cache_key(b"{}", 1, d1) != k0
    assert packcache.cache_key(b"{}", 2, d0) != k0
    assert packcache.cache_key(b"{ }", 1, d0) != k0
    (src / "notes.txt").write_text("not a source")
    assert packcache.source_digest(src) == d1


def test_pack_cache_round_trip(tmp_path):
    import jax.numpy as jnp
    calls = []

    def build():
        calls.append(1)
        w = jnp.ones((2, 2), jnp.bfloat16)
        return {"groups": {"g": {"q": jnp.arange(6, dtype=jnp.int8),
                                 "valid": np.ones(3, bool),
                                 "bucket_rows": (1, 2)}},
                "pruned": {"w": w}, "w_pruned": w}
    path = tmp_path / "p.pkl"
    a, info = packcache.load_or_build(path, build)
    b, info2 = packcache.load_or_build(path, build)
    assert not info["hit"] and info2["hit"] and len(calls) == 1
    assert isinstance(b["groups"]["g"]["q"], type(a["groups"]["g"]["q"]))
    assert isinstance(b["groups"]["g"]["valid"], np.ndarray)
    assert b["pruned"]["w"].dtype == jnp.bfloat16
    assert b["groups"]["g"]["bucket_rows"] == (1, 2)
    assert b["w_pruned"] is b["pruned"]["w"]      # one array, not two
    np.testing.assert_array_equal(b["groups"]["g"]["q"], a["groups"]["g"]["q"])


def test_mfu_flop_count_of_a_configuration():
    m = json.loads((BENCH / "configs" / "granite-3-2b.json").read_text())
    model = m["model"]
    d, f, h, kv, hd, L, v = 2048, 8192, 32, 8, 64, 40, 49155
    proj = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    want = L * (2 * 0.1 * proj + 4 * h * hd * 100) + 2 * d * v
    got = flops.token_flops(model, 0.9, "all", 100)
    assert got == pytest.approx(want, rel=1e-12)
    # MLP-only packs leave attention dense
    mlp = flops.token_flops(model, 0.9, "mlp", 0)
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    assert mlp == pytest.approx(L * 2 * (attn + 0.1 * 3 * d * f) + 2 * d * v)


def test_espim_step_bytes_counts_planes_x_and_out():
    sparse = {"gated": True, "groups": {
        "qkv": {"bucket_rows": (8,), "halves": 1, "n_cols": 16, "buckets": [
            {"q": np.zeros((2, 1, 4, 8), np.int8),
             "cols": np.zeros((2, 1, 4, 8), np.int32),
             "srow": np.zeros((2, 8), np.float32)}]},
        "gateup": {"bucket_rows": (8,), "halves": 2, "n_cols": 16,
                   "buckets": [{"values": np.zeros((2, 1, 4, 16), np.float32),
                                "cols": np.zeros((2, 1, 4, 16), np.int32)}]}}}
    qkv = 64 + 256 + 64 + 2 * (16 + 8) * 3 * 4
    gu = 512 + 512 + 2 * (16 + 8) * 3 * 4       # GLU: one output half
    assert flops.espim_step_bytes(sparse, 3) == qkv + gu
    assert flops.espim_step_ops(sparse, 3) == 2 * (64 + 128) * 3


def test_no_path_in_the_harness_names_a_cell():
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    names = [w["name"] for w in spec["workloads"]]
    names += [c["name"] for c in spec["configs"]]
    names += sorted({w["traffic"] for w in spec["workloads"]})
    for p in list((BENCH / "benchlib").glob("*.py")) + [BENCH / "run.py"] \
            + list((BENCH / "metrics").glob("*.py")):
        text = p.read_text()
        for n in names:
            assert n not in text, (p.name, n)


def test_every_listed_piece_has_its_file():
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        assert (BENCH.parent / c["file"]).is_file()
    for w in spec["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (BENCH / "cells" / f"{w['name']}.json").is_file()
    for m in spec["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_peaks_table_is_keyed_by_device_kind():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_s"] == 819e9
    assert "source" in v5e

