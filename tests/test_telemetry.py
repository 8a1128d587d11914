"""Telemetry: span tracer invariants, disabled-mode zero-cost, metrics
registry / histogram quantiles, Prometheus exposition, engine coverage."""
import json
import threading

import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.kernels import ops
from repro.models import factory
from repro.serve.engine import Request, ServeEngine
from repro.serve.scheduler import (RequestMetrics, Scheduler,
                                   latency_summary, percentiles)
from repro.telemetry import metrics as tm
from repro.telemetry import trace as tt

KEY = jax.random.PRNGKey(0)


# ------------------------------------------------------------------ tracer
def test_nested_span_invariants():
    tr = tt.Tracer(enabled=True)
    with tr.span("outer", cat="a") as outer:
        with tr.span("inner", cat="b") as inner:
            pass
        with tr.span("inner2", cat="b") as inner2:
            pass
    spans = tr.spans()
    assert [s.name for s in spans] == ["inner", "inner2", "outer"]
    assert inner.parent_id == outer.sid
    assert inner2.parent_id == outer.sid
    assert outer.parent_id == 0 and outer.depth == 0
    assert inner.depth == 1
    # children are contained in the parent and ordered, durations >= 0
    assert outer.t0_ns <= inner.t0_ns <= inner.t1_ns <= outer.t1_ns
    assert inner.t1_ns <= inner2.t0_ns
    assert all(s.dur_ns >= 0 for s in spans)


def test_span_out_of_order_close_raises():
    tr = tt.Tracer(enabled=True)
    a = tr.span("a")
    b = tr.span("b")
    a.__enter__()
    b.__enter__()
    with pytest.raises(RuntimeError, match="out of order"):
        a.__exit__(None, None, None)
    # recover: close in order
    b.__exit__(None, None, None)
    a.__exit__(None, None, None)


def test_span_set_and_instant_args():
    tr = tt.Tracer(enabled=True)
    with tr.span("s", cat="c", args={"k": 1}) as sp:
        sp.set("extra", "v")
    tr.instant("mark", cat="fault", args={"slot": 3})
    doc = tr.chrome_trace()
    by_name = {e["name"]: e for e in doc["traceEvents"]}
    assert by_name["s"]["args"] == {"k": 1, "extra": "v"}
    assert by_name["mark"]["ph"] == "i"
    assert by_name["mark"]["args"] == {"slot": 3}


def test_disabled_tracer_allocates_nothing(monkeypatch):
    """The hot-path contract: a disabled tracer constructs zero Span
    objects (counting shim) and hands out one shared null singleton."""
    calls = {"n": 0}
    real_span = tt.Span

    class CountingSpan(real_span):
        def __init__(self, *a, **kw):
            calls["n"] += 1
            super().__init__(*a, **kw)

    class CountingAnnotated(tt._AnnotatedSpan):
        def __init__(self, *a, **kw):
            calls["n"] += 1
            super().__init__(*a, **kw)

    monkeypatch.setattr(tt, "Span", CountingSpan)
    monkeypatch.setattr(tt, "_AnnotatedSpan", CountingAnnotated)
    for tr in (tt.Tracer(enabled=False),
               tt.Tracer(enabled=False, profiler=True)):
        got = [tr.span("hot", cat="x") for _ in range(100)]
        assert calls["n"] == 0
        assert all(g is got[0] for g in got)      # the shared singleton
        assert got[0] is tr.span("other")         # name-independent
        with got[0] as s:
            assert s.set("k", "v") is s           # API parity, still no-op
        tr.instant("nope")
        assert tr.spans() == [] and tr.instants == []
    # enabled tracer DOES construct through the (patched) class
    tr_on = tt.Tracer(enabled=True)
    with tr_on.span("real"):
        pass
    assert calls["n"] == 1


def test_disabled_fence_does_not_sync(monkeypatch):
    """fence() must not touch jax when tracing is off — instrumentation
    cannot change the untraced pipeline's host/device overlap."""
    hit = {"n": 0}
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: hit.__setitem__("n", hit["n"] + 1))
    x = object()
    assert tt.NULL_TRACER.fence(x) is x
    assert hit["n"] == 0
    tr = tt.Tracer(enabled=True)
    tr.fence(x)
    assert hit["n"] == 1


def _host_annotations(logdir):
    """{line: [(name, start_ns, end_ns)]} of the ``/host:CPU`` plane of
    the profile under ``logdir``."""
    import glob
    (path,) = glob.glob(str(logdir / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    (plane,) = [p for p in pd.planes if p.name == "/host:CPU"]
    return {ln.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in ln.events] for ln in plane.lines}


def test_profiler_mode_puts_engine_spans_on_the_profiler_clock(tmp_path):
    """In profiler mode the engine's spans are TraceAnnotations of their
    exact names, nested as the engine opens them, on the host plane of the
    profiler's trace; the tracer itself records nothing."""
    cfg = get_config("granite-3-2b", reduced=True)
    params = factory.init_params(cfg, KEY)
    tr = tt.Tracer(enabled=True, profiler=True)
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, tracer=tr)
    for rid in range(2):
        eng.submit(Request(rid=rid, prompt=[1 + rid, 2, 3],
                           max_new_tokens=4))
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    assert tr.spans() == [] and tr.instants == []
    assert eng.stats.decode_steps > 0

    def inside(outer, inner):
        return outer[1] <= inner[1] and inner[2] <= outer[2]

    chains = 0
    for evs in _host_annotations(tmp_path).values():
        by = {n: [e for e in evs if e[0] == n]
              for n in ("engine.step", "decode.step", "cache.gather")}
        for g in by["cache.gather"]:
            if any(inside(d, g) and any(inside(s, d)
                                        for s in by["engine.step"])
                   for d in by["decode.step"]):
                chains += 1
    assert chains == eng.stats.decode_steps


def test_profiler_mode_fence_does_not_sync(monkeypatch):
    hit = {"n": 0}
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: hit.__setitem__("n", hit["n"] + 1))
    tr = tt.Tracer(enabled=True, profiler=True)
    x = object()
    with tr.span("decode.launch") as sp:
        assert sp.set("slot", 0) is sp           # no-op, as on a Span
        assert tr.fence(x) is x
    assert hit["n"] == 0


def test_tracer_thread_safety():
    tr = tt.Tracer(enabled=True)

    def work(tid):
        for i in range(50):
            with tr.span(f"t{tid}", cat="w"):
                with tr.span(f"t{tid}.child", cat="w"):
                    pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = tr.spans()
    assert len(spans) == 4 * 50 * 2
    by_sid = {s.sid: s for s in spans}
    assert len(by_sid) == len(spans)              # globally unique ids
    for s in spans:
        if s.parent_id:
            assert by_sid[s.parent_id].tid == s.tid   # links stay on-thread


def test_chrome_trace_schema_and_validation(tmp_path):
    tr = tt.Tracer(enabled=True)
    with tr.span("a", cat="x"):
        pass
    tr.instant("i1")
    path = tmp_path / "trace.json"
    doc = tr.write_chrome_trace(str(path), provenance={"impl": "ref"})
    tt.validate_chrome_trace(doc)
    on_disk = json.loads(path.read_text())
    assert on_disk["otherData"]["provenance"] == {"impl": "ref"}
    xs = [e for e in on_disk["traceEvents"] if e["ph"] == "X"]
    assert xs and all(e["dur"] >= 0 for e in xs)
    with pytest.raises(ValueError):
        tt.validate_chrome_trace({"traceEvents": []})
    with pytest.raises(ValueError):
        tt.validate_chrome_trace(
            {"traceEvents": [{"name": "n", "ph": "X", "ts": 0.0}]})


def test_jsonl_export_header_first(tmp_path):
    tr = tt.Tracer(enabled=True)
    with tr.span("a", cat="x"):
        pass
    path = tmp_path / "trace.jsonl"
    n = tr.write_jsonl(str(path), provenance={"impl": "ref"})
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    assert lines[0]["type"] == "header"
    assert lines[0]["provenance"] == {"impl": "ref"}
    assert n == len(lines) - 1 == 1
    assert lines[1]["type"] == "span" and lines[1]["name"] == "a"


def test_phase_breakdown_schema():
    tr = tt.Tracer(enabled=True)
    for _ in range(3):
        with tr.span("step", cat="engine"):
            with tr.span("p", cat="prefill"):
                pass
            with tr.span("d", cat="decode"):
                pass
    bd = tt.phase_breakdown(tr, parent="step")
    assert tuple(k for k in tt.BREAKDOWN_SCHEMA_KEYS if k in bd) \
        == tt.BREAKDOWN_SCHEMA_KEYS
    assert set(bd["phases"]) == {"prefill", "decode"}
    assert bd["phases"]["prefill"]["count"] == 3
    assert 0 < bd["coverage"] <= 1.0 + 1e-6
    cov = tt.span_coverage(tr.spans(), "step")
    assert cov["parents"] == 3 and not cov["overlap_errors"]


# ----------------------------------------------------------------- metrics
def test_histogram_bucket_edges():
    h = tm.Histogram("h", {}, edges=(1.0, 10.0, 100.0))
    # exactly-at-edge lands in the bucket whose upper bound it is
    # (bisect_left: counts[i] holds x <= edges[i])
    for x in (0.5, 1.0, 5.0, 10.0, 100.0, 1e9):
        h.observe(x)
    assert h.counts == [2, 2, 1, 1]               # last = +Inf overflow
    assert h.count == 6
    assert h.min == 0.5 and h.max == 1e9
    # quantiles are clamped to observed data, never a synthetic edge
    assert h.quantile(0.0) == 0.5
    assert h.quantile(1.0) == 1e9
    q50 = h.quantile(0.5)
    assert 1.0 <= q50 <= 10.0
    h.reset()
    assert h.count == 0 and h.quantile(0.5) is None


def test_histogram_rejects_bad_input():
    with pytest.raises(ValueError):
        tm.Histogram("h", {}, edges=(10.0, 1.0))
    h = tm.Histogram("h", {})
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError):
        tm.log_buckets(0.0, 1.0, 10)


def test_histogram_quantile_accuracy():
    """Streaming quantile must land within one bucket (~9% for the
    presets) of the exact percentile on a lognormal sample."""
    rng = np.random.default_rng(0)
    xs = np.exp(rng.normal(0.0, 1.5, size=5000))
    h = tm.Histogram("h", {}, edges=tm.LATENCY_BUCKETS_S)
    for x in xs:
        h.observe(float(x))
    for q in (0.5, 0.95):
        exact = float(np.quantile(xs, q))
        est = h.quantile(q)
        assert abs(est - exact) / exact < 0.10, (q, est, exact)


def test_counter_and_gauge():
    r = tm.Registry()
    c = r.counter("c_total")
    c.inc().inc(3)
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)
    g = r.gauge("g")
    g.set(0.25)
    assert r.snapshot() == {"c_total": 4, "g": 0.25}


def test_registry_labels_and_identity():
    r = tm.Registry({"model": "m"})
    a = r.counter("tok_total", state="ok")
    b = r.counter("tok_total", state="ok")
    assert a is b                                  # create-once
    c = r.counter("tok_total", state="bad")
    assert c is not a
    with pytest.raises(ValueError):
        r.gauge("tok_total")                       # kind conflict
    a.inc(2)
    c.inc()
    snap = r.snapshot()
    assert snap['tok_total{model="m",state="bad"}'] == 1
    assert snap['tok_total{model="m",state="ok"}'] == 2


def test_prometheus_golden():
    r = tm.Registry({"model": "m"})
    r.counter("req_total", help="requests").inc(3)
    r.gauge("occ").set(0.5)
    h = r.histogram("lat_s", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(7.0)
    golden = "\n".join([
        '# TYPE lat_s histogram',
        'lat_s_bucket{le="0.1",model="m"} 1',
        'lat_s_bucket{le="1",model="m"} 2',
        'lat_s_bucket{le="+Inf",model="m"} 3',
        'lat_s_sum{model="m"} 7.55',
        'lat_s_count{model="m"} 3',
        '# TYPE occ gauge',
        'occ{model="m"} 0.5',
        '# HELP req_total requests',
        '# TYPE req_total counter',
        'req_total{model="m"} 3',
    ]) + "\n"
    assert r.to_prometheus() == golden


def test_snapshot_golden_and_deterministic():
    """``Registry.snapshot()`` is the substrate the flight recorder dumps
    and the bench docs embed: its key ORDER and value shapes are pinned
    here so two registries fed the same instruments — in any insertion
    order — serialize identically (diffable dumps, stable baselines)."""
    def build(order):
        r = tm.Registry({"model": "m"})
        ops = {
            "a": lambda: r.counter("req_total", state="ok").inc(2),
            "b": lambda: r.counter("req_total", state="shed").inc(),
            "c": lambda: r.gauge("occ").set(0.5),
            "d": lambda: [r.histogram("lat_s", buckets=(0.1, 1.0))
                          .observe(v) for v in (0.05, 0.5)],
        }
        for k in order:
            ops[k]()
        return r.snapshot()

    snap = build("abcd")
    golden_keys = [
        'lat_s{model="m"}',
        'occ{model="m"}',
        'req_total{model="m",state="ok"}',
        'req_total{model="m",state="shed"}',
    ]
    assert list(snap) == golden_keys         # sorted names, sorted labels
    assert snap['occ{model="m"}'] == 0.5
    assert snap['req_total{model="m",state="ok"}'] == 2
    hist = snap['lat_s{model="m"}']
    assert hist["count"] == 2 and hist["sum"] == pytest.approx(0.55)
    assert {"min", "max", "mean", "p50", "p95"} <= set(hist)
    # insertion order never leaks into the serialization
    for order in ("dcba", "bdac"):
        assert json.dumps(build(order), sort_keys=False) == \
            json.dumps(snap, sort_keys=False)


def test_validate_snapshot_sparse_gate():
    snap = {f"{name}{{x=\"1\"}}": 0 for name in tm.REQUIRED_SERVE_METRICS}
    tm.validate_snapshot(snap)
    dense = {k: v for k, v in snap.items() if not k.startswith("espim_")}
    tm.validate_snapshot(dense, sparse=False)
    with pytest.raises(AssertionError, match="espim_bytes_per_token"):
        tm.validate_snapshot(dense, sparse=True)


# ---------------------------------------------------------------- profile
def test_time_launch_warmup_discard():
    from repro.telemetry import time_launch
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        return np.zeros(2)

    t = time_launch(fn, iters=4, warmup=2, bytes_moved=1 << 20,
                    dense_bytes=1 << 20, dense_us=100.0)
    assert calls["n"] == 6                        # 2 warmup + 4 timed
    assert t.iters == 4 and t.best_us <= t.p50_us <= t.p95_us
    assert t.gbps_best > 0 and t.roofline_frac > 0
    d = t.to_dict()
    for k in ("best_us", "p50_us", "p95_us", "bytes_moved", "gbps_best",
              "roofline_frac"):
        assert k in d
    with pytest.raises(ValueError):
        time_launch(fn, iters=0)


# -------------------------------------------------- scheduler percentiles
def test_latency_summary_streaming_no_sort(monkeypatch):
    """PR 7 bugfix regression: the engine report path must use the
    histograms' O(buckets) quantiles, never re-sort the sample list."""
    sched = Scheduler()
    for i in range(50):
        m = RequestMetrics(rid=i, prompt_len=4, t_submit=0.0,
                           t_admit=0.001, t_first=0.01 * (i + 1))
        m.n_out = 5
        sched.finish(m)
    # any np.percentile call = full-sort path leaked back in
    import repro.serve.scheduler as sched_mod
    monkeypatch.setattr(
        sched_mod.np, "percentile",
        lambda *a, **k: (_ for _ in ()).throw(
            AssertionError("exact-sort percentile on the streaming path")))
    s = sched.summary()
    assert s["requests"] == 50
    assert s["ttft_s"]["p50"] is not None
    assert s["ttft_s"]["p50"] <= s["ttft_s"]["p95"]
    # the ad-hoc exact path still exists (and still sorts)
    monkeypatch.undo()
    assert percentiles([1.0, 3.0])["p50"] == 2.0
    exact = latency_summary(sched.completed)
    assert abs(exact["ttft_s"]["p50"] - s["ttft_s"]["p50"]) \
        / exact["ttft_s"]["p50"] < 0.10


# ------------------------------------------------------------- provenance
def test_provenance_dataclass_stable():
    p = ops.Provenance.collect(impl="ref", quant="int8", attn="sparse",
                               packs={"g": "abc"})
    d = p.to_dict()
    assert d == ops.provenance(impl="ref", quant="int8", attn="sparse",
                               packs={"g": "abc"})
    assert list(d) == ["backend", "impl", "quant", "attn",
                      "pallas_interpret", "packs", "schedule", "env"]
    json.dumps(d)                                  # JSON-ready
    assert ops.Provenance.collect(impl="ref").packs is None
    # pre-autotune callers keep a null schedule field (schema stability);
    # tuned runs carry the TunedPlan.to_provenance() dict
    assert d["schedule"] is None
    tuned = ops.Provenance.collect(
        impl="ref", schedule={"source": "search", "tuned": True})
    assert tuned.to_dict()["schedule"]["tuned"] is True


# ---------------------------------------------------------- engine traced
def test_engine_step_span_coverage_and_metrics():
    """The acceptance bar: a traced engine run covers >= 95% of every
    engine.step with non-overlapping phase spans, and the metrics
    registry carries every required family."""
    cfg = get_config("granite-3-2b", reduced=True)
    params = factory.init_params(cfg, KEY)
    tr = tt.Tracer(enabled=True)
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, tracer=tr)
    for rid in range(3):
        eng.submit(Request(rid=rid, prompt=[1 + rid, 2, 3, 4],
                           max_new_tokens=5))
    eng.run()
    spans = tr.spans()
    cov = tt.span_coverage(spans, "engine.step")
    assert cov["parents"] > 0
    assert cov["coverage"] >= 0.95, cov
    assert cov["overlap_errors"] == [], cov
    cats = {s.cat for s in spans}
    assert {"engine", "scheduler", "decode", "prefill"} <= cats
    bd = tt.phase_breakdown(tr, parent="engine.step")
    assert bd["coverage"] >= 0.95
    # dense engine: every required family except the espim_* plane stats
    tm.validate_snapshot(eng.metrics.snapshot(), sparse=False)
    # step histograms observed once per non-idle tick
    snap = eng.metrics.snapshot()
    steps = sum(v["count"] for k, v in snap.items()
                if k.startswith("serve_step_seconds"))
    assert steps == eng.stats.prefill_chunks + eng.stats.decode_steps


def test_a_view_rebuild_runs_the_gather_program_once():
    """A decode tick that gathers the cache view anew runs the cache's
    gather program, XLA module ``jit_kv_gather_view``, exactly once, and
    a tick that reuses the view runs it not at all: the device trace's
    count of that program is the count of rebuilds."""
    cfg = get_config("granite-3-2b", reduced=True)
    params = factory.init_params(cfg, KEY)
    eng = ServeEngine(cfg, params, batch_slots=1, max_len=48,
                      block_size=16)
    gather = eng.cache._gather
    runs = []

    def counted(*a):
        runs.append(gather.lower(*a).as_text())
        return gather(*a)

    eng.cache._gather = counted
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=8))
    while eng.stats.decode_steps == 0:
        eng.step()
    assert len(runs) == 1               # the first tick builds the view
    assert "module @jit_kv_gather_view" in runs[0]
    eng.step()                          # the next row stays in block 0
    assert len(runs) == 1
    eng.cache.invalidate_view()
    eng.step()
    assert len(runs) == 2


def test_engine_disabled_tracer_by_default():
    cfg = get_config("granite-3-2b", reduced=True)
    params = factory.init_params(cfg, KEY)
    eng = ServeEngine(cfg, params, batch_slots=1, max_len=48)
    assert not eng.tracer.enabled
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=4))
    eng.run()
    assert eng.tracer.spans() == []                # nothing recorded
    # ...but the metrics registry still counted (metrics are always on)
    snap = eng.metrics.snapshot()
    toks = sum(v for k, v in snap.items()
               if k.startswith("serve_tokens_total"))
    assert toks == eng.stats.tokens_generated == 4
