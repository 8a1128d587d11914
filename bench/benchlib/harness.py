"""One run of one benchmark cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names its configuration and its
traffic mix; the harness reads them from ``bench/configs/<config>.json``,
``bench/traffic/<mix>.json`` and ``bench/cells/<cell>.json``, what
depends on the configuration's layers (seeded weights, the reference,
operation and byte counts) from ``bench/families/<model.family>.py``,
and the per-layer metrics from ``bench/metrics/<metric>.py``.  It names
none of them itself.

A run: refuse to start without a TPU of a kind in ``bench/peaks.json``;
make the weights from the configuration's ``weight_seed`` on the device;
load the ESPIM packs from the pack cache or build and save them; build
the program's ``ServeEngine``; warm up on the cell's own traffic; drive
``submit``/``step`` for ``--seconds``; then free the program's state and
check a sample of what the window served against the plain float32
reference.  The last line of standard output is one JSON object.

``setup_s`` runs from the process's start to the window's: imports,
weights, pack load, engine, compiles (from the cache after a checkout's
first run) and warm-up.  Building the packs on a cache miss, and saving
them, is the offline step a deployment makes once per configuration and
is printed apart, not counted in it.

``--trace 1`` spends the first half of the window under the profiler,
with the program's tracer writing its spans into the profiler's trace
(no fences), and the second half with the program's fenced tracer, and
prints per-layer metrics.  A reader gets the trace, the program's spans
of both halves and its counters over the first half.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import time

import numpy as np

from benchlib import packcache, stats, trace_reduce
from benchlib.traffic import Traffic

__all__ = ["Cell", "main", "run"]

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
FAILED_STATES_OK = ("completed",)


class RunError(RuntimeError):
    """The run cannot produce a result (no chip, bad cell)."""


class Cell:
    """Everything one cell names, read from the benchmark's files."""

    def __init__(self, root: pathlib.Path, name: str):
        self.root = root
        spec = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise RunError(f"unknown workload {name!r}; known: "
                           f"{sorted(cells)}")
        self.name, self.entry = name, cells[name]
        conf = {c["name"]: c for c in spec["configs"]}[self.entry["config"]]
        self.config_path = root / conf["file"]
        self.config_bytes = self.config_path.read_bytes()
        self.config = json.loads(self.config_bytes)
        self.model = self.config["model"]
        family = self.model["family"]
        self.family = root / "bench" / "families" / f"{family}.py"
        if not self.family.is_file():
            raise RunError(f"no family module for {family!r}: add "
                           f"bench/families/{family}.py")
        self.mix = json.loads((root / "bench" / "traffic" /
                               f"{self.entry['traffic']}.json").read_text())
        self.cell = json.loads((root / "bench" / "cells" /
                                f"{name}.json").read_text())
        self.chips = int(self.entry["chips"])

        def mine(m):
            return "workloads" not in m or name in m["workloads"]
        self.end_to_end = [m for m in spec["end_to_end"] if mine(m)]
        self.per_layer = [m for m in spec["per_layer"] if mine(m)]


def _fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


def check_device(jax, peaks: dict, chips: int):
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise RunError(f"no TPU: JAX runs on {dev.platform}")
    if dev.device_kind not in peaks:
        raise RunError(f"device kind {dev.device_kind!r} is not in the "
                       f"peaks table (known: {sorted(peaks)})")
    if len(devs) < chips:
        raise RunError(f"the cell needs {chips} chips, JAX sees "
                       f"{len(devs)}")
    return dev


class _Compiles:
    """Counts compilations through ``jax.monitoring``."""

    def __init__(self, jax):
        self.backend = 0
        self.traced = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.backend += 1
        elif name == "/jax/core/compile/jaxpr_trace_duration":
            self.traced += 1

    def snap(self):
        return (self.backend, self.traced)


def _check_model(cfg, m: dict) -> None:
    """The program's config must be the one the configuration file (and
    so the reference) describes: the keys below must be in the file, and
    every key of the file that names a field of the program's config
    must agree with it (``head_dim`` with the head size in use)."""
    got = {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
           "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
           "head_dim": cfg.hd, "d_ff": cfg.d_ff,
           "vocab_size": cfg.vocab_size, "activation": cfg.activation,
           "gated_mlp": cfg.gated_mlp, "tie_embeddings": cfg.tie_embeddings,
           "rope_theta": cfg.rope_theta, "norm_eps": cfg.norm_eps,
           "param_dtype": cfg.param_dtype,
           "compute_dtype": cfg.compute_dtype, "family": cfg.family,
           "qkv_bias": cfg.qkv_bias, "norm": cfg.norm,
           "kv_cache_dtype": cfg.kv_cache_dtype}
    fields = {f.name for f in dataclasses.fields(cfg)} - set(got)
    stated = {k: getattr(cfg, k) for k in m if k in fields}
    bad = {k: (v, m.get(k)) for k, v in {**got, **stated}.items()
           if k in m and m[k] != v}
    missing = sorted(set(got) - set(m))
    if bad or missing:
        raise RunError(f"the program's config departs from the "
                       f"configuration file: {bad}, missing {missing}")


class Client:
    """Offers the cell's traffic to the engine and stamps every token."""

    def __init__(self, jax, eng, Request, traffic: Traffic, log,
                 tok_flops):
        self.jax, self.eng, self.Request = jax, eng, Request
        self.traffic, self.log = traffic, log
        self.reqs: dict = {}
        self.active: dict = {}
        self.seen: dict = {}
        self.lateness: list = []
        self.done_at: dict = {}
        self.flops = 0.0
        self.tok_flops = tok_flops
        self.prefilled: dict = {}

    def submit(self, spec: dict, due: float) -> None:
        rid = spec["index"]
        req = self.Request(rid=rid, prompt=spec["prompt"],
                           max_new_tokens=spec["max_new"])
        self.reqs[rid] = req
        self.active[rid] = req
        self.seen[rid] = 0
        self.prefilled[rid] = 0
        self.log.offer(rid, due)
        with self.jax.profiler.TraceAnnotation("bench.submit"):
            self.eng.submit(req)
        self.lateness.append(time.perf_counter() - due)

    def step(self) -> float:
        """One engine tick; stamps the tokens it emitted and adds the
        operations of every token it processed to ``flops``."""
        with self.jax.profiler.TraceAnnotation("bench.step"):
            self.eng.step()
        t = time.perf_counter()
        pos = {s.req.rid: s.pos for s in self.eng.slots
               if s is not None and s.phase == "prefill"}
        flops = 0.0
        for rid, req in list(self.active.items()):
            n = len(req.output)
            a = self.prefilled[rid]
            b = pos.get(rid, len(req.prompt) if n else a)
            # prompt rows prefilled this tick (positions a..b-1), then the
            # decoded tokens: output j > 0 was computed at context P + j
            flops += sum(self.tok_flops(p + 1) for p in range(a, b))
            self.prefilled[rid] = b
            if n > self.seen[rid]:
                flops += sum(self.tok_flops(len(req.prompt) + j)
                             for j in range(max(1, self.seen[rid]), n))
                self.log.stamp(rid, n - self.seen[rid], t)
                self.seen[rid] = n
            if req.done:
                self.done_at[rid] = t
                del self.active[rid]
        self.flops += flops
        return t

    def idle(self) -> bool:
        return (not self.eng.scheduler.has_pending
                and all(s is None for s in self.eng.slots))

    def all_decoding(self) -> bool:
        return all(s is not None and s.phase == "decode"
                   for s in self.eng.slots)


def registry_values(reg) -> dict:
    """{series: (kind, value)} of every counter and gauge in the program's
    metrics registry.  A series is the instrument's name with the labels
    it adds to the registry's own, as ``name{key="value",...}``."""
    out = {}
    for name, fam in reg._metrics.items():
        for inst in fam.values():
            if inst.kind not in ("counter", "gauge"):
                continue
            lab = {k: v for k, v in inst.labels.items()
                   if reg.base_labels.get(k) != v}
            key = ",".join(f'{k}="{lab[k]}"' for k in sorted(lab))
            out[f"{name}{{{key}}}" if key else name] = (inst.kind,
                                                         inst.value)
    return out


def window_counters(start: dict, end: dict) -> dict:
    """From two ``registry_values`` readings: each counter's rise from
    ``start`` to ``end``, and each gauge's value at ``end``."""
    return {k: v - start.get(k, (kind, 0))[1] if kind == "counter" else v
            for k, (kind, v) in end.items()}


def _load_reader(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _sample(client: Client, states: dict, t0: float, t1: float, k: int,
            seed: int):
    """Up to ``k`` requests the window served a token of, drawn from the
    seed, the one with the most served tokens always among them.  A
    request still in service at the close is compared on the tokens it
    was served; one that ended other than completed is not drawn."""
    done = sorted(rid for rid, r in client.reqs.items()
                  if states.get(rid, "completed") == "completed"
                  and any(t0 < t <= t1 for t in client.log.stamps[rid]))
    if not done:
        return []
    longest = max(done, key=lambda r: (len(client.reqs[r].output), -r))
    rest = [r for r in done if r != longest]
    rng = np.random.default_rng([int(seed), 7])
    pick = list(rng.permutation(rest)[: max(0, k - 1)])
    return [longest] + [int(r) for r in pick]


# one step below each stated precision: weight planes, activations
LOWER = {"int8": 4, "bfloat16": "float8_e4m3fn", "float32": "bfloat16"}


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        *, require_tpu: bool = True, impl: str = "pallas",
        cache_dir: pathlib.Path | None = None, fault=None,
        control: bool = False) -> dict:
    """One run; returns the result object (the last line's JSON).

    ``require_tpu=False``, ``impl`` and ``fault`` are for tests on the
    CPU: ``fault(engine)`` may break the engine before the window.
    ``control`` also reads, on the same
    tokens, the control (the reference in the program's place with its
    weights one step below the stated ones), the reference with the
    stated weights and its activations one step below the stated compute
    dtype, and a witness of the program's own rounding (stated weights
    and compute dtype); it holds each to the same limits and saves every
    per-token reading under ``<cache>/control/`` (``bench/control.py``)."""
    import jax

    def mem() -> str:
        st = jax.devices()[0].memory_stats() or {}
        return (f"in use {st.get('bytes_in_use', 0)}, peak "
                f"{st.get('peak_bytes_in_use', 0)}")

    cache_dir = cache_dir or (cell.root / "bench" / ".cache")
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if require_tpu:
        dev = check_device(jax, peaks, cell.chips)
        peak = peaks[dev.device_kind]
    else:
        dev = jax.devices()[0]
        peak = peaks.get(dev.device_kind) or next(iter(peaks.values()))
    jax.config.update("jax_compilation_cache_dir", str(cache_dir / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = _Compiles(jax)

    sys.path.insert(0, str(cell.root / "src"))
    from repro.configs.registry import get_config
    from repro.core.sparse_model import pruned_param_tree, sparsify_model
    from repro.kernels import ops
    from repro.serve.engine import Request, ServeEngine
    from repro.telemetry import trace as tt

    from benchlib import reference

    fam = _load_reader(cell.family)
    conf, m, c = cell.config, cell.model, cell.cell
    wseed = int(conf["weight_seed"])
    secs = {}
    t = time.perf_counter()
    cfg = get_config(conf["arch"]).replace(**conf.get("overrides", {}))
    _check_model(cfg, m)
    params = fam.make_params(m, wseed)
    jax.block_until_ready(params)
    secs["init"] = time.perf_counter() - t
    print(f"weights made: device memory {mem()}", file=sys.stderr, flush=True)

    key = packcache.cache_key(cell.config_bytes, wseed,
                              packcache.source_digest(cell.root / "src"))
    path = cache_dir / "packs" / f"{conf['name']}-{key}.pkl"
    sparse, info = packcache.load_or_build(path, lambda: sparsify_model(
        cfg, params, float(conf["sparsity"]),
        projections=conf["projections"], quant=conf["quant"]))
    secs.update({k: v for k, v in info.items() if k != "hit"})
    # every projection serves from the packs (decode) and the pruned
    # copies (prefill, and the dense fallback): the engine gets the pruned
    # model, and the dense originals, needed only to pack, are freed
    params = pruned_param_tree(params, sparse)
    print(f"pack cache {'hit' if info['hit'] else 'miss'}: {path.name}; "
          f"device memory {mem()}", file=sys.stderr, flush=True)

    t = time.perf_counter()
    eng = ServeEngine(cfg, params, batch_slots=int(c["slots"]),
                      max_len=int(c["max_len"]), temperature=0.0,
                      sparse=sparse, impl=impl,
                      prefill_chunk=int(c["prefill_chunk"]), seed=0)
    prov = ops.provenance(impl=impl)
    if require_tpu and (prov["impl"] != "pallas" or prov["pallas_interpret"]):
        raise RunError(f"the engine would not run the native kernels: {prov}")
    secs["engine"] = time.perf_counter() - t
    step_bytes = fam.espim_step_bytes(sparse, int(c["slots"]))
    step_ops = fam.espim_step_ops(sparse, int(c["slots"]))
    if fault is not None:
        fault(eng)

    sparsity, projections = float(conf["sparsity"]), conf["projections"]
    f0 = fam.token_flops(m, sparsity, projections, 0)
    f1 = fam.token_flops(m, sparsity, projections, 1) - f0

    def tok_flops(ctx):
        return f0 + f1 * ctx

    # ---- warm-up on the cell's own traffic ------------------------------
    t = time.perf_counter()
    log = stats.TokenLog()
    open_loop = cell.mix["loop"] == "open"
    vocab = int(m["vocab_size"])
    traffic = Traffic(cell.mix, c, vocab, seed)
    client = Client(jax, eng, Request, traffic, log, tok_flops)

    def top_up():
        while eng.scheduler.queue_depth < traffic.backlog:
            client.submit(traffic.next(), time.perf_counter())

    if open_loop:
        # a batch of the mix's own requests, served to the end before the
        # arrivals start: every program of the window runs once
        warm = Client(jax, eng, Request,
                      Traffic(cell.mix, c, vocab, [int(seed), 1]),
                      stats.TokenLog(), tok_flops)
        for _ in range(int(c["slots"])):
            spec = warm.traffic.next()
            spec["index"] += 10 ** 9
            warm.submit(spec, time.perf_counter())
        while not warm.idle():
            warm.step()
        del warm
    else:
        # the backlog itself: the window opens once every slot decodes
        top_up()
        while not (client.all_decoding() and eng.stats.decode_steps > 0):
            client.step()
            top_up()
    secs["warmup"] = time.perf_counter() - t
    print(f"warmed up: device memory {mem()}", file=sys.stderr, flush=True)

    logdir = cache_dir / "trace" / cell.name
    if trace:
        shutil.rmtree(logdir, ignore_errors=True)
        jax.profiler.start_trace(str(logdir))

    # ---- the window ------------------------------------------------------
    t0 = time.perf_counter()
    setup_s = t0 - t_start - secs.get("pack", 0.0) - secs.get("save", 0.0)
    comp0 = compiles.snap()
    steps0 = (eng.stats.decode_steps, eng.stats.prefill_chunks)
    flops0, flops_a = client.flops, None
    t_half = t0 + seconds / 2 if trace else None
    tracer = counters0 = counters = None
    win = jax.profiler.TraceAnnotation("bench.window") if trace else None
    if win:
        counters0 = registry_values(eng.metrics)
        eng.tracer = tt.Tracer(enabled=True, profiler=True)
        win.__enter__()
    t_a = None
    now = t0
    end = t0 + seconds
    while now < end:
        if trace and tracer is None and now >= t_half:
            # the profiler keeps recording (stopping it takes seconds);
            # the device metrics read only the annotated first half
            win.__exit__(None, None, None)
            t_a = now
            flops_a = client.flops - flops0
            counters = window_counters(counters0,
                                       registry_values(eng.metrics))
            tracer = tt.Tracer(enabled=True)
            eng.tracer = tracer
        if open_loop:
            while traffic.peek_due() + t0 <= now:
                spec = traffic.next()
                client.submit(spec, t0 + spec["due_s"])
            if client.idle():
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, min(traffic.peek_due() + t0,
                                            end) - now))
                now = time.perf_counter()
                continue
        else:
            top_up()
        now = client.step()
    t1 = now
    comp1 = compiles.snap()
    steps1 = (eng.stats.decode_steps - steps0[0],
              eng.stats.prefill_chunks - steps0[1],
              eng.scheduler.queue_depth)
    if trace:
        eng.tracer = tt.get_tracer()
        jax.profiler.stop_trace()
    # open loop: serve what was offered to its end, untimed, so every
    # request due in the window has its first token and its answer
    drain_end = time.perf_counter() + 60.0
    while open_loop and not client.idle() and time.perf_counter() < drain_end:
        client.step()

    peak_bytes = int(jax.devices()[0].memory_stats().get(
        "peak_bytes_in_use", 0)) if dev.platform == "tpu" else 0
    states = {mm.rid: mm.state for mm in eng.scheduler.completed}
    # requests offered before the close and not finished before the open
    attempted = [rid for rid in client.reqs
                 if log.due[rid] < end and client.done_at.get(rid, t1) > t0]
    failed = [rid for rid in attempted
              if (rid in states and states[rid] not in FAILED_STATES_OK)
              or (open_loop and not client.reqs[rid].done)]
    lat = client.lateness if open_loop else []
    print(f"setup phases: {json.dumps({k: round(v, 3) for k, v in secs.items()})}"
          f" setup_s {setup_s:.3f} (pack and save left out)",
          file=sys.stderr, flush=True)
    print(f"window {t1 - t0:.3f} s: compilations {comp1[0] - comp0[0]}, "
          f"traces {comp1[1] - comp0[1]}; decode steps {steps1[0]}, "
          f"prefill chunks {steps1[1]}, queued at the close {steps1[2]}; "
          f"peak_bytes_in_use {peak_bytes}",
          file=sys.stderr, flush=True)
    if lat:
        print(f"generator lateness: max {max(lat) * 1e3:.3f} ms, p95 "
              f"{stats.percentile(lat, 95) * 1e3:.3f} ms",
              file=sys.stderr, flush=True)

    # ---- metrics ---------------------------------------------------------
    e2e = {"setup_s": setup_s,
           "tok_s": log.tokens(t0, t1) / (t1 - t0)}
    gaps = log.gaps(t0, t1)
    if gaps:
        e2e["itl_p95_ms"] = stats.percentile(gaps, 95) * 1e3
        print("inter-token gaps (ms): " + ", ".join(
            f"p{p} {stats.percentile(gaps, p) * 1e3:.3f}"
            for p in (50, 90, 95, 97, 99)) +
            f", mean {sum(gaps) / len(gaps) * 1e3:.3f}",
            file=sys.stderr, flush=True)
    due_in = [rid for rid in client.reqs if t0 <= log.due[rid] < end]
    if open_loop and due_in:
        tt_ = log.ttfts(due_in)
        if tt_:
            e2e["ttft_p95_s"] = stats.percentile(tt_, 95)
            print(f"ttft: p50 {stats.percentile(tt_, 50):.4f} s, p90 "
                  f"{stats.percentile(tt_, 90):.4f} s over {len(tt_)}",
                  file=sys.stderr, flush=True)
    print(f"samples: {len(gaps)} gaps, {len(due_in)} requests due",
          file=sys.stderr, flush=True)

    result_metrics = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    breakdown = None
    if not trace:
        for mt in cell.end_to_end:
            if mt["name"] not in e2e:
                raise RunError(f"metric {mt['name']} was not measured")
            result_metrics[mt["name"]] = {"value": e2e[mt["name"]],
                                          "unit": mt["unit"]}
    else:
        tr = trace_reduce.extract(trace_reduce.find_xplane(str(logdir)))
        w = trace_reduce.window(tr)
        busy = trace_reduce.busy_ns(tr, w)
        device["busy_s"] = busy / 1e9
        device["window_s"] = (w[1] - w[0]) / 1e9
        ctx = {"model": m, "sparsity": sparsity, "projections": projections,
               "peak": peak, "trace": tr, "window_ns": w,
               "window_s": t_a - t0, "flops": flops_a,
               "spans": tracer.spans() if tracer else [],
               "program": trace_reduce.events_in(tr["program"], w),
               "counters": counters or {},
               "espim_step_bytes": step_bytes, "espim_step_ops": step_ops}
        for mt in cell.per_layer:
            rd = _load_reader(BENCH / "metrics" / f"{mt['name']}.py")
            v = rd.read(ctx)
            if v is not None:
                result_metrics[mt["name"]] = {"value": float(v),
                                              "unit": mt["unit"]}
        breakdown = {"device_ops": trace_reduce.top_ops(tr, w),
                     "idle_gaps": trace_reduce.idle_gaps(tr, w)}

    # ---- correctness: free the program's state, then the reference ------
    chk = c["check"]
    sample = _sample(client, states, t0, t1, int(chk["sample"]), seed)
    rows = [(client.reqs[r].prompt, client.reqs[r].output) for r in sample]
    del eng, sparse, params, client
    gc.collect()
    jax.clear_caches()
    t = time.perf_counter()
    n_tok = sum(len(o) for _, o in rows)
    variants = {}
    if control:
        bits = int(conf["quant"][3:])
        variants = {"control": {"bits": LOWER[conf["quant"]]},
                    "control_act": {"bits": bits,
                                    "act": LOWER[m["compute_dtype"]]},
                    "witness": {"bits": bits, "act": m["compute_dtype"]}}
    got = {}
    if rows:
        got = fam.readings(m, wseed, sparsity, projections, rows,
                           int(c["max_len"]), variants)
        for r, rid in enumerate(sample):
            desc = ", ".join(
                f"{k} mean {g[r].mean():.4g} max {g[r].max():.4g} at "
                f"{int(g[r].argmax())}" for k, g in got.items())
            print(f"request {rid}: prompt {len(rows[r][0])}, served "
                  f"{len(rows[r][1])}: {desc}", file=sys.stderr, flush=True)
    print(f"reference: {len(rows)} requests, {n_tok} served tokens, "
          f"{time.perf_counter() - t:.3f} s", file=sys.stderr, flush=True)
    if control and rows:
        (cache_dir / "control").mkdir(parents=True, exist_ok=True)
        np.savez(cache_dir / "control" / f"{cell.name}-{seed}.npz",
                 rids=np.asarray(sample), prompt_len=np.asarray(
                     [len(p) for p, _ in rows]),
                 **{f"{k}_{r}": g[r] for k, g in got.items()
                    for r in range(len(rows))})

    def judge(gaps) -> tuple[bool, dict]:
        st = reference.gap_stats(gaps) if gaps else {}
        for k, v in st.items():
            if k not in chk["limits"]:
                print(f"reading {k}: {v}", file=sys.stderr, flush=True)
        checks = {k: {"value": st.get(k, float("inf")), "limit": float(lim)}
                  for k, lim in chk["limits"].items()}
        ok = all(v["value"] <= v["limit"] for v in checks.values())
        return ok, checks

    ok, checks = judge(got.get("served"))
    checks["failed"] = {"value": len(failed), "limit": 0}
    checks["served_tokens_at_least"] = {"value": n_tok,
                                        "limit": int(chk["min_tokens"])}
    correct = ok and not failed and n_tok >= int(chk["min_tokens"])
    out = {"correct": bool(correct), "attempted": len(attempted),
           "failed": len(failed), "metrics": result_metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    for name in variants:
        print(f"{name}:", file=sys.stderr, flush=True)
        v_ok, v_checks = judge(got.get(name))
        out[name] = {"correct": v_ok, "checks": v_checks}
        for k, v in v_checks.items():
            print(f"{name} check {k}: {v['value']} (limit {v['limit']})",
                  file=sys.stderr, flush=True)
    out["checks"] = checks
    for k, v in checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    return out


def main(argv=None, t_start: float | None = None) -> None:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = Cell(ROOT, args.workload)
        out = run(cell, args.seed, args.seconds, bool(args.trace), t_start)
    except RunError as e:
        _fail(str(e))
    print(json.dumps(out), flush=True)
