"""From a profiler trace to metrics.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps what the metrics read, in a small JSON-able form:

* ``device``: per device plane (``/device:TPU:<n>``), the events of its
  ``XLA Ops`` line (one per operation run on the device, named by its
  HLO instruction, such as ``%espim_spmv_planes.40``; a loop's
  operations nest inside the loop's own event) and of its ``XLA
  Modules`` line (one per compiled program run), each ``[name,
  start_ns, duration_ns]``;
* ``host``: the benchmark's own ``jax.profiler.TraceAnnotation`` spans
  (names starting with ``bench.``), on the same clock;
* ``program``: the program's own spans, which its tracer writes as
  ``TraceAnnotation``s in the profiler's mode, each ``[name, start_ns,
  duration_ns]``: every host event named as the program names its spans,
  dotted lower-case words such as ``engine.step`` or ``cache.gather``
  (the runtime's own host events, the Python tracer's ``$``-prefixed
  calls and the CPU backend's ops, such as ``dot_general.1``, are not).

The functions below take that form, so a recorded trace committed with
the benchmark checks them without a chip.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

__all__ = ["extract", "find_xplane", "window", "union", "busy_ns",
           "events_in", "module_events", "kernel_ns", "top_ops",
           "idle_gaps", "under", "self_times"]

OPS, MODULES = "XLA Ops", "XLA Modules"
PROGRAM_SPAN = re.compile(r"[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+")


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def extract(path: str, host_prefix: str = "bench.") -> dict:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    out = {"device": {}, "host": [], "program": []}
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS, MODULES):
                    lines[line.name] = [[ev.name.split(" = ", 1)[0],
                                         float(ev.start_ns),
                                         float(ev.duration_ns)]
                                        for ev in line.events]
            out["device"][plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(host_prefix):
                        key = "host"
                    elif PROGRAM_SPAN.fullmatch(ev.name):
                        key = "program"
                    else:
                        continue
                    out[key].append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
    return out


def window(tr: dict, name: str = "bench.window") -> tuple:
    """[start, end] in ns of the host annotation ``name``."""
    spans = [(t, t + d) for n, t, d in tr["host"] if n == name]
    if not spans:
        raise ValueError(f"the trace has no {name!r} annotation")
    return spans[0]


def union(intervals) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(intervals, w):
    return [(max(a, w[0]), min(b, w[1])) for a, b in intervals
            if b > w[0] and a < w[1]]


def busy_ns(tr: dict, w: tuple) -> float:
    """Mean over device planes of the union of op intervals in ``w``."""
    planes = list(tr["device"].values())
    if not planes:
        return 0.0
    tot = 0.0
    for lines in planes:
        iv = _clip([(t, t + d) for _, t, d in lines.get(OPS, [])], w)
        tot += sum(b - a for a, b in union(iv))
    return tot / len(planes)


def events_in(evs, w: tuple, pattern: str | None = None) -> list:
    """Events wholly inside ``w`` whose name matches ``pattern``."""
    rx = re.compile(pattern) if pattern else None
    return [e for e in evs if e[1] >= w[0] and e[1] + e[2] <= w[1]
            and (rx is None or rx.search(e[0]))]


def module_events(tr: dict, w: tuple, pattern: str) -> list:
    return [e for lines in tr["device"].values()
            for e in events_in(lines.get(MODULES, []), w, pattern)]


def kernel_ns(tr: dict, spans: list, pattern: str) -> float:
    """Total device time of the ops matching ``pattern`` that run inside
    the given program spans."""
    rx = re.compile(pattern)
    spans = sorted((s[1], s[1] + s[2]) for s in spans)
    starts = [a for a, _ in spans]
    tot = 0.0
    for lines in tr["device"].values():
        for n, t, d in lines.get(OPS, []):
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and t + d <= spans[i][1] and rx.search(n):
                tot += d
    return tot


def self_times(evs) -> list:
    """[name, start, self duration] per event: its duration less that of
    the events nested directly inside it (a loop less its body's ops)."""
    out, stack = [], []
    for name, t, d in sorted(evs, key=lambda e: (e[1], -e[2])):
        while stack and t >= stack[-1][1] + stack[-1][2]:
            stack.pop()
        rec = [name, t, d]
        if stack:
            stack[-1][3][2] -= d
        out.append(rec)
        stack.append((name, t, d, rec))
    return out


def top_ops(tr: dict, w: tuple, n: int = 10) -> list:
    """[[op name, seconds]] of the ops that took most device time in
    ``w``, by self time, so the ops of a loop are not counted twice."""
    tot: dict = {}
    for lines in tr["device"].values():
        inside = [e for e in lines.get(OPS, []) if e[1] >= w[0]
                  and e[1] + e[2] <= w[1]]
        for name, _, d in self_times(inside):
            tot[name] = tot.get(name, 0.0) + d
    return [[k, v / 1e9] for k, v in sorted(tot.items(),
                                             key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: dict, w: tuple, n: int = 10) -> list:
    """Idle device time in ``w``, summed by what the host was doing: the
    innermost ``bench.*`` annotation (other than the window's own) at the
    middle of each gap.  [[activity (gap count), seconds]], longest
    first."""
    host = sorted((t, t + d, name) for name, t, d in tr["host"]
                  if name != "bench.window")
    starts = [h[0] for h in host]
    tot: dict = {}
    for lines in tr["device"].values():
        busy = union(_clip([(t, t + d) for _, t, d in lines.get(OPS, [])],
                           w))
        edges = [w[0]] + [x for iv in busy for x in iv] + [w[1]]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            # the benchmark's spans do not overlap, so the latest one
            # that starts before the middle is the only candidate
            i = bisect.bisect_right(starts, mid) - 1
            what = host[i][2] if i >= 0 and host[i][1] >= mid else "none"
            c, s = tot.get(what, (0, 0.0))
            tot[what] = (c + 1, s + (b - a))
    ranked = sorted(tot.items(), key=lambda kv: -kv[1][1])[:n]
    return [[f"{k} ({c} gaps)", s / 1e9] for k, (c, s) in ranked]


def under(spans, root: str) -> dict:
    """The program tracer's spans grouped by their nearest ancestor named
    ``root``: {root span: [its descendant spans]}."""
    by_id = {s.sid: s for s in spans}
    out = {s: [] for s in spans if s.name == root}
    for s in spans:
        p = by_id.get(s.parent_id)
        while p is not None and p.name != root:
            p = by_id.get(p.parent_id)
        if p is not None:
            out[p].append(s)
    return out
