"""The serving program's own marks in a profiler trace, for the per-layer
metrics of the serving layers.

``load(path)`` keeps what ``trace.load`` keeps (device ops labelled
``module/op``, the runners' ``bench.*`` spans; kernels are not marked,
nothing here reads them) and the program's ``serve.*`` host spans with
their arguments.  ``reduce`` gives, inside the window:

* ``module_s`` — device seconds of each compiled program by the name it
  carries (``jit_decode_step``, ``jit_head_decode``, ``jit_prefill``, ...):
  the union of its ops' intervals, averaged over the devices used;
* ``spans`` — for each ``serve.*`` name that opened in the window, its
  count ``n``, its seconds ``s`` (cut at the window's close) and the sum
  of each numeric argument (``sum``);
* ``idle_gaps`` — the device's idle seconds by the innermost host span
  over them, ``bench.*`` or ``serve.*`` (the latest-starting one that
  covers the moment; "host.unmarked" where none does).

A traced run leaves its trace under ``perfbench_out/trace/<cell>`` (the
default ``--trace-dir`` of ``run.py``), and ``reading(ctx)`` finds it
there.  Where the program leaves no such span or module name, the readers
built on it find nothing and read nothing.
"""

from __future__ import annotations

import bisect
import functools
import glob
import heapq
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from harness import trace
from harness.common import ROOT

SPAN_PREFIX = "serve."


def load(path: str) -> Dict:
    """``trace.load``'s records (``devices`` and the ``bench.*`` spans in
    ``host``) plus ``"serve": [(name, start_ns, dur_ns, args)]``, the
    program's spans, in one pass over the file (a traced window of a
    chat cell is over a hundred MB)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple]] = {}
    host: List[Tuple] = []
    serve: List[Tuple] = []
    for plane in data.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((float(e.start_ns), float(e.start_ns + e.duration_ns),
                           e.name) for e in lines.get(trace.MODULES_LINE, []))
            starts = [m[0] for m in mods]
            ops = []
            for e in lines.get(trace.OPS_LINE, []):
                i = bisect.bisect_right(starts, float(e.start_ns)) - 1
                module = mods[i][2] if i >= 0 and \
                    float(e.start_ns) < mods[i][1] else ""
                ops.append((e.name, float(e.start_ns), float(e.duration_ns),
                            False, trace.op_label(e.name, module)))
            devices[plane.name] = ops
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.name, float(e.start_ns),
                                     float(e.duration_ns)))
                    elif e.name.startswith(SPAN_PREFIX):
                        serve.append((e.name, float(e.start_ns),
                                      float(e.duration_ns), dict(e.stats)))
    return {"devices": devices, "host": host, "serve": serve}


def _window(records, ops) -> Tuple[float, float]:
    """``bench.window`` where it covers the device's ops, else their span
    (the rule of ``trace.reduce``)."""
    lo = min(s for _, s, _, _, _ in ops)
    hi = max(s + d for _, s, d, _, _ in ops)
    win = [(s, s + d) for n, s, d in records["host"]
           if n == trace.WINDOW_SPAN]
    if win and win[0][0] <= lo and hi <= win[0][1]:
        return win[0]
    return lo, hi


def innermost(gaps, spans) -> Dict[str, float]:
    """Seconds of the (non-overlapping) ``gaps`` by the innermost span over
    each part of them: the latest-starting span that covers it, ties by
    name; ``spans`` are ``(name, start_ns, dur_ns)``.  One sweep, so a
    span that opened before many shorter ones still counts."""
    spans = sorted((s, s + d, n) for n, s, d in spans
                   if n != trace.WINDOW_SPAN)
    rank = {i: r for r, i in enumerate(sorted(
        range(len(spans)), key=lambda i: (spans[i][0], spans[i][2])))}
    out: Dict[str, float] = defaultdict(float)
    live: List[Tuple[int, int]] = []     # (-rank, index) of opened spans
    j = 0
    for g0, g1 in sorted(gaps):
        a = g0
        while a < g1:
            while j < len(spans) and spans[j][0] <= a:
                heapq.heappush(live, (-rank[j], j))
                j += 1
            while live and spans[live[0][1]][1] <= a:
                heapq.heappop(live)
            b = min(g1, spans[j][0]) if j < len(spans) else g1
            name = "host.unmarked"
            if live:
                _, end, name = spans[live[0][1]]
                b = min(b, end)
            out[name] += (b - a) * 1e-9
            a = b
    return dict(out)


def _span_totals(serve, lo: float, hi: float) -> Dict[str, Dict]:
    out: Dict[str, Dict] = {}
    for name, s, d, args in serve:
        if lo <= s < hi:
            t = out.setdefault(name, {"n": 0, "s": 0.0, "sum": {}})
            t["n"] += 1
            t["s"] += min(d, hi - s) * 1e-9
            for k, v in args.items():
                if isinstance(v, (int, float)):
                    t["sum"][k] = t["sum"].get(k, 0) + v
    return out


def reduce(records: Dict, top: int = 12) -> Optional[Dict]:
    """``module_s``, ``spans`` and ``idle_gaps`` inside the window, with
    the ``busy_s`` and ``window_s`` they are read against; None where the
    trace holds no device op."""
    devices = [ops for _, ops in sorted(records["devices"].items()) if ops]
    if not devices:
        return None
    spans = records["host"] + [r[:3] for r in records["serve"]]
    module_t: Dict[str, float] = defaultdict(float)
    gaps_by: Dict[str, float] = defaultdict(float)
    busy_t = 0.0
    for ops in devices:
        lo, hi = _window(records, ops)
        busy = trace._clip(trace._union(
            [(s, s + d) for _, s, d, _, _ in ops]), lo, hi)
        busy_t += sum(e - s for s, e in busy) * 1e-9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for k, v in innermost([(a, b) for a, b in zip(edges[::2], edges[1::2])
                               if b > a], spans).items():
            gaps_by[k] += v
        by_module: Dict[str, List] = defaultdict(list)
        for _, s, d, _, label in ops:
            if "/" in label:
                by_module[label.split("/", 1)[0]].append((s, s + d))
        for m, iv in by_module.items():
            module_t[m] += sum(e - s for s, e in trace._clip(
                trace._union(iv), lo, hi)) * 1e-9
    n = len(devices)
    lo, hi = _window(records, devices[0])
    return {"busy_s": busy_t / n, "window_s": (hi - lo) * 1e-9,
            "module_s": {k: v / n for k, v in sorted(
                module_t.items(), key=lambda kv: -kv[1])},
            "spans": _span_totals(records["serve"], lo, hi),
            "idle_gaps": sorted(([k, v / n] for k, v in gaps_by.items()),
                                key=lambda kv: -kv[1])[:top]}


@functools.lru_cache(maxsize=2)
def _reduced(path: str, mtime_ns: int, size: int) -> Optional[Dict]:
    return reduce(load(path))


def trace_file(ctx: Dict) -> Optional[str]:
    """The newest trace of the run's cell under the default trace
    directory, or None."""
    found = glob.glob(os.path.join(ROOT, "perfbench_out", "trace",
                                   str(ctx.get("cell")), "**",
                                   "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def reading(ctx: Dict) -> Optional[Dict]:
    """``reduce`` of the trace a traced serving run just took, read once
    for all the readers of the run; None in any other run."""
    if ctx.get("kind") != "serve" or not ctx.get("trace"):
        return None
    path = trace_file(ctx)
    if path is None:
        return None
    st = os.stat(path)
    return _reduced(path, st.st_mtime_ns, st.st_size)
