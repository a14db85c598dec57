"""From a JAX profiler trace to device metrics.

``load(path)`` reads an ``.xplane.pb`` into plain records; ``reduce``
turns them into the device's busy time, the time of the Pallas kernels
(Mosaic custom calls), the device operations that took most time, and the
longest idle gaps by what the host was doing (the ``bench.*`` spans the
runners record).  Busy time is the union of the op intervals on each
device's "XLA Ops" line, averaged over the devices used.

Until every ``pallas_call`` carries a ``name=``, a kernel is found as a
custom call among the device's ops: on a TPU an op event is named by its
HLO text, and a kernel's reads ``custom-call(...)`` with
``custom_call_target="tpu_custom_call"``.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNEL_MARKS = ("custom-call(", "tpu_custom_call")
WINDOW_SPAN = "bench.window"


def start(log_dir: str) -> None:
    """Start the profiler into an emptied ``log_dir`` (one trace per run,
    so traced runs do not pile traces up on disk)."""
    import shutil
    import jax
    shutil.rmtree(log_dir, ignore_errors=True)
    jax.profiler.start_trace(log_dir)


def stop_and_find(log_dir: str) -> str:
    import jax
    jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _is_kernel(name: str, stats: Dict) -> bool:
    """A Mosaic kernel: the op itself is a ``tpu_custom_call``."""
    text = name + " " + " ".join(str(v) for v in stats.values())
    return all(m in text for m in KERNEL_MARKS)


def op_label(name: str, module: str = "") -> str:
    """``module/op`` from an op event named by its HLO text
    (``%jvp__.1 = f32[...] custom-call(...)`` -> ``jit_f/jvp__``)."""
    op = re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].lstrip("%"))
    module = re.sub(r"\(\d+\)$", "", module)
    return f"{module}/{op}" if module else op


def load(path: str) -> Dict:
    """{"devices": {plane: [(name, start_ns, dur_ns, is_kernel, label)]},
    "host": [(name, start_ns, dur_ns)]} — host records are the runners'
    ``bench.*`` spans only."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple]] = {}
    host: List[Tuple] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((float(e.start_ns), float(e.start_ns + e.duration_ns),
                           e.name) for e in lines.get(MODULES_LINE, []))
            starts = [m[0] for m in mods]
            ops = []
            for e in lines.get(OPS_LINE, []):
                i = bisect.bisect_right(starts, float(e.start_ns)) - 1
                module = mods[i][2] if i >= 0 and \
                    float(e.start_ns) < mods[i][1] else ""
                ops.append((e.name, float(e.start_ns), float(e.duration_ns),
                            _is_kernel(e.name, dict(e.stats)),
                            op_label(e.name, module)))
            devices[plane.name] = ops
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.name, float(e.start_ns),
                                     float(e.duration_ns)))
    return {"devices": devices, "host": host}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _attribute(gaps, host) -> Dict[str, float]:
    """Seconds of idle gap by the innermost host span over each part of
    it (the latest-starting span that covers it); "host.unmarked" where
    no span does."""
    spans = sorted((s, s + d, n) for n, s, d in host if n != WINDOW_SPAN)
    starts = [s for s, _, _ in spans]
    out: Dict[str, float] = defaultdict(float)
    for g0, g1 in gaps:
        hi = bisect.bisect_left(starts, g1)
        cover = [(s, e, n) for s, e, n in spans[max(0, hi - 64):hi]
                 if e > g0]
        cuts = sorted({g0, g1} | {min(max(x, g0), g1)
                                  for s, e, _ in cover for x in (s, e)})
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            live = [(s, n) for s, e, n in cover if s <= mid < e]
            name = max(live)[1] if live else "host.unmarked"
            out[name] += (b - a) * 1e-9
    return dict(out)


def reduce(records: Dict, top: int = 10) -> Dict:
    """Busy and window seconds, kernel seconds, and the breakdown.

    The window is the ``bench.window`` host span where there is one and
    it covers the device's ops (the same clock); otherwise the span of
    the device's ops.
    """
    win = [(s, s + d) for n, s, d in records["host"] if n == WINDOW_SPAN]
    per_dev = []
    op_time: Dict[str, float] = defaultdict(float)
    kernel_s = 0.0
    all_gaps: List[Tuple[float, float]] = []
    window_s = None
    for plane, ops in sorted(records["devices"].items()):
        if not ops:
            continue
        lo = min(s for _, s, _, _, _ in ops)
        hi = max(s + d for _, s, d, _, _ in ops)
        if win and win[0][0] <= lo and hi <= win[0][1]:
            lo, hi = win[0]
        busy = _clip(_union([(s, s + d) for _, s, d, _, _ in ops]), lo, hi)
        per_dev.append(sum(e - s for s, e in busy) * 1e-9)
        window_s = (hi - lo) * 1e-9 if window_s is None else window_s
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        all_gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for name, s, d, is_kernel, label in ops:
            op_time[label] += d * 1e-9
            if is_kernel:
                kernel_s += d * 1e-9
    if not per_dev:
        return {"busy_s": None, "window_s": None, "kernel_s": None,
                "breakdown": None}
    n_dev = len(per_dev)
    gaps_by = _attribute(all_gaps, records["host"])
    return {
        "busy_s": sum(per_dev) / n_dev,
        "window_s": window_s,
        "kernel_s": kernel_s / n_dev,
        "breakdown": {
            "device_ops": sorted(([k, v / n_dev] for k, v in op_time.items()),
                                 key=lambda kv: -kv[1])[:top],
            "idle_gaps": sorted(([k, v / n_dev] for k, v in gaps_by.items()),
                                key=lambda kv: -kv[1])[:top]},
    }


def summary(records: Dict, limit: int = 40) -> Dict:
    """A short look at a trace (for a first inspection of a new chip's
    naming): distinct op labels with a kernel flag, and host span names."""
    seen = {}
    for ops in records["devices"].values():
        for name, _, _, k, label in ops:
            if len(seen) < limit and (name, label) not in seen:
                seen[(name, label)] = k
    return {"planes": sorted(records["devices"]),
            "ops": [[n, l, k] for (n, l), k in seen.items()],
            "host_spans": sorted({n for n, _, _ in records["host"]})}
