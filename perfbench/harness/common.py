"""Small shared pieces: seeds, the device check, the compile counter,
percentiles and the result line."""

from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path
from typing import Dict, Iterable, List, Optional

ROOT = Path(__file__).resolve().parents[2]      # the checkout
BENCH_DIR = ROOT / "perfbench"
T_START = time.perf_counter()                   # first import of the harness
_MARKS: List = []                               # (set-up stage, its end)


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def seed_key(seed: int):
    """A JAX key from any whole number (seeds may pass 32 bits)."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def require_devices(chips: int):
    """The devices the cell runs on; raises :class:`NoDevice` on a CPU or
    when fewer chips than ``chips`` are attached.  There is no fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoDevice("JAX found no accelerator (platform 'cpu')")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devices)}")
    return devices[:chips]


def device_info(devices) -> Dict:
    """Platform, kind, count and the peak bytes on the fullest chip."""
    peaks = []
    for d in devices:
        try:
            peaks.append(int(d.memory_stats().get("peak_bytes_in_use", 0)))
        except Exception:             # a backend without memory stats
            peaks.append(0)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


class CompileCounter:
    """Counts JAX traces and backend compiles, so that a window can show
    that nothing was compiled inside it."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax
        self.counts = {e: 0 for e in self.EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.counts:
            self.counts[event] += 1

    def total(self) -> int:
        return sum(self.counts.values())


def nearest_rank(values: Iterable[float], q: float) -> Optional[float]:
    """The q-th percentile by nearest rank (``inf`` counts as a miss)."""
    vals = sorted(values)
    if not vals:
        return None
    k = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[k - 1]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def print_result(*, correct: bool, attempted: int, failed: int,
                 metrics: Dict[str, Dict], device: Dict,
                 checks: Dict[str, Dict], breakdown: Optional[Dict] = None
                 ) -> None:
    """The checks as the last lines on stderr, then the result as the last
    line on stdout with the checks under the last key."""
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r} "
            f"({'ok' if c['value'] <= c['limit'] else 'FAIL'})")
    line: Dict = {"correct": bool(correct), "attempted": int(attempted),
                  "failed": int(failed), "metrics": metrics,
                  "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


def checks_pass(checks: Dict[str, Dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def elapsed_since_start() -> float:
    return time.perf_counter() - T_START


def mark(stage: str) -> None:
    """Notes the end of a set-up stage, for the run's record on stderr."""
    _MARKS.append((stage, elapsed_since_start()))


def setup_stages() -> str:
    """Seconds each marked set-up stage took, in order."""
    prev, parts = 0.0, []
    for stage, t in _MARKS:
        parts.append(f"{stage} {t - prev:.3f}")
        prev = t
    return ", ".join(parts)


def bytes_in_use(devices) -> int:
    """Bytes held now on the fullest chip (0 where the backend has no
    memory statistics)."""
    held = [0]
    for d in devices:
        try:
            held.append(int(d.memory_stats().get("bytes_in_use", 0)))
        except Exception:             # a backend without memory stats
            pass
    return max(held)


def fmt_list(xs: List[float]) -> str:
    return "[" + ", ".join(f"{x:.6g}" for x in xs) + "]"
