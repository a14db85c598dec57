"""Published peaks of one chip, keyed by JAX's ``device_kind``.  A kind
that is not in ``perfbench/peaks.json`` is an error, never a default."""

from __future__ import annotations

import json
from typing import Dict

from harness.common import BENCH_DIR


def peaks(device_kind: str) -> Dict[str, float]:
    with open(BENCH_DIR / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]
