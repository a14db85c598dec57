"""Work counted from shapes and the head's block pattern alone, so the
yardstick reads the same whatever kernel does the work: padding steps and
flush tiles never count."""

from __future__ import annotations

from typing import Dict

import numpy as np


def spmm_vjp_work(n_live_blocks: int, block: int, tokens: int,
                  rows: int, cols: int, elem_bytes: int = 2) -> Dict:
    """Useful FLOPs and needed bytes of one forward + VJP of
    ``logits = h @ W^T`` for a block-sparse ``W`` of ``(rows, cols)``
    with ``n_live_blocks`` live ``block x block`` blocks, over ``tokens``
    hidden rows, with a given cotangent.

    FLOPs: ``2 * block**2 * tokens`` per live block for each of the
    forward, ``dh = dC @ W`` and ``dA = (dC^T h)|pattern``.
    Bytes: each operand read or written once — the blocks (read), ``dA``
    (written), ``h`` (read), the logits (written), the cotangent (read),
    ``dh`` (written).
    """
    per_pass = 2 * block * block * tokens * n_live_blocks
    block_bytes = n_live_blocks * block * block * elem_bytes
    dense = (tokens * cols * 2 + tokens * rows * 2) * elem_bytes
    return {"flops": 3 * per_pass, "bytes": 2 * block_bytes + dense}


def roofline_seconds(work: Dict, peak: Dict) -> Dict:
    """The least time the chip could take, and which bound sets it."""
    t_flops = work["flops"] / peak["bf16_flop_per_s"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}


def plan_utilization(train_plan) -> float:
    """Useful block steps over scheduled lane steps of a train plan's
    forward and transpose-side schedules, in percent."""
    useful = sched = 0
    for p in (train_plan.fwd, train_plan.bwd):
        useful += int(p.n_real_steps)
        sched += int(p.n_lanes) * int(p.steps)
    return 100.0 * useful / max(sched, 1)


def head_mask(pattern_seed: int, rows: int, cols: int, block: int,
              density: float) -> np.ndarray:
    """The head's block mask: drawn from the configuration's
    ``pattern_seed`` alone, every block-row kept (one forced block)."""
    gm, gk = rows // block, cols // block
    rng = np.random.default_rng(int(pattern_seed))
    mask = rng.random((gm, gk)) < density
    mask[np.arange(gm), np.arange(gm) % gk] = True
    return mask
