"""The work counters, the plan count and the roofline against hand counts
on tiny patterns."""

import numpy as np
import pytest

from harness import counters


def test_spmm_vjp_work_by_hand():
    # 3 live 2x2 blocks, 5 tokens, W is (4, 6)
    w = counters.spmm_vjp_work(3, 2, 5, rows=4, cols=6, elem_bytes=2)
    assert w["flops"] == 3 * (2 * 2 * 2 * 5 * 3)          # 360
    # blocks read + dA written: 2 * 3 * 4 elements; h and dh: 2 * 5 * 6;
    # logits and cotangent: 2 * 5 * 4 — all at 2 bytes
    assert w["bytes"] == 2 * (2 * 12 + 2 * 30 + 2 * 20)


def test_roofline_picks_the_binding_side():
    peak = {"bf16_flop_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    r = counters.roofline_seconds({"flops": 300.0, "bytes": 20.0}, peak)
    assert r == {"seconds": 3.0, "bound": "compute"}
    r = counters.roofline_seconds({"flops": 100.0, "bytes": 50.0}, peak)
    assert r == {"seconds": 5.0, "bound": "memory"}


def test_head_mask_depends_on_pattern_seed_only():
    a = counters.head_mask(5, 512, 256, 64, 0.5)
    b = counters.head_mask(5, 512, 256, 64, 0.5)
    c = counters.head_mask(6, 512, 256, 64, 0.5)
    assert a.shape == (8, 4) and (a == b).all() and (a != c).any()
    assert a.any(axis=1).all()                  # every block-row kept
    assert a[np.arange(8), np.arange(8) % 4].all()


class _Plan:
    def __init__(self, real, lanes, steps):
        self.n_real_steps, self.n_lanes, self.steps = real, lanes, steps


class _Train:
    fwd = _Plan(10, 4, 3)       # 10 of 12 lane steps useful
    bwd = _Plan(10, 4, 4)       # 10 of 16


def test_plan_utilization_by_hand():
    assert counters.plan_utilization(_Train()) == pytest.approx(
        100.0 * 20 / 28)


def test_plan_utilization_on_a_real_plan():
    import jax
    from harness.sparse_head import block_csr, head_blocks
    from repro.serve import SparseLogitHead
    mask = counters.head_mask(1, 512, 256, 64, 0.5)
    spec = {"head": {"block": 64, "density": 0.5}}
    w = block_csr(mask, head_blocks(spec, mask, jax.random.PRNGKey(0)), 64)
    plan = SparseLogitHead.build(w, trainable=True).plan
    u = counters.plan_utilization(plan)
    assert 0 < u <= 100
    # every live block is one useful step on each side
    assert plan.fwd.n_real_steps == plan.bwd.n_real_steps == mask.sum()
