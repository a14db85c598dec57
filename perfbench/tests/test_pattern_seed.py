"""The head's block pattern comes from the configuration's
``pattern_seed``: ``--seed`` changes values, inputs and arrivals, never the
live-block count or the plan."""

import numpy as np

import smoke
from harness import counters
from harness.sparse_head import pattern_digest


def _plan_arrays(plan):
    p = getattr(plan, "fwd", plan)
    return [np.asarray(a) for a in (p.order, p.step_row, p.step_col,
                                    p.written)]


def test_serving_head_pattern_ignores_seed():
    cell = smoke.cell("qwen3-4b.chat")
    drv = cell.runner()
    a, b = drv.Serve(cell, 1), drv.Serve(cell, 2**31 + 77)
    assert pattern_digest(a.mask) == pattern_digest(b.mask)
    assert a.mask.sum() == b.mask.sum()
    for x, y in zip(_plan_arrays(a.head.plan), _plan_arrays(b.head.plan)):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(np.asarray(a.head_w.blocks, np.float32),
                              np.asarray(b.head_w.blocks, np.float32))


def test_train_plan_ignores_seed():
    cell = smoke.cell("qwen3-4b.head-distill")
    drv = cell.runner()
    a, b = drv.HeadVJP(cell, 5), drv.HeadVJP(cell, 6)
    assert counters.plan_utilization(a.plan) == \
        counters.plan_utilization(b.plan)
    for side in ("fwd", "bwd"):
        for x, y in zip(_plan_arrays(getattr(a.plan, side)),
                        _plan_arrays(getattr(b.plan, side))):
            np.testing.assert_array_equal(x, y)
    assert not np.array_equal(np.asarray(a.h, np.float32),
                              np.asarray(b.h, np.float32))


def test_pattern_seed_moves_the_pattern():
    cell = smoke.cell("qwen3-4b.chat")
    spec = cell.config
    m1 = counters.head_mask(spec["head"]["pattern_seed"], 1024, 128, 64,
                            0.5)
    m2 = counters.head_mask(spec["head"]["pattern_seed"] + 1, 1024, 128,
                            64, 0.5)
    assert pattern_digest(m1) != pattern_digest(m2)
