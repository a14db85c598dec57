"""Two-phase SpGEMM tests: sorted-CSR utilities, symbolic-phase pattern
goldens, shared ExecutionPlan-layer invariants, and properties checking the
sparse-output kernel against ``core.gustavson`` and the dense oracle."""

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.csr import (CSR, ell_slots, grow_nnz_max, merge_by_column,
                            spgemm_row_upper_bounds)
from repro.core.gustavson import dense_oracle, spmspm_rowwise
from repro.core.maple import analyze_spgemm
from repro.kernels import (ExecutionPlan, csr_to_ell, maple_spgemm,
                           plan_spgemm, plan_spmm)

pytestmark = pytest.mark.tier1


def _rand_csr(rng, m, n, density, pad=0):
    d = ((rng.random((m, n)) < density) * rng.standard_normal((m, n))
         ).astype(np.float32)
    return d, CSR.from_dense(d, nnz_max=max(int((d != 0).sum()), 1) + pad)


# --------------------------------------------------------------------------
# sorted-CSR utilities (core.csr)
# --------------------------------------------------------------------------

def test_merge_by_column_golden():
    cols = [3, 1, 3, -1, 0, 1]
    vals = np.asarray([1.0, 2.0, 4.0, 9.0, 8.0, 0.5], np.float32)
    uc, acc = merge_by_column(cols, vals)
    assert uc.tolist() == [0, 1, 3]          # sorted, pads dropped
    np.testing.assert_allclose(acc, [8.0, 2.5, 5.0])
    uc2, none = merge_by_column(cols)
    assert uc2.tolist() == [0, 1, 3] and none is None


def test_grow_nnz_max_policy():
    assert grow_nnz_max(0) == 8
    assert grow_nnz_max(9) == 16
    assert grow_nnz_max(129) == 256
    assert grow_nnz_max(5, current=64) == 64       # monotone from current
    assert grow_nnz_max(100, current=64) == 128
    with pytest.raises(ValueError):
        grow_nnz_max(-1)
    # geometric quantization: few distinct capacities over a wide nnz range
    assert len({grow_nnz_max(i) for i in range(1, 1000)}) == 8


def test_spgemm_row_upper_bounds():
    rng = np.random.default_rng(0)
    ad, a = _rand_csr(rng, 10, 8, 0.4)
    bd, b = _rand_csr(rng, 8, 12, 0.3)
    ub = spgemm_row_upper_bounds(a, b)
    exact = (((ad != 0).astype(int) @ (bd != 0).astype(int)) > 0).sum(axis=1)
    assert (ub >= exact).all()
    assert (ub <= b.shape[1]).all()


def test_ell_slots_map():
    rptr = np.asarray([0, 2, 2, 5])
    idx, live = ell_slots(rptr)
    assert idx.shape == (3, 3)
    assert live.tolist() == [[True, True, False], [False] * 3, [True] * 3]
    assert idx[0, :2].tolist() == [0, 1] and idx[2].tolist() == [2, 3, 4]
    with pytest.raises(ValueError, match="longest row"):
        ell_slots(rptr, width=2)


def test_csr_to_ell_truncation_guard():
    """Regression: narrow max_row_len used to silently drop row tails."""
    a = CSR.from_dense(np.array([[1, 2, 3], [4, 0, 0]], np.float32))
    with pytest.raises(ValueError, match="truncate"):
        csr_to_ell(a, max_row_len=2)
    v, c = csr_to_ell(a, max_row_len=2, truncate=True)   # explicit opt-in
    assert v.shape == (2, 2) and np.asarray(v)[0].tolist() == [1, 2]
    v3, _ = csr_to_ell(a, max_row_len=3)                 # wide enough: fine
    assert np.asarray(v3)[0].tolist() == [1, 2, 3]


# --------------------------------------------------------------------------
# symbolic phase (plan_spgemm pattern + scatter)
# --------------------------------------------------------------------------

def test_symbolic_pattern_golden():
    # the hand-counted pair from test_schedule: C row0=[7,1,8], row2=[0,6,0]
    a = CSR.from_dense(np.array([[1, 0, 2], [0, 0, 0], [0, 3, 0]],
                                np.float32))
    b = CSR.from_dense(np.array([[1, 1, 0], [0, 2, 0], [3, 0, 4]],
                                np.float32))
    plan = plan_spgemm(a, b, n_lanes=2)
    assert plan.out_row_ptr.tolist() == [0, 3, 3, 4]
    assert plan.out_cols.tolist() == [0, 1, 2, 1]
    assert plan.nnz_c == 4 and plan.lc == 3
    assert plan.stats.partial_products == 5
    # every partial product got exactly one scatter position
    assert int((plan.scatter_pos >= 0).sum()) == 5


@pytest.mark.parametrize("balance", ["work", "fibers", "none"])
def test_spgemm_plan_invariants(balance):
    rng = np.random.default_rng(7)
    ad, _ = _rand_csr(rng, 9, 8, 0.4)
    ad[1::3] = 0.0                                    # empty rows
    a = CSR.from_dense(ad, nnz_max=max(int((ad != 0).sum()), 1) + 2)
    _, b = _rand_csr(rng, 8, 10, 0.3)
    plan = plan_spgemm(a, b, n_lanes=3, balance=balance)
    assert isinstance(plan, ExecutionPlan)

    live = plan.step_col >= 0
    a_len = np.diff(np.asarray(a.row_ptr))
    # every live A slot scheduled exactly once, as its flat ELL id
    expect = sorted(i * plan.la + t for i in range(a.shape[0])
                    for t in range(int(a_len[i])))
    assert sorted(plan.order[live].tolist()) == expect
    assert plan.n_real_steps == int(a_len.sum())
    for l in range(plan.n_lanes):
        rows = plan.step_row[l][live[l]]
        assert (np.diff(rows) >= 0).all()             # contiguous PSB runs
        assert set(rows.tolist()) == set(np.nonzero(plan.written[l])[0])
    # rows atomic: each output row owned by at most one lane
    assert (plan.written.sum(axis=0) <= 1).all()
    # pad steps target the sacrificial row only
    assert (plan.step_row[~live] == a.shape[0]).all()
    pc = plan.predicted_cycles()
    assert set(pc) == {"plan", "maple", "row_atomic"}
    assert pc["plan"] == plan.lane_work.max(initial=0)
    assert 0.0 <= plan.utilization <= 1.0


def test_work_balanced_beats_fiber_proxy():
    """The tentpole's scheduling claim: LPT by Σ nnz(B[k',:]) levels lanes
    that the nnz(A) proxy leaves skewed (work hides behind fiber counts)."""
    # A-row (fibers, work): r0 (1, 4), r1 (4, 3), r2 (4, 3), r3 (1, 2)
    bd = np.zeros((10, 8), np.float32)
    bd[0, :4] = 1.0                                   # heavy B row: 4 nnz
    for r in (1, 2, 3, 5, 6, 7):
        bd[r, r % 8] = 1.0                            # singleton rows
    bd[9, :2] = 1.0                                   # 2-nnz row
    ad = np.zeros((4, 10), np.float32)
    ad[0, 0] = 1.0
    ad[1, 1:5] = 1.0
    ad[2, 5:9] = 1.0
    ad[3, 9] = 1.0
    a, b = CSR.from_dense(ad), CSR.from_dense(bd)
    bal = plan_spgemm(a, b, n_lanes=2, balance="work")
    fib = plan_spgemm(a, b, n_lanes=2, balance="fibers")
    assert int(bal.lane_work.max()) == 6              # {4,2} | {3,3}
    assert int(fib.lane_work.max()) == 7              # fiber ties misplace r0
    assert bal.predicted_cycles()["plan"] < fib.predicted_cycles()["plan"]
    # both still compute the same C
    for plan in (bal, fib):
        c = maple_spgemm(a, b, plan=plan)
        np.testing.assert_allclose(np.asarray(c.to_dense()), ad @ bd,
                                   rtol=1e-5, atol=1e-5)


def test_shared_plan_layer():
    """SpmmPlan and SpgemmPlan are the same ExecutionPlan abstraction."""
    from repro.core.csr import BlockCSR
    rng = np.random.default_rng(3)
    d = rng.standard_normal((16, 16)).astype(np.float32)
    d[8:] = 0.0
    bsr_plan = plan_spmm(BlockCSR.from_dense(d, (8, 8)), n_lanes=2)
    _, a = _rand_csr(rng, 8, 8, 0.4)
    spg_plan = plan_spgemm(a, a, n_lanes=2)
    for plan in (bsr_plan, spg_plan):
        assert isinstance(plan, ExecutionPlan)
        assert set(plan.predicted_cycles()) == {"plan", "maple",
                                                "row_atomic"}
        assert 0.0 <= plan.utilization <= 1.0
    assert bsr_plan.n_block_rows == bsr_plan.n_rows   # legacy alias


# --------------------------------------------------------------------------
# numeric phase: sparse-output kernel vs the oracles
# --------------------------------------------------------------------------

def _check_padded_csr_contract(c: CSR):
    nnz = int(np.asarray(c.row_ptr)[-1])
    cols = np.asarray(c.col_id)
    rptr = np.asarray(c.row_ptr)
    assert (cols[nnz:] == -1).all() and (cols[:nnz] >= 0).all()
    assert (np.asarray(c.value)[nnz:] == 0).all()
    for i in range(c.shape[0]):                       # sorted, unique cols
        seg = cols[rptr[i]:rptr[i + 1]]
        if seg.size > 1:
            assert (np.diff(seg) > 0).all()


@pytest.mark.parametrize("schedule", ["balanced", "row_atomic", "naive"])
def test_spgemm_matches_oracles(schedule):
    rng = np.random.default_rng(11)
    ad, a = _rand_csr(rng, 14, 10, 0.35, pad=3)
    bd, b = _rand_csr(rng, 10, 12, 0.3, pad=2)
    c = maple_spgemm(a, b, schedule=schedule, n_lanes=3)
    assert isinstance(c, CSR) and c.shape == (14, 12)
    got = np.asarray(c.to_dense())
    np.testing.assert_allclose(got, np.asarray(dense_oracle(a, b)),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got, np.asarray(spmspm_rowwise(a, b)),
                               rtol=1e-4, atol=1e-4)
    _check_padded_csr_contract(c)


def test_spgemm_nnz_at_capacity():
    rng = np.random.default_rng(13)
    ad, a = _rand_csr(rng, 10, 10, 0.4)
    plan = plan_spgemm(a, a, n_lanes=2)
    assert plan.nnz_c > 1
    c = maple_spgemm(a, a, nnz_max=plan.nnz_c)        # exactly at capacity
    assert c.nnz_max == plan.nnz_c
    np.testing.assert_allclose(np.asarray(c.to_dense()), ad @ ad,
                               rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError, match="nnz_max"):
        maple_spgemm(a, a, nnz_max=plan.nnz_c - 1)


def test_spgemm_degenerate_patterns():
    rng = np.random.default_rng(17)
    zd = np.zeros((6, 5), np.float32)
    z = CSR.from_dense(zd)
    _, b = _rand_csr(rng, 5, 7, 0.5)
    for lhs, rhs, mm, nn in [(z, b, 6, 7), (b, CSR.from_dense(
            np.zeros((7, 4), np.float32)), 5, 4)]:
        c = maple_spgemm(lhs, rhs)
        assert int(np.asarray(c.row_ptr)[-1]) == 0
        assert (np.asarray(c.col_id) == -1).all()
        np.testing.assert_array_equal(np.asarray(c.to_dense()),
                                      np.zeros((mm, nn), np.float32))


def test_spgemm_zero_dimension_operands():
    """Regression: zero-dim shapes used to hit the kernel's >=1-row panels
    with 0-row operands and crash inside the Pallas fetch."""
    rng = np.random.default_rng(31)
    _, b = _rand_csr(rng, 5, 4, 0.5)
    cases = [
        (CSR.from_dense(np.zeros((4, 0), np.float32)),
         CSR.from_dense(np.zeros((0, 5), np.float32)), (4, 5)),
        (CSR.from_dense(np.zeros((0, 5), np.float32)), b, (0, 4)),
        (b, CSR.from_dense(np.zeros((4, 0), np.float32)), (5, 0)),
    ]
    for lhs, rhs, shape in cases:
        c = maple_spgemm(lhs, rhs)
        assert c.shape == shape
        assert int(np.asarray(c.row_ptr)[-1]) == 0
        assert (np.asarray(c.col_id) == -1).all()


def test_spgemm_plan_row_upper_bound():
    """The plan records the O(nnz_a) pre-bound and it dominates the exact
    per-row output sizes."""
    rng = np.random.default_rng(37)
    _, a = _rand_csr(rng, 9, 7, 0.4)
    _, b = _rand_csr(rng, 7, 8, 0.4)
    plan = plan_spgemm(a, b)
    np.testing.assert_array_equal(plan.row_upper,
                                  spgemm_row_upper_bounds(a, b))
    assert (plan.row_upper >= np.diff(plan.out_row_ptr)).all()


@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(m=st.integers(1, 12), k=st.integers(1, 10), n=st.integers(1, 12),
       da=st.floats(0.0, 0.5), db=st.floats(0.0, 0.5),
       seed=st.integers(0, 2**16))
def test_spgemm_property(m, k, n, da, db, seed):
    """Output equals both oracles and the exact symbolic nnz across random
    sparsities (boundary draws cover empty and all-zero operands)."""
    rng = np.random.default_rng(seed)
    ad, a = _rand_csr(rng, m, k, da)
    bd, b = _rand_csr(rng, k, n, db)
    c = maple_spgemm(a, b, n_lanes=2)
    got = np.asarray(c.to_dense())
    np.testing.assert_allclose(got, ad @ bd, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got, np.asarray(dense_oracle(a, b)),
                               rtol=1e-4, atol=1e-4)
    assert int(np.asarray(c.row_ptr)[-1]) == analyze_spgemm(a, b).nnz_c
    _check_padded_csr_contract(c)


# --------------------------------------------------------------------------
# dispatch, jit composition, validation
# --------------------------------------------------------------------------

def test_spgemm_jit_with_prebuilt_plan():
    rng = np.random.default_rng(19)
    ad, a = _rand_csr(rng, 8, 8, 0.4)
    plan = plan_spgemm(a, a, n_lanes=2)
    f = jax.jit(lambda aa: maple_spgemm(aa, aa, plan=plan).to_dense())
    np.testing.assert_allclose(np.asarray(f(a)), ad @ ad,
                               rtol=1e-4, atol=1e-4)
    # same pattern, new values: the jitted call reuses the closed-over plan
    a2 = CSR(value=a.value * 2, col_id=a.col_id, row_ptr=a.row_ptr,
             shape=a.shape)
    np.testing.assert_allclose(np.asarray(f(a2)), 4 * (ad @ ad),
                               rtol=1e-4, atol=1e-4)
    # without a plan the symbolic phase cannot read traced metadata
    with pytest.raises(ValueError, match="symbolic"):
        jax.jit(lambda aa: maple_spgemm(aa, aa).to_dense())(a)


def test_spgemm_validation():
    rng = np.random.default_rng(23)
    _, a = _rand_csr(rng, 6, 5, 0.4)
    _, b = _rand_csr(rng, 5, 6, 0.4)
    with pytest.raises(ValueError, match="contraction"):
        maple_spgemm(a, CSR.from_dense(np.zeros((7, 3), np.float32)))
    with pytest.raises(ValueError, match="unknown schedule"):
        maple_spgemm(a, b, schedule="fastest")
    with pytest.raises(TypeError, match="CSR"):
        maple_spgemm(a, np.zeros((5, 6), np.float32))
    with pytest.raises(ValueError, match="plan is for"):
        maple_spgemm(a, b, plan=plan_spgemm(b, a))
    # same shapes, thinner operand: plan gathers past its capacity
    dense_d = (np.ones((6, 5)) * rng.standard_normal((6, 5))).astype(
        np.float32)
    thin_d = np.zeros((6, 5), np.float32)
    thin_d[np.arange(5), np.arange(5)] = 1.0
    plan_dense = plan_spgemm(CSR.from_dense(dense_d), b)
    with pytest.raises(ValueError, match="capacity"):
        maple_spgemm(CSR.from_dense(thin_d), b, plan=plan_dense)
    with pytest.raises(ValueError, match="balance"):
        plan_spgemm(a, b, balance="speed")
    with pytest.raises(ValueError, match="n_lanes"):
        plan_spgemm(a, b, n_lanes=0)


def test_spmspm_routes_through_spgemm(monkeypatch):
    """Satellite: CSR b goes through the sparse-output kernel; dense b
    keeps the legacy positional-PSB path."""
    from repro.kernels import ops
    rng = np.random.default_rng(29)
    ad, a = _rand_csr(rng, 8, 6, 0.4)
    bd, b = _rand_csr(rng, 6, 9, 0.3)
    calls = []
    orig = ops.maple_spgemm
    monkeypatch.setattr(ops, "maple_spgemm",
                        lambda *ar, **kw: calls.append(1) or orig(*ar, **kw))
    out = np.asarray(ops.maple_spmspm(a, b))
    assert calls, "CSR b should route through maple_spgemm"
    np.testing.assert_allclose(out, ad @ bd, rtol=1e-4, atol=1e-4)
    calls.clear()
    import jax.numpy as jnp
    out2 = np.asarray(ops.maple_spmspm(a, jnp.asarray(bd)))
    assert not calls, "dense b stays on the legacy kernel"
    np.testing.assert_allclose(out2, ad @ bd, rtol=1e-4, atol=1e-4)
