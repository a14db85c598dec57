"""Fused output dataflow guarantees: the planned SpMM forward and VJP
never materialize a ``(G, lanes, M, N)`` per-lane buffer (asserted on the
jaxpr), the fused layouts agree with each other and with the naive walk,
and jit vs eager is bit-identical under a prebuilt plan."""

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.csr import BlockCSR
from repro.kernels import maple_spmm, plan_spmm, plan_spmm_vjp

pytestmark = pytest.mark.tier1

G, GM, GK, BM, BK, N, LANES = 2, 4, 6, 8, 8, 16, 3
M, K = GM * BM, GK * BK


def _operands(seed=0, gm=GM, gk=GK):
    rng = np.random.default_rng(seed)
    mask = rng.random((gm, gk)) < 0.5
    mask[0] = True                                # one heavy (split) row
    d = rng.standard_normal((gm * BM, gk * BK)).astype(np.float32)
    d *= np.repeat(np.repeat(mask, BM, 0), BK, 1)
    a = BlockCSR.from_dense(d, (BM, BK), n_blocks_max=int(mask.sum()) + 2)
    b3 = jnp.asarray(
        rng.standard_normal((G, gk * BK, N)).astype(np.float32))
    return d, a, b3


# --------------------------------------------------------------------------
# jaxpr inspection: the lane buffer is dead
# --------------------------------------------------------------------------

def _iter_jaxprs(x):
    if isinstance(x, jax.extend.core.ClosedJaxpr):
        yield x.jaxpr
    elif isinstance(x, jax.extend.core.Jaxpr):
        yield x
    elif isinstance(x, (list, tuple)):
        for item in x:
            yield from _iter_jaxprs(item)


def _all_shapes(jaxpr, out):
    """Every intermediate ShapedArray in the jaxpr, recursing into
    call/closed sub-jaxprs (pjit, custom_vjp, scan, cond, ...)."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            shape = getattr(getattr(v, "aval", None), "shape", None)
            if shape is not None:
                out.add(tuple(int(s) for s in shape))
        for param in eqn.params.values():
            for sub in _iter_jaxprs(param):
                _all_shapes(sub, out)
    return out


def test_planned_spmm_never_materializes_lane_buffer():
    _, a, b3 = _operands()
    tp = plan_spmm_vjp(a, n_lanes=LANES, chunk=2)

    def fwd(blocks, bb):
        aa = BlockCSR(blocks, a.block_col, a.block_row, a.row_ptr,
                      a.shape, a.block_shape)
        return maple_spmm(aa, bb, bn=N, plan=tp)

    shapes = _all_shapes(jax.make_jaxpr(fwd)(a.blocks, b3).jaxpr, set())
    assert (G, M, N) in shapes, "sanity: the merged output must appear"
    assert (G, LANES, M, N) not in shapes, \
        "forward materialized the retired (G, lanes, M, N) lane buffer"

    grad = jax.grad(lambda blk, bb: jnp.sum(fwd(blk, bb) ** 2),
                    argnums=(0, 1))
    shapes = _all_shapes(jax.make_jaxpr(grad)(a.blocks, b3).jaxpr, set())
    assert (G, K, N) in shapes, "sanity: dB must appear"
    assert (G, LANES, M, N) not in shapes
    assert (G, LANES, K, N) not in shapes, \
        "dB backward materialized a (G, lanes, K, N) lane buffer"


def test_compact_flush_buffer_is_plan_sized():
    """The compact layout's only intermediate is the written-map-sized
    tile stack — strictly smaller than the retired full lane buffer."""
    _, a, b3 = _operands(seed=3, gm=8)
    plan = plan_spmm(a, n_lanes=LANES, chunk=2, fused="compact")
    assert plan.r_max < plan.n_block_rows, "pattern must not degenerate"

    def fwd(blocks, bb):
        aa = BlockCSR(blocks, a.block_col, a.block_row, a.row_ptr,
                      a.shape, a.block_shape)
        return maple_spmm(aa, bb, bn=N, plan=plan)

    m8 = 8 * BM
    shapes = _all_shapes(jax.make_jaxpr(fwd)(a.blocks, b3).jaxpr, set())
    assert (G, LANES, plan.r_max * BM, N) in shapes, \
        "sanity: the compact flush tiles must appear"
    assert (G, LANES, m8, N) not in shapes


# --------------------------------------------------------------------------
# schedule equivalence against the fused path, bit-level jit/no-jit
# --------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("fused", ["rmw", "compact"])
@pytest.mark.parametrize("row_atomic", [False, True])
def test_fused_jit_nojit_bit_identical(fused, row_atomic):
    """Same prebuilt plan, jit vs eager: bit-identical outputs and
    gradients (identical program, identical f32 merge order)."""
    _, a, b3 = _operands(seed=7)
    tp = plan_spmm_vjp(a, n_lanes=LANES, chunk=None if row_atomic else 2,
                       row_atomic=row_atomic, fused=fused)

    def fwd(blocks, bb):
        aa = BlockCSR(blocks, a.block_col, a.block_row, a.row_ptr,
                      a.shape, a.block_shape)
        return maple_spmm(aa, bb, bn=N, plan=tp)

    loss = lambda blk, bb: jnp.sum(fwd(blk, bb) ** 2)
    eager = (fwd(a.blocks, b3), *jax.grad(loss, argnums=(0, 1))(a.blocks, b3))
    jitted = (jax.jit(fwd)(a.blocks, b3),
              *jax.jit(jax.grad(loss, argnums=(0, 1)))(a.blocks, b3))
    for e, j in zip(eager, jitted):
        np.testing.assert_array_equal(np.asarray(e), np.asarray(j))


@pytest.mark.parametrize("schedule", ["balanced", "row_atomic"])
def test_fused_layouts_match_each_other_and_naive(schedule):
    """rmw and compact merge the same f32 chunk partials — they must agree
    with each other and with the naive single-stream walk to f32-merge
    tolerance, on every schedule."""
    d, a, b3 = _operands(seed=11)
    naive = np.asarray(maple_spmm(a, b3, bn=N, schedule="naive"))
    outs = {}
    for fused in ("rmw", "compact"):
        # row_atomic forbids an explicit chunk (it would be silently
        # ignored — plan_spmm raises on the combination)
        row_atomic = schedule == "row_atomic"
        plan = plan_spmm(a, n_lanes=LANES,
                         chunk=None if row_atomic else 2,
                         row_atomic=row_atomic, fused=fused)
        outs[fused] = np.asarray(maple_spmm(a, b3, bn=N, plan=plan))
        np.testing.assert_allclose(outs[fused], naive, rtol=1e-5, atol=1e-5)
        expect = np.einsum("mk,gkn->gmn", d, np.asarray(b3))
        np.testing.assert_allclose(outs[fused], expect, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(outs["rmw"], outs["compact"],
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("kind", ["empty_rows", "all_zero", "one_row"])
@pytest.mark.parametrize("fused", ["rmw", "compact"])
def test_fused_edge_patterns(kind, fused):
    """Degenerate patterns: never-flushed rows stay exactly zero in both
    fused layouts (rmw: cached row_mask; compact: scatter-add zeros)."""
    rng = np.random.default_rng(13)
    mask = np.zeros((GM, GK), bool)
    if kind == "empty_rows":
        mask[1] = rng.random(GK) < 0.6
        mask[3, 0] = True
    elif kind == "one_row":
        mask[2] = True
    d = rng.standard_normal((M, K)).astype(np.float32)
    d *= np.repeat(np.repeat(mask, BM, 0), BK, 1)
    a = BlockCSR.from_dense(d, (BM, BK))
    b = jnp.asarray(rng.standard_normal((K, N)).astype(np.float32))
    plan = plan_spmm(a, n_lanes=LANES, chunk=2, fused=fused)
    out = np.asarray(maple_spmm(a, b, bn=N, plan=plan))
    np.testing.assert_allclose(out, d @ np.asarray(b), rtol=1e-4, atol=1e-4)
    empty = ~np.repeat(mask.any(axis=1), BM)
    np.testing.assert_array_equal(out[empty], 0.0)


def test_rmw_requires_interpret_and_compiled_calls_take_compact():
    """The rmw accumulating flush depends on the interpreter re-fetching
    revisited output tiles: the raw kernel refuses to lower compiled, and
    the wrapper dispatches compiled calls to the compact layout even when
    the plan prefers rmw (both layouts' metadata ride every plan, so the
    preference is a per-call choice, not a trap)."""
    from repro.kernels.maple_spmm import maple_spmm_planned_pallas
    _, a, b3 = _operands(seed=19)
    plan = plan_spmm(a, n_lanes=LANES, chunk=2, fused="rmw")
    with pytest.raises(NotImplementedError, match="interpret"):
        maple_spmm_planned_pallas(
            a.blocks, jnp.asarray(plan.order), jnp.asarray(plan.step_row),
            jnp.asarray(plan.step_col), jnp.asarray(plan.step_acc),
            b3, m=M, bn=N, interpret=False)
    assert plan_spmm(a, n_lanes=LANES).fused == "compact"   # auto
    # trace (not execute) a compiled call: the rmw-preferring plan must
    # route through the compact flush tiles, never the rmw kernel raise
    def compiled(blocks, bb):
        aa = BlockCSR(blocks, a.block_col, a.block_row, a.row_ptr,
                      a.shape, a.block_shape)
        return maple_spmm(aa, bb, bn=N, plan=plan, interpret=False)
    shapes = _all_shapes(jax.make_jaxpr(compiled)(a.blocks, b3).jaxpr, set())
    assert (G, LANES, plan.r_max * BM, N) in shapes
    assert (G, LANES, M, N) not in shapes


def test_plan_fused_metadata_invariants():
    """step_acc marks exactly one initializing flush per written row, the
    compact slot map inverts written, and the cached row_mask is the
    element-level any-writer mask."""
    _, a, _ = _operands(seed=17)
    for fused in ("rmw", "compact"):
        plan = plan_spmm(a, n_lanes=LANES, chunk=2, fused=fused)
        live = plan.step_col >= 0
        for r in range(plan.n_block_rows):
            writers = np.nonzero(plan.written[:, r])[0]
            if writers.size == 0:
                continue
            # the row's designated initializer is its first lane in grid
            # traversal order; every other lane's steps accumulate
            init_lanes = set()
            for l in range(plan.n_lanes):
                steps_lr = live[l] & (plan.step_row[l] == r)
                if steps_lr.any() and (plan.step_acc[l][steps_lr] == 0).all():
                    init_lanes.add(l)
            assert init_lanes == {int(writers.min())}
        for l in range(plan.n_lanes):
            rows_l = np.nonzero(plan.written[l])[0]
            assert plan.slot_row[l, :rows_l.size].tolist() == rows_l.tolist()
            assert (plan.slot_row[l, rows_l.size:] == -1).all()
        assert plan.r_max == max(int(plan.written.sum(axis=1).max()), 1)
        np.testing.assert_array_equal(
            plan.row_mask, np.repeat(plan.written.any(axis=0), BM))
        # traffic model: fused output footprints undercut the retired
        # lane-buffer epilogue, which is priced only under its explicit
        # legacy name — the old spelling raises so a stale comparison
        # cannot silently treat the dead mode as live
        for mode in ("rmw", "compact"):
            assert plan.output_traffic_bytes(G, N, mode=mode) < \
                plan.output_traffic_bytes(G, N, mode="legacy_epilogue")
        with pytest.raises(ValueError, match="legacy_epilogue"):
            plan.output_traffic_bytes(G, N, mode="epilogue")


@pytest.mark.parametrize("fused", ["rmw", "compact"])
def test_multi_jtile_output_grid(fused):
    """bn < N (two output-column tiles): the per-(g, j) PSB re-zeroing
    and the rmw step_acc protocol across j-tile revisits are exercised —
    everything else in this file runs bn == N, where the j axis is 1."""
    d, a, _ = _operands(seed=41)
    rng = np.random.default_rng(42)
    b3 = jnp.asarray(rng.standard_normal((G, K, 2 * N)).astype(np.float32))
    plan = plan_spmm(a, n_lanes=LANES, chunk=2, fused=fused)
    out = np.asarray(maple_spmm(a, b3, bn=N, plan=plan))   # n//bn == 2
    expect = np.einsum("mk,gkn->gmn", d, np.asarray(b3))
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)
    # fwd + grad, jit and eager, stay bit-identical across the j grid
    tp = plan_spmm_vjp(a, n_lanes=LANES, chunk=2, fused=fused)

    def loss(blocks, bb):
        aa = BlockCSR(blocks, a.block_col, a.block_row, a.row_ptr,
                      a.shape, a.block_shape)
        return jnp.sum(maple_spmm(aa, bb, bn=N, plan=tp) ** 2)

    g_eager = jax.grad(loss, argnums=(0, 1))(a.blocks, b3)
    g_jit = jax.jit(jax.grad(loss, argnums=(0, 1)))(a.blocks, b3)
    for ge, gj in zip(g_eager, g_jit):
        assert np.array_equal(np.asarray(ge), np.asarray(gj))
