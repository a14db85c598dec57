"""Format layer (``core.formats``) + similarity reorder (``kernels.reorder``):
converter round trips (golden + property), pad contracts, fingerprint
stability across containers, cross-format bit-identity through
``maple_spmm``, deprecation shims, reorder permutation/bit-identity
contracts (fwd + grad) and the autotuner's reorder knob (never-worse,
occupancy-keyed cache)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import formats
from repro.core.csr import CSR, BlockCSR
from repro.core.formats import (BitmapBlocked, EllPack, SparseFormat,
                                as_block_csr, as_element_csr,
                                block_pattern_meta, from_dense, to_bitmap,
                                to_ell)
from repro.core.sparsity import block_pattern_mask
from repro.kernels import maple_spmm, plan_spmm, plan_spmm_vjp
from repro.kernels.autotune import (plan_cache_clear, plan_search,
                                    plan_search_vjp)
from repro.kernels.reorder import (RowReorder, apply_reorder,
                                   occupancy_digest, plan_reordered_spmm,
                                   reorder_rows)
from repro.kernels.schedule import pattern_fingerprint, spmm_knob_space

pytestmark = pytest.mark.tier1

GM = GK = 6
BM = BK = 4
KINDS = ("uniform", "power_law", "banded", "empty_rows")


def _dense(kind: str, seed: int = 0, *, thin: float | None = 0.6):
    """Masked dense payload for one golden pattern kind; ``thin`` keeps
    roughly that fraction of elements inside live blocks (element-level
    zeros are what the format pad contracts and the reorder refinement
    must survive)."""
    rng = np.random.default_rng(seed)
    if kind == "empty_rows":
        mask = block_pattern_mask("uniform", rng, GM, GK)
        mask[1] = False
        mask[4] = False
    else:
        mask = block_pattern_mask(kind, rng, GM, GK)
    d = rng.standard_normal((GM * BM, GK * BK)).astype(np.float32)
    d *= np.repeat(np.repeat(mask, BM, 0), BK, 1)
    if thin is not None:
        d *= rng.random(d.shape) < thin
    return d


def _bcsr(kind: str, seed: int = 0, **kw):
    return BlockCSR.from_dense(jnp.asarray(_dense(kind, seed, **kw)),
                               block_shape=(BM, BK))


# --------------------------------------------------------------------------
# containers + converters
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("fmt", ["bcsr", "ell", "bitmap"])
def test_from_dense_round_trip(kind, fmt):
    d = _dense(kind)
    c = from_dense(jnp.asarray(d), (BM, BK), format=fmt)
    assert isinstance(c, SparseFormat)
    c.check_pad_contract()
    np.testing.assert_array_equal(np.asarray(c.to_dense()), d)


def test_from_dense_csr_front_door():
    d = _dense("uniform")
    c = from_dense(jnp.asarray(d), format="csr")
    assert isinstance(c, CSR)
    np.testing.assert_array_equal(np.asarray(c.to_dense()), d)
    with pytest.raises(ValueError, match="block_shape"):
        from_dense(jnp.asarray(d), (BM, BK), format="csr")
    with pytest.raises(ValueError, match="format"):
        from_dense(jnp.asarray(d), (BM, BK), format="coo")


@pytest.mark.parametrize("kind", KINDS)
def test_converters_land_canonical_payload(kind):
    """Every route into BlockCSR yields the identical canonical-order
    packed payload — the invariant cross-format bit-identity rides on."""
    b = _bcsr(kind)
    for c in (to_ell(b), to_bitmap(b),
              from_dense(jnp.asarray(_dense(kind)), (BM, BK), format="ell"),
              from_dense(jnp.asarray(_dense(kind)), (BM, BK),
                         format="bitmap")):
        r = as_block_csr(c)
        nnzb = int(np.asarray(b.row_ptr)[-1])
        np.testing.assert_array_equal(np.asarray(r.blocks)[:nnzb],
                                      np.asarray(b.blocks)[:nnzb])
        np.testing.assert_array_equal(np.asarray(r.block_col)[:nnzb],
                                      np.asarray(b.block_col)[:nnzb])
        np.testing.assert_array_equal(np.asarray(r.row_ptr),
                                      np.asarray(b.row_ptr))


def test_bitmap_round_trip_zero_copy():
    b = _bcsr("uniform")
    bmp = to_bitmap(b)
    # canonical BlockCSR at exact capacity -> payload passes through
    assert bmp.blocks is b.blocks
    assert as_block_csr(bmp).blocks is bmp.blocks


def test_ell_width_too_small_raises():
    d = _dense("uniform")
    with pytest.raises(ValueError, match="width"):
        EllPack.from_dense(jnp.asarray(d), (BM, BK), width=1)


@pytest.mark.parametrize("fmt", ["ell", "bitmap"])
def test_pad_contract_catches_corruption(fmt):
    c = from_dense(jnp.asarray(_dense("uniform")), (BM, BK), format=fmt)
    c.check_pad_contract()
    if fmt == "ell":
        bad = np.asarray(c.block_col).copy()
        bad[bad >= 0] = np.sort(bad[bad >= 0])[::-1][:int((bad >= 0).sum())] \
            if (bad >= 0).sum() > 1 else bad[bad >= 0]
        # dead slot with non--1 marker
        dead = np.argwhere(np.asarray(c.block_col) < 0)
        if dead.size:
            bad = np.asarray(c.block_col).copy()
            bad[tuple(dead[0])] = -7
            broken = EllPack(blocks=c.blocks, block_col=jnp.asarray(bad),
                             shape=c.shape, block_shape=c.block_shape)
            with pytest.raises(ValueError):
                broken.check_pad_contract()
    else:
        # payload behind a dead bitmap slot must be zero
        blocks = np.asarray(c.blocks).copy()
        nnzb = int(np.asarray(c.bitmap).sum())
        if blocks.shape[0] > nnzb:
            blocks[-1] += 1.0
            broken = BitmapBlocked(blocks=jnp.asarray(blocks),
                                   bitmap=c.bitmap, shape=c.shape,
                                   block_shape=c.block_shape)
            with pytest.raises(ValueError):
                broken.check_pad_contract()


@pytest.mark.parametrize("kind", KINDS)
def test_as_element_csr(kind):
    b = _bcsr(kind)
    e = as_element_csr(b)
    e.check_pad_contract()
    np.testing.assert_array_equal(np.asarray(e.to_dense()),
                                  np.asarray(b.to_dense()))
    # explicit zeros inside live blocks are kept: nnz = live block capacity
    nnzb = int(np.asarray(b.row_ptr)[-1])
    assert int(e.nnz) == nnzb * BM * BK


@given(seed=st.integers(0, 40))
@settings(max_examples=12, deadline=None)
def test_round_trip_property(seed):
    rng = np.random.default_rng(seed)
    gm, gk = int(rng.integers(1, 5)), int(rng.integers(1, 5))
    bm, bk = int(rng.integers(1, 4)), int(rng.integers(1, 4))
    d = rng.standard_normal((gm * bm, gk * bk)).astype(np.float32)
    d *= rng.random(d.shape) < 0.5
    for fmt in ("bcsr", "ell", "bitmap"):
        c = from_dense(jnp.asarray(d), (bm, bk), format=fmt)
        c.check_pad_contract()
        np.testing.assert_array_equal(np.asarray(c.to_dense()), d)
        r = as_block_csr(c)
        r.check_pad_contract()
        np.testing.assert_array_equal(np.asarray(r.to_dense()), d)


# --------------------------------------------------------------------------
# fingerprints + kernel integration
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_fingerprint_stable_across_formats(kind):
    b = _bcsr(kind)
    fp = pattern_fingerprint(b)
    assert pattern_fingerprint(to_ell(b)) == fp
    assert pattern_fingerprint(to_bitmap(b)) == fp
    meta = [block_pattern_meta(c) for c in (b, to_ell(b), to_bitmap(b))]
    for m in meta[1:]:
        assert m[0] == meta[0][0] and m[1] == meta[0][1]
        np.testing.assert_array_equal(m[2], meta[0][2])
        np.testing.assert_array_equal(m[3], meta[0][3])


@pytest.mark.parametrize("kind", KINDS)
def test_spmm_bit_identical_across_formats(kind):
    b = _bcsr(kind)
    rhs = jnp.asarray(np.random.default_rng(2).standard_normal(
        (GK * BK, 8)).astype(np.float32))
    plan = plan_spmm(b)
    ref = np.asarray(maple_spmm(b, rhs, plan=plan))
    for c in (to_ell(b), to_bitmap(b)):
        np.testing.assert_array_equal(
            np.asarray(maple_spmm(c, rhs, plan=plan)), ref)
    np.testing.assert_allclose(
        ref, np.asarray(b.to_dense()) @ np.asarray(rhs), atol=1e-4)


def test_plan_spmm_accepts_formats():
    b = _bcsr("uniform")
    for c in (to_ell(b), to_bitmap(b)):
        p = plan_spmm(c)
        np.testing.assert_array_equal(p.order, plan_spmm(b).order)


def test_deprecation_shims():
    from repro.core.csr import ell_slots as shim_slots
    from repro.kernels import csr_to_ell as shim_ctell
    from repro.kernels.ops import csr_to_ell as ops_ctell

    d = _dense("uniform")
    a = CSR.from_dense(jnp.asarray(d))
    slots, live = shim_slots(a.row_ptr)
    slots2, live2 = formats.ell_slots(a.row_ptr)
    np.testing.assert_array_equal(np.asarray(slots), np.asarray(slots2))
    np.testing.assert_array_equal(np.asarray(live), np.asarray(live2))
    for fn in (shim_ctell, ops_ctell):
        v, c = fn(a)
        v2, c2 = formats.csr_to_ell(a)
        np.testing.assert_array_equal(np.asarray(v), np.asarray(v2))
        np.testing.assert_array_equal(np.asarray(c), np.asarray(c2))


def test_spgemm_accepts_blocked_operands():
    from repro.kernels import maple_spgemm

    d = _dense("uniform")
    b = BlockCSR.from_dense(jnp.asarray(d), block_shape=(BM, BK))
    ref = np.asarray(maple_spgemm(CSR.from_dense(jnp.asarray(d)),
                                  CSR.from_dense(jnp.asarray(d))).to_dense())
    out = np.asarray(maple_spgemm(b, to_ell(b)).to_dense())
    np.testing.assert_allclose(out, ref, atol=1e-4)


# --------------------------------------------------------------------------
# reorder: permutation contracts, bit-identity, gradients
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_reorder_permutation_contracts(kind):
    b = _bcsr(kind)
    rr = reorder_rows(b)
    m = b.shape[0]
    np.testing.assert_array_equal(np.sort(rr.perm), np.arange(m))
    np.testing.assert_array_equal(rr.perm[rr.inv], np.arange(m))
    assert rr.density_after >= rr.density_before - 1e-12
    ap = apply_reorder(b, rr)
    ap.check_pad_contract()
    np.testing.assert_allclose(np.asarray(ap.to_dense()),
                               np.asarray(b.to_dense())[rr.perm])


@pytest.mark.parametrize("kind", KINDS)
def test_reorder_row_atomic_bit_identity(kind):
    """Row-atomic both sides: rows are never split, so a permuted
    execution is bit-identical to the unpermuted one (the pinned
    contract; chunked plans only reassociate and get allclose)."""
    b = _bcsr(kind)
    rhs = jnp.asarray(np.random.default_rng(3).standard_normal(
        (GK * BK, 8)).astype(np.float32))
    ref = np.asarray(maple_spmm(b, rhs, plan=plan_spmm(b, row_atomic=True)))
    out = np.asarray(maple_spmm(
        b, rhs, plan=plan_reordered_spmm(b, row_atomic=True)))
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("kind", KINDS)
def test_reorder_balanced_allclose(kind):
    b = _bcsr(kind)
    rhs = jnp.asarray(np.random.default_rng(4).standard_normal(
        (GK * BK, 8)).astype(np.float32))
    ref = np.asarray(maple_spmm(b, rhs, plan=plan_spmm(b)))
    out = np.asarray(maple_spmm(b, rhs, plan=plan_reordered_spmm(b)))
    np.testing.assert_allclose(out, ref, atol=1e-4)


def test_reorder_requires_auto_plan():
    b = _bcsr("uniform")
    rhs = jnp.zeros((GK * BK, 4), jnp.float32)
    with pytest.raises(ValueError, match="auto"):
        maple_spmm(b, rhs, plan=plan_spmm(b), reorder=True)


def test_reorder_grad_matches_on_covered_pattern():
    """Gradients through a reordered train plan equal the unreordered
    SDDMM wherever the refined pattern still covers the position, and are
    exactly zero on pruned positions (whole permuted group empty across a
    block column) — the occupancy-refinement contract."""
    b = _bcsr("uniform", thin=0.5)
    rhs = jnp.asarray(np.random.default_rng(5).standard_normal(
        (GK * BK, 8)).astype(np.float32))
    rr = reorder_rows(b)

    def loss(blocks, plan):
        a2 = BlockCSR(blocks=blocks, block_col=b.block_col,
                      block_row=b.block_row, row_ptr=b.row_ptr,
                      shape=b.shape, block_shape=b.block_shape)
        return (maple_spmm(a2, rhs, plan=plan) ** 2).sum()

    plan_cache_clear()
    tp_rr = plan_search_vjp(b, budget=64, reorder=True)
    assert getattr(tp_rr.fwd, "reorder", None) is not None
    tp = plan_spmm_vjp(b)
    g_rr = np.asarray(jax.grad(loss)(b.blocks, tp_rr))
    g = np.asarray(jax.grad(loss)(b.blocks, tp))
    nnzb_p = rr.n_blocks
    cov = np.zeros(g.shape[:2], bool)
    cov[rr.src_block[:nnzb_p][rr.src_live[:nnzb_p]],
        rr.src_row[:nnzb_p][rr.src_live[:nnzb_p]]] = True
    np.testing.assert_allclose(g_rr[cov], g[cov], atol=1e-3)
    assert not g_rr[~cov].any()
    # occupancy-live positions are always covered
    nnzb = int(np.asarray(b.row_ptr)[-1])
    occ = np.zeros(g.shape[:2], bool)
    occ[:nnzb] = np.abs(np.asarray(b.blocks)[:nnzb]).sum(axis=2) != 0
    assert (occ <= cov).all()


def test_reorder_wins_on_structured_occupancy():
    """Interleaved row signatures: grouping even/odd rows halves the live
    block count, and the surrogate-driven search takes the win."""
    rng = np.random.default_rng(7)
    m, k = GM * BM, GK * BK
    d = rng.standard_normal((m, k)).astype(np.float32)
    colmask = np.zeros((m, k), bool)
    colmask[0::2, :k // 2] = True
    colmask[1::2, k // 2:] = True
    b = BlockCSR.from_dense(jnp.asarray(d * colmask), block_shape=(BM, BK))
    rr = reorder_rows(b)
    assert rr.n_blocks * 2 == int(np.asarray(b.row_ptr)[-1])
    assert rr.density_after == pytest.approx(1.0)
    plan_cache_clear()
    _, rep = plan_search(b, budget=256, reorder="auto", full=True,
                         use_cache=False)
    assert rep.best_config["reorder"] is True


# --------------------------------------------------------------------------
# autotuner knob: space, never-worse, occupancy-keyed cache
# --------------------------------------------------------------------------

def test_knob_space_reorder_options():
    b = _bcsr("uniform")
    s_default = spmm_knob_space(b)
    assert all(c["reorder"] is False for c in s_default)
    s_auto = spmm_knob_space(b, reorder="auto")
    assert any(c["reorder"] for c in s_auto)
    assert [c for c in s_auto if not c["reorder"]] == s_default
    with pytest.raises(ValueError, match="reorder"):
        spmm_knob_space(b, reorder="always")
    # single-device knob: never paired with shard counts
    s_sharded = spmm_knob_space(b, shard_counts=(2,), reorder="auto")
    assert all(not c["reorder"] for c in s_sharded)


@pytest.mark.parametrize("kind", KINDS)
def test_reorder_auto_never_worse(kind):
    b = _bcsr(kind)
    plan_cache_clear()
    p_no, rep_no = plan_search(b, budget=256, full=True, use_cache=False)
    p_auto, rep_auto = plan_search(b, budget=256, reorder="auto", full=True,
                                   use_cache=False)
    assert p_auto.predicted_cycles()["plan"] \
        <= p_no.predicted_cycles()["plan"]
    rhs = jnp.asarray(np.random.default_rng(6).standard_normal(
        (GK * BK, 8)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(maple_spmm(b, rhs, plan=p_auto)),
        np.asarray(maple_spmm(b, rhs, plan=p_no)), atol=1e-4)


def test_reorder_cache_keyed_on_occupancy():
    """Same block pattern, different element occupancy -> different
    digests and no cache collision (a cached reorder must never serve a
    payload it wasn't built from)."""
    b1 = _bcsr("uniform", thin=0.5)
    d2 = np.asarray(b1.to_dense()).copy()
    live = d2 != 0
    rng = np.random.default_rng(9)
    # zero half the live elements: block pattern may shrink — rebuild at
    # the same pattern by zeroing only non-load-bearing elements (keep at
    # least one nonzero per live block row-pair is overkill; just check
    # fingerprints before using)
    d2[live] *= (rng.random(int(live.sum())) < 0.5)
    b2 = BlockCSR.from_dense(jnp.asarray(d2), block_shape=(BM, BK))
    if pattern_fingerprint(b1) == pattern_fingerprint(b2):
        assert occupancy_digest(b1) != occupancy_digest(b2)
        plan_cache_clear()
        p1 = plan_search(b1, budget=32, reorder="auto")
        p2 = plan_search(b2, budget=32, reorder="auto")
        assert p1 is not p2
    # identical payloads share the digest and hit the cache
    assert occupancy_digest(b1) == occupancy_digest(
        BlockCSR.from_dense(b1.to_dense(), block_shape=(BM, BK)))
    plan_cache_clear()
    assert plan_search(b1, budget=32, reorder="auto") \
        is plan_search(b1, budget=32, reorder="auto")


def test_maple_spmm_auto_reorder_kwarg():
    b = _bcsr("banded")
    rhs = jnp.asarray(np.random.default_rng(8).standard_normal(
        (GK * BK, 8)).astype(np.float32))
    plan_cache_clear()
    out = np.asarray(maple_spmm(b, rhs, plan="auto", reorder="auto"))
    np.testing.assert_allclose(
        out, np.asarray(b.to_dense()) @ np.asarray(rhs), atol=1e-4)


def test_reorder_rejects_mismatched_operand():
    b = _bcsr("uniform")
    rr = reorder_rows(b)
    other = _bcsr("uniform", seed=11)  # different pattern, same shape
    bigger = BlockCSR.from_dense(
        jnp.zeros((GM * BM, 2 * GK * BK), jnp.float32).at[0, 0].set(1.0),
        block_shape=(BM, BK))
    with pytest.raises(ValueError, match="built for"):
        apply_reorder(bigger, rr)


def test_reorder_raises_under_jit():
    b = _bcsr("uniform")
    with pytest.raises(ValueError, match="jit"):
        jax.jit(lambda blocks: reorder_rows(BlockCSR(
            blocks, b.block_col, b.block_row, b.row_ptr, b.shape,
            b.block_shape)))(b.blocks)
