"""Gradcheck layer for the differentiable Maple kernels.

Three kinds of evidence per VJP (maple_spmm / maple_spgemm / the SDDMM
kernels backing their dA):

* **dense-oracle** — ``jax.grad`` of the same contraction via ``to_dense``
  and plain matmul, masked to the fixed sparsity pattern (structure gets
  no gradient; payloads must match to 1e-4);
* **finite differences** — directional derivative along a random
  direction vs ``<grad, d>`` (independent of any autodiff machinery);
* **properties** — hypothesis-or-fallback sweeps over the three workload
  families (uniform / power-law / banded) including empty-row, all-zero
  and at-capacity operands.

Plus the end-to-end scenario the VJPs open: a jitted train loop over a
sparse-MLP LM whose loss must fall over 20 steps **without a single
``to_dense`` call in the step** (guarded by monkeypatching ``to_dense``
to raise — the backward must stay inside compressed storage).

The fast subset is marked ``tier1``; the full file is the CI ``grad``
job.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.csr import CSR, BlockCSR
from repro.kernels import (maple_spgemm, maple_spmm, plan_spgemm,
                           plan_spmm_vjp)
from repro.kernels.maple_sddmm import maple_sddmm_bsr_pallas
from repro.models.layers import sparse_linear


# --------------------------------------------------------------------------
# pattern factories (block and element granularity, the paper's families)
# --------------------------------------------------------------------------

def block_mask(kind, rng, gm, gk):
    if kind == "uniform":
        mask = rng.random((gm, gk)) < 0.4
    elif kind == "power_law":
        mask = np.zeros((gm, gk), bool)
        for i in range(gm):
            ln = max(1, int(round(gk * (i + 1) ** -1.3)))
            mask[i, rng.choice(gk, size=ln, replace=False)] = True
    elif kind == "banded":
        mask = np.abs(np.subtract.outer(np.arange(gm),
                                        np.arange(gk))) <= 1
    elif kind == "empty_rows":
        mask = rng.random((gm, gk)) < 0.5
        mask[::2] = False
    elif kind == "all_zero":
        mask = np.zeros((gm, gk), bool)
    else:
        raise ValueError(kind)
    return mask


def _bsr_from_mask(rng, mask, bm, bk, extra_pad=0):
    gm, gk = mask.shape
    d = rng.standard_normal((gm * bm, gk * bk)).astype(np.float32)
    d *= np.repeat(np.repeat(mask, bm, 0), bk, 1)
    a = BlockCSR.from_dense(d, (bm, bk),
                            n_blocks_max=max(int(mask.sum()), 1) + extra_pad)
    return d, a


def _rebuild_bsr(a, blocks):
    return BlockCSR(blocks, a.block_col, a.block_row, a.row_ptr,
                    a.shape, a.block_shape)


def _rebuild_csr(a, value):
    return CSR(value, a.col_id, a.row_ptr, a.shape)


def _elem_mask(kind, rng, m, k):
    if kind == "uniform":
        mask = rng.random((m, k)) < 0.25
    elif kind == "power_law":
        mask = np.zeros((m, k), bool)
        for i in range(m):
            ln = max(1, int(round(k * (i + 1) ** -1.2)))
            mask[i, rng.choice(k, size=ln, replace=False)] = True
    elif kind == "banded":
        mask = np.abs(np.subtract.outer(np.arange(m),
                                        np.arange(k))) < 2
    elif kind == "empty_rows":
        mask = rng.random((m, k)) < 0.4
        mask[::2] = False
    elif kind == "all_zero":
        mask = np.zeros((m, k), bool)
    else:
        raise ValueError(kind)
    return mask


def _csr_from_mask(rng, mask, extra_pad=0):
    d = (mask * rng.standard_normal(mask.shape)).astype(np.float32)
    c = CSR.from_dense(d, nnz_max=max(int((d != 0).sum()), 1) + extra_pad)
    return d, c


def _fd_directional(f, x, key, eps=1e-2):
    """Central finite difference of scalar ``f`` along a random unit
    direction at ``x``; returns (fd, direction)."""
    d = jax.random.normal(key, x.shape, jnp.float32)
    d = d / jnp.maximum(jnp.linalg.norm(d.reshape(-1)), 1e-9)
    d = d.astype(x.dtype)
    fd = (f(x + eps * d) - f(x - eps * d)) / (2 * eps)
    return float(fd), d


# --------------------------------------------------------------------------
# maple_spmm VJP vs dense oracle (tier1 fast subset)
# --------------------------------------------------------------------------

@pytest.mark.tier1
@pytest.mark.parametrize("kind", ["uniform", "power_law", "banded"])
def test_spmm_grads_match_dense_oracle(kind):
    rng = np.random.default_rng(7)
    bm = bk = 8
    d, a = _bsr_from_mask(rng, block_mask(kind, rng, 4, 6), bm, bk,
                          extra_pad=2)
    x = jnp.asarray(rng.standard_normal((48, 16)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((32, 16)).astype(np.float32))

    ga, gx = jax.grad(
        lambda blk, xx: jnp.sum(maple_spmm(_rebuild_bsr(a, blk), xx,
                                           bn=16) * w),
        argnums=(0, 1))(a.blocks, x)
    gad, gxd = jax.grad(
        lambda dd, xx: jnp.sum((dd @ xx) * w), argnums=(0, 1))(
        jnp.asarray(d), x)

    np.testing.assert_allclose(np.asarray(gx), np.asarray(gxd),
                               rtol=1e-4, atol=1e-4)
    pattern = np.repeat(np.repeat(
        block_mask(kind, np.random.default_rng(7), 4, 6), bm, 0), bk, 1)
    da_dense = np.asarray(_rebuild_bsr(a, ga).to_dense())
    np.testing.assert_allclose(da_dense, np.asarray(gad) * pattern,
                               rtol=1e-4, atol=1e-4)
    # pad slots carry exactly zero gradient
    nnzb = int(np.asarray(a.row_ptr)[-1])
    np.testing.assert_array_equal(np.asarray(ga[nnzb:]), 0.0)


def test_spmm_grad_finite_difference():
    rng = np.random.default_rng(3)
    d, a = _bsr_from_mask(rng, block_mask("uniform", rng, 3, 4), 8, 8)
    x = jnp.asarray(rng.standard_normal((32, 16)).astype(np.float32))
    tp = plan_spmm_vjp(a)

    def loss_blocks(blk):
        return jnp.sum(maple_spmm(_rebuild_bsr(a, blk), x, bn=16,
                                  plan=tp) ** 2)

    def loss_x(xx):
        return jnp.sum(maple_spmm(a, xx, bn=16, plan=tp) ** 2)

    for f, arg, key in ((loss_blocks, a.blocks, 0), (loss_x, x, 1)):
        g = jax.grad(f)(arg)
        fd, dvec = _fd_directional(f, arg, jax.random.PRNGKey(key))
        ip = float(jnp.vdot(g.astype(jnp.float32),
                            dvec.astype(jnp.float32)))
        assert abs(fd - ip) <= 2e-2 * max(abs(fd), abs(ip), 1.0), (fd, ip)


@pytest.mark.tier1
@pytest.mark.parametrize("kind", ["empty_rows", "all_zero"])
def test_spmm_grads_degenerate_patterns(kind):
    rng = np.random.default_rng(11)
    d, a = _bsr_from_mask(rng, block_mask(kind, rng, 4, 4), 8, 8,
                          extra_pad=1)
    x = jnp.asarray(rng.standard_normal((32, 8)).astype(np.float32))
    ga, gx = jax.grad(
        lambda blk, xx: jnp.sum(maple_spmm(_rebuild_bsr(a, blk), xx,
                                           bn=8) ** 2),
        argnums=(0, 1))(a.blocks, x)
    gad, gxd = jax.grad(
        lambda dd, xx: jnp.sum((dd @ xx) ** 2), argnums=(0, 1))(
        jnp.asarray(d), x)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gxd),
                               rtol=1e-4, atol=1e-4)
    da_dense = np.asarray(_rebuild_bsr(a, ga).to_dense())
    patt = np.asarray(_rebuild_bsr(
        a, jnp.ones_like(a.blocks)).to_dense()) != 0
    np.testing.assert_allclose(da_dense, np.asarray(gad) * patt,
                               rtol=1e-4, atol=1e-4)


@pytest.mark.tier1
def test_spmm_grad_traced_metadata_jnp_fallback():
    """The naive-under-jit path: metadata itself is traced and no train
    plan exists, so the VJP must route through the jnp gather/scatter
    backward (_spmm_bwd_jnp) — pinned here against the dense oracle."""
    rng = np.random.default_rng(29)
    mask = block_mask("power_law", rng, 4, 4)
    d, a = _bsr_from_mask(rng, mask, 8, 8, extra_pad=2)
    x = jnp.asarray(rng.standard_normal((32, 8)).astype(np.float32))

    @jax.jit
    def loss(blocks, block_row, block_col, row_ptr, xx):
        aa = BlockCSR(blocks, block_col, block_row, row_ptr,
                      a.shape, a.block_shape)
        return jnp.sum(maple_spmm(aa, xx, bn=8, schedule="naive") ** 2)

    ga, gx = jax.grad(loss, argnums=(0, 4))(
        a.blocks, a.block_row, a.block_col, a.row_ptr, x)
    gad, gxd = jax.grad(
        lambda dd, xx: jnp.sum((dd @ xx) ** 2), argnums=(0, 1))(
        jnp.asarray(d), x)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gxd),
                               rtol=1e-4, atol=1e-4)
    patt = np.repeat(np.repeat(mask, 8, 0), 8, 1)
    np.testing.assert_allclose(
        np.asarray(_rebuild_bsr(a, ga).to_dense()),
        np.asarray(gad) * patt, rtol=1e-4, atol=1e-4)


@pytest.mark.tier1
def test_spmm_grads_at_capacity_and_batched():
    rng = np.random.default_rng(5)
    mask = block_mask("uniform", rng, 3, 3)
    d, a = _bsr_from_mask(rng, mask, 8, 8, extra_pad=0)  # no pad slots
    assert a.n_blocks_max == max(int(mask.sum()), 1)
    x3 = jnp.asarray(rng.standard_normal((2, 24, 8)).astype(np.float32))
    ga = jax.grad(lambda blk: jnp.sum(
        maple_spmm(_rebuild_bsr(a, blk), x3, bn=8) ** 2))(a.blocks)
    gad = jax.grad(lambda dd: jnp.sum(
        jnp.einsum("mk,gkn->gmn", dd, x3) ** 2))(jnp.asarray(d))
    patt = np.repeat(np.repeat(mask, 8, 0), 8, 1)
    np.testing.assert_allclose(
        np.asarray(_rebuild_bsr(a, ga).to_dense()),
        np.asarray(gad) * patt, rtol=1e-4, atol=2e-4)


# --------------------------------------------------------------------------
# the block SDDMM kernel in isolation (dA's engine)
# --------------------------------------------------------------------------

@pytest.mark.tier1
def test_sddmm_bsr_kernel_matches_einsum():
    rng = np.random.default_rng(9)
    mask = block_mask("power_law", rng, 4, 5)
    d, a = _bsr_from_mask(rng, mask, 8, 8, extra_pad=3)
    g, n = 2, 16
    dc = jnp.asarray(rng.standard_normal((g, 32, n)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((g, 40, n)).astype(np.float32))
    out = maple_sddmm_bsr_pallas(dc, b, a.block_row, a.block_col,
                                 bm=8, bk=8, bn=8, interpret=True)
    full = jnp.einsum("gmn,gkn->mk", dc, b)           # dense dC @ B^T
    full_t = np.asarray(full).reshape(4, 8, 5, 8).transpose(0, 2, 1, 3)
    br = np.asarray(a.block_row)
    bc = np.asarray(a.block_col)
    nnzb = int(np.asarray(a.row_ptr)[-1])
    for s in range(nnzb):
        np.testing.assert_allclose(np.asarray(out[s]),
                                   full_t[br[s], bc[s]],
                                   rtol=1e-4, atol=1e-4)
    # pad slots are masked to zero inside the kernel
    np.testing.assert_array_equal(np.asarray(out[nnzb:]), 0.0)


# --------------------------------------------------------------------------
# maple_spgemm VJP (dA via the element SDDMM, dB via the A^T-side scatter)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind", [
    pytest.param("uniform",
                 marks=[pytest.mark.tier1, pytest.mark.slow]),
    "power_law", "banded",
])
def test_spgemm_grads_match_dense_oracle(kind):
    rng = np.random.default_rng(13)
    ad, a = _csr_from_mask(rng, _elem_mask(kind, rng, 12, 10), extra_pad=3)
    bd, b = _csr_from_mask(rng, _elem_mask(kind, rng, 10, 14), extra_pad=2)
    w = jnp.asarray(rng.standard_normal((12, 14)).astype(np.float32))

    ga, gb = jax.grad(
        lambda av, bv: jnp.sum(maple_spgemm(
            _rebuild_csr(a, av), _rebuild_csr(b, bv)).to_dense() * w),
        argnums=(0, 1))(a.value, b.value)
    gad, gbd = jax.grad(
        lambda x, y: jnp.sum((x @ y) * w), argnums=(0, 1))(
        jnp.asarray(ad), jnp.asarray(bd))

    np.testing.assert_allclose(
        np.asarray(_rebuild_csr(a, ga).to_dense()),
        np.asarray(gad) * (ad != 0), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(_rebuild_csr(b, gb).to_dense()),
        np.asarray(gbd) * (bd != 0), rtol=1e-4, atol=1e-4)
    # structure carries no gradient: pad value slots stay exactly zero
    np.testing.assert_array_equal(
        np.asarray(ga[int(np.asarray(a.row_ptr)[-1]):]), 0.0)


@pytest.mark.parametrize("kind", [
    "empty_rows", pytest.param("all_zero", marks=pytest.mark.tier1),
])
def test_spgemm_grads_degenerate_patterns(kind):
    rng = np.random.default_rng(17)
    ad, a = _csr_from_mask(rng, _elem_mask(kind, rng, 8, 8), extra_pad=2)
    bd, b = _csr_from_mask(rng, _elem_mask("uniform", rng, 8, 8),
                           extra_pad=0)  # at capacity
    ga, gb = jax.grad(
        lambda av, bv: jnp.sum(maple_spgemm(
            _rebuild_csr(a, av), _rebuild_csr(b, bv)).to_dense() ** 2),
        argnums=(0, 1))(a.value, b.value)
    gad, gbd = jax.grad(
        lambda x, y: jnp.sum((x @ y) ** 2), argnums=(0, 1))(
        jnp.asarray(ad), jnp.asarray(bd))
    np.testing.assert_allclose(
        np.asarray(_rebuild_csr(a, ga).to_dense()),
        np.asarray(gad) * (ad != 0), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(_rebuild_csr(b, gb).to_dense()),
        np.asarray(gbd) * (bd != 0), rtol=1e-4, atol=1e-4)


def test_spgemm_grad_finite_difference():
    rng = np.random.default_rng(19)
    ad, a = _csr_from_mask(rng, _elem_mask("uniform", rng, 10, 10))
    plan = plan_spgemm(a, a)

    def loss(av):
        c = maple_spgemm(_rebuild_csr(a, av), _rebuild_csr(a, av),
                         plan=plan)
        return jnp.sum(c.value ** 2)

    g = jax.grad(loss)(a.value)
    fd, dvec = _fd_directional(loss, a.value, jax.random.PRNGKey(2))
    ip = float(jnp.vdot(g, dvec))
    assert abs(fd - ip) <= 2e-2 * max(abs(fd), abs(ip), 1.0), (fd, ip)


# --------------------------------------------------------------------------
# hypothesis-or-fallback property sweeps
# --------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(kind=st.sampled_from(["uniform", "power_law", "banded",
                             "empty_rows"]),
       seed=st.integers(0, 2 ** 16), pad=st.integers(0, 4))
def test_spmm_grad_property(kind, seed, pad):
    rng = np.random.default_rng(seed)
    mask = block_mask(kind, rng, 3, 4)
    d, a = _bsr_from_mask(rng, mask, 8, 8, extra_pad=pad)
    x = jnp.asarray(rng.standard_normal((32, 8)).astype(np.float32))
    ga, gx = jax.grad(
        lambda blk, xx: jnp.sum(jnp.cos(maple_spmm(
            _rebuild_bsr(a, blk), xx, bn=8))),
        argnums=(0, 1))(a.blocks, x)
    gad, gxd = jax.grad(
        lambda dd, xx: jnp.sum(jnp.cos(dd @ xx)), argnums=(0, 1))(
        jnp.asarray(d), x)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gxd),
                               rtol=1e-4, atol=1e-4)
    patt = np.repeat(np.repeat(mask, 8, 0), 8, 1)
    np.testing.assert_allclose(
        np.asarray(_rebuild_bsr(a, ga).to_dense()),
        np.asarray(gad) * patt, rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(kind=st.sampled_from(["uniform", "power_law", "banded",
                             "empty_rows"]),
       seed=st.integers(0, 2 ** 16), pad=st.integers(0, 3))
def test_spgemm_grad_property(kind, seed, pad):
    rng = np.random.default_rng(seed)
    ad, a = _csr_from_mask(rng, _elem_mask(kind, rng, 9, 7),
                           extra_pad=pad)
    bd, b = _csr_from_mask(rng, _elem_mask("uniform", rng, 7, 11),
                           extra_pad=pad)
    ga, gb = jax.grad(
        lambda av, bv: jnp.sum(jnp.sin(maple_spgemm(
            _rebuild_csr(a, av), _rebuild_csr(b, bv)).to_dense())),
        argnums=(0, 1))(a.value, b.value)
    gad, gbd = jax.grad(
        lambda x, y: jnp.sum(jnp.sin(x @ y)), argnums=(0, 1))(
        jnp.asarray(ad), jnp.asarray(bd))
    np.testing.assert_allclose(
        np.asarray(_rebuild_csr(a, ga).to_dense()),
        np.asarray(gad) * (ad != 0), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(_rebuild_csr(b, gb).to_dense()),
        np.asarray(gbd) * (bd != 0), rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# sparse_linear end to end: jitted, prebuilt plan, three pattern families
# --------------------------------------------------------------------------

@pytest.mark.tier1
@pytest.mark.parametrize("kind", ["uniform", "power_law", "banded"])
def test_sparse_linear_grad_jitted_prebuilt_plan(kind):
    """Acceptance: jax.grad through sparse_linear (balanced schedule,
    jitted, prebuilt plan) matches the dense oracle to 1e-4."""
    rng = np.random.default_rng(23)
    mask = block_mask(kind, rng, 4, 6)
    d, w = _bsr_from_mask(rng, mask, 8, 8, extra_pad=2)  # (32, 48)
    tp = plan_spmm_vjp(w)
    x = jnp.asarray(rng.standard_normal((2, 3, 48)).astype(np.float32))

    @jax.jit
    def loss(blocks, xx):
        y = sparse_linear(_rebuild_bsr(w, blocks), xx, plan=tp, bn=16)
        return jnp.sum(y ** 2)

    gw, gx = jax.grad(loss, argnums=(0, 1))(w.blocks, x)
    gwd, gxd = jax.grad(
        lambda dd, xx: jnp.sum(jnp.einsum("bsf,vf->bsv", xx, dd) ** 2),
        argnums=(0, 1))(jnp.asarray(d), x)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gxd),
                               rtol=1e-4, atol=1e-4)
    patt = np.repeat(np.repeat(mask, 8, 0), 8, 1)
    np.testing.assert_allclose(
        np.asarray(_rebuild_bsr(w, gw).to_dense()),
        np.asarray(gwd) * patt, rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# end-to-end scenario: sparse-MLP LM trains, never densifying A
# --------------------------------------------------------------------------

def _tiny_sparse_cfg():
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name="tiny-sparse", family="dense", n_layers=2, d_model=32,
        n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64, vocab_size=128,
        vocab_pad_multiple=64, sparse_mlp=True, sparse_block=(8, 8),
        sparse_density=0.4, remat=False)


@pytest.mark.timeout(240)
def test_sparse_mlp_training_loss_decreases_without_densify(monkeypatch):
    from repro.data import DataConfig, synth_batch
    from repro.models import lm
    from repro.train import (OptimizerConfig, init_opt_state,
                             make_train_step)

    cfg = _tiny_sparse_cfg()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    plan = lm.sparse_mlp_plan(params)
    assert plan is not None
    ocfg = OptimizerConfig(peak_lr=1e-3, warmup_steps=2, total_steps=100)
    opt = init_opt_state(ocfg, params)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=4)
    step = jax.jit(make_train_step(cfg, ocfg, 1, mlp_plan=plan))

    # the guard: the sparse operand must never densify — neither in the
    # forward nor in the backward.  Tracing happens on the first step, so
    # a to_dense anywhere in the step would raise here.
    def _boom(self):
        raise AssertionError("to_dense called inside the train step")
    monkeypatch.setattr(BlockCSR, "to_dense", _boom)
    monkeypatch.setattr(CSR, "to_dense", _boom)

    losses = []
    for s in range(20):
        params, opt, m = step(params, opt, synth_batch(dcfg, s))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses
    # gradients actually reached the sparse payloads: weights moved
    w = [x for x in jax.tree_util.tree_leaves(
        params, is_leaf=lambda v: isinstance(v, BlockCSR))
        if isinstance(x, BlockCSR)][0]
    fresh = lm.init_params(cfg, jax.random.PRNGKey(0))
    w0 = [x for x in jax.tree_util.tree_leaves(
        fresh, is_leaf=lambda v: isinstance(v, BlockCSR))
        if isinstance(x, BlockCSR)][0]
    assert float(jnp.abs(w.blocks - w0.blocks).max()) > 0
    # ... and the pattern (metadata) did not
    np.testing.assert_array_equal(np.asarray(w.block_col),
                                  np.asarray(w0.block_col))
    np.testing.assert_array_equal(np.asarray(w.row_ptr),
                                  np.asarray(w0.row_ptr))
