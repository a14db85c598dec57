"""Row-wise product (Gustavson) references vs dense oracle + properties."""

import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.csr import CSR
from repro.core.gustavson import (dense_oracle, spmm_rowwise,
                                  spmspm_rowwise, spmspm_rowwise_scan)


def _rand(rng, m, n, density):
    return ((rng.random((m, n)) < density)
            * rng.standard_normal((m, n))).astype(np.float32)


def test_spmm_matches_dense():
    rng = np.random.default_rng(0)
    a = CSR.from_dense(_rand(rng, 24, 16, 0.3))
    b = jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(spmm_rowwise(a, b)),
                               np.asarray(dense_oracle(a, b)),
                               rtol=1e-5, atol=1e-5)


def test_spmspm_matches_dense():
    rng = np.random.default_rng(1)
    ad = _rand(rng, 16, 12, 0.4)
    bd = _rand(rng, 12, 20, 0.3)
    a, b = CSR.from_dense(ad), CSR.from_dense(bd)
    np.testing.assert_allclose(np.asarray(spmspm_rowwise(a, b)), ad @ bd,
                               rtol=1e-5, atol=1e-5)


def test_spmspm_scan_matches_vectorized():
    rng = np.random.default_rng(2)
    ad = _rand(rng, 32, 32, 0.15)
    a = CSR.from_dense(ad, nnz_max=int((ad != 0).sum()) + 5)
    got = spmspm_rowwise_scan(a, a, row_chunk=8)
    np.testing.assert_allclose(np.asarray(got), ad @ ad, rtol=1e-4, atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(m=st.integers(1, 16), k=st.integers(1, 16), n=st.integers(1, 16),
       da=st.floats(0.05, 0.8), db=st.floats(0.05, 0.8),
       seed=st.integers(0, 2**16))
def test_spmspm_property(m, k, n, da, db, seed):
    rng = np.random.default_rng(seed)
    ad, bd = _rand(rng, m, k, da), _rand(rng, k, n, db)
    a, b = CSR.from_dense(ad), CSR.from_dense(bd)
    np.testing.assert_allclose(np.asarray(spmspm_rowwise(a, b)), ad @ bd,
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_spmm_linearity_property(seed):
    """Row-wise product is linear in A's values (Eq. 3)."""
    rng = np.random.default_rng(seed)
    ad = _rand(rng, 12, 10, 0.4)
    b = jnp.asarray(rng.standard_normal((10, 6)).astype(np.float32))
    a1 = CSR.from_dense(ad)
    a2 = CSR.from_dense(2.0 * ad)
    y1 = np.asarray(spmm_rowwise(a1, b))
    y2 = np.asarray(spmm_rowwise(a2, b))
    np.testing.assert_allclose(y2, 2.0 * y1, rtol=1e-5, atol=1e-5)
