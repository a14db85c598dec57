"""CSR / BlockCSR container tests incl. hypothesis round-trip properties."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

pytestmark = pytest.mark.tier1


import jax
import jax.numpy as jnp

from repro.core.csr import CSR, BlockCSR, bsr_transpose, csr_transpose


def random_sparse(rng, m, n, density):
    d = (rng.random((m, n)) < density) * rng.standard_normal((m, n))
    return d.astype(np.float32)


def test_csr_roundtrip_basic():
    rng = np.random.default_rng(0)
    d = random_sparse(rng, 13, 7, 0.3)
    c = CSR.from_dense(d)
    np.testing.assert_array_equal(np.asarray(c.to_dense()), d)


def test_csr_padding_slots_harmless():
    rng = np.random.default_rng(1)
    d = random_sparse(rng, 8, 8, 0.2)
    nnz = int((d != 0).sum())
    c = CSR.from_dense(d, nnz_max=nnz + 17)
    assert c.nnz_max == nnz + 17
    np.testing.assert_array_equal(np.asarray(c.to_dense()), d)
    assert int(c.nnz) == nnz


def test_csr_row_ids():
    d = np.array([[1, 0], [0, 2], [0, 0]], np.float32)
    c = CSR.from_dense(d)
    rows = np.asarray(c.row_ids())[: int(c.nnz)]
    np.testing.assert_array_equal(rows, [0, 1])


@pytest.mark.slow
@settings(max_examples=25, deadline=None)
@given(
    m=st.integers(1, 24), n=st.integers(1, 24),
    density=st.floats(0.0, 0.6), seed=st.integers(0, 2**16),
)
def test_csr_roundtrip_property(m, n, density, seed):
    rng = np.random.default_rng(seed)
    d = random_sparse(rng, m, n, density)
    c = CSR.from_dense(d, nnz_max=max(int((d != 0).sum()), 1) + 3)
    np.testing.assert_allclose(np.asarray(c.to_dense()), d, atol=0)
    # row_ptr is monotone and consistent with nnz
    rp = np.asarray(c.row_ptr)
    assert (np.diff(rp) >= 0).all()
    assert rp[-1] == (d != 0).sum()


def test_blockcsr_roundtrip():
    rng = np.random.default_rng(2)
    d = np.zeros((64, 96), np.float32)
    # fill a few blocks
    d[0:16, 32:48] = rng.standard_normal((16, 16))
    d[48:64, 0:16] = rng.standard_normal((16, 16))
    b = BlockCSR.from_dense(d, (16, 16))
    np.testing.assert_array_equal(np.asarray(b.to_dense()), d)
    assert b.density() == pytest.approx(2 / (4 * 6))


def test_blockcsr_rejects_nondivisible():
    with pytest.raises(ValueError):
        BlockCSR.from_dense(np.zeros((10, 16), np.float32), (16, 16))


# --------------------------------------------------------------------------
# transposes
# --------------------------------------------------------------------------

def test_csr_transpose_roundtrip_pattern_and_values():
    rng = np.random.default_rng(4)
    d = random_sparse(rng, 11, 7, 0.35)
    d[-2:] = 0.0                                  # trailing all-zero rows
    a = CSR.from_dense(d, nnz_max=int((d != 0).sum()) + 5)
    at = csr_transpose(a)
    assert at.shape == (7, 11)
    np.testing.assert_array_equal(np.asarray(at.to_dense()), d.T)
    # involution on the pattern AND the padded containers: same capacity,
    # identical metadata, identical value vector
    aa = csr_transpose(at, nnz_max=a.nnz_max)
    np.testing.assert_array_equal(np.asarray(aa.col_id),
                                  np.asarray(a.col_id))
    np.testing.assert_array_equal(np.asarray(aa.row_ptr),
                                  np.asarray(a.row_ptr))
    np.testing.assert_array_equal(np.asarray(aa.value),
                                  np.asarray(a.value))


def test_csr_transpose_sorted_columns_and_pad_preservation():
    rng = np.random.default_rng(5)
    d = random_sparse(rng, 9, 13, 0.4)
    a = CSR.from_dense(d, nnz_max=int((d != 0).sum()) + 7)
    at = csr_transpose(a)
    rp = np.asarray(at.row_ptr)
    ci = np.asarray(at.col_id)
    nnz = int(rp[-1])
    for i in range(at.shape[0]):                  # sorted, unique columns
        seg = ci[rp[i]:rp[i + 1]]
        assert (np.diff(seg) > 0).all()
    # pad contract preserved: col_id = -1, value = 0 past the live prefix
    np.testing.assert_array_equal(ci[nnz:], -1)
    np.testing.assert_array_equal(np.asarray(at.value)[nnz:], 0.0)
    assert at.nnz_max == a.nnz_max                # capacity carried over


def test_csr_transpose_capacity_and_traced_values():
    d = np.array([[1, 0, 2], [0, 3, 0]], np.float32)
    a = CSR.from_dense(d, nnz_max=5)
    with pytest.raises(ValueError):
        csr_transpose(a, nnz_max=2)               # below live nnz
    # values may be traced: transpose composes with jit (pattern is host)
    out = jax.jit(lambda v: csr_transpose(
        CSR(v, a.col_id, a.row_ptr, a.shape)).value)(a.value)
    at = csr_transpose(a)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(at.value))


def test_bsr_transpose_roundtrip():
    rng = np.random.default_rng(6)
    d = np.zeros((32, 48), np.float32)
    d[0:8, 16:24] = rng.standard_normal((8, 8))
    d[24:32, 0:8] = rng.standard_normal((8, 8))
    d[0:8, 40:48] = rng.standard_normal((8, 8))
    a = BlockCSR.from_dense(d, (8, 8), n_blocks_max=6)
    at = bsr_transpose(a)
    assert at.shape == (48, 32) and at.block_shape == (8, 8)
    np.testing.assert_array_equal(np.asarray(at.to_dense()), d.T)
    np.testing.assert_array_equal(
        np.asarray(bsr_transpose(at).to_dense()), d)
    # pads: col -1, zero payload
    nnzb = int(np.asarray(at.row_ptr)[-1])
    np.testing.assert_array_equal(np.asarray(at.block_col)[nnzb:], -1)
    np.testing.assert_array_equal(np.asarray(at.blocks)[nnzb:], 0.0)


def test_csr_to_ell_still_raises_on_truncation_after_transpose():
    """Regression: the transpose path must not loosen the csr_to_ell
    silent-truncation guard (PR 2 contract)."""
    from repro.kernels import csr_to_ell
    d = np.array([[1, 2, 3], [4, 0, 0], [0, 0, 0]], np.float32)
    at = csr_transpose(CSR.from_dense(d))
    # column 0 of d has 2 entries -> row 0 of d^T has 2; asking for 1 drops
    with pytest.raises(ValueError):
        csr_to_ell(at, max_row_len=1)
    vals, cols = csr_to_ell(at, max_row_len=1, truncate=True)
    assert vals.shape == (3, 1)


# --------------------------------------------------------------------------
# pad contract: trailing all-zero rows never depend on OOB scatter drops
# --------------------------------------------------------------------------

def test_to_dense_trailing_zero_rows_pad_contract():
    d = np.zeros((6, 4), np.float32)
    d[0, 1] = 2.0
    d[1, 3] = -1.0
    a = CSR.from_dense(d, nnz_max=9)             # 7 pad slots, rows 2-5 empty
    a.check_pad_contract()                       # producer upholds it
    # every pad slot resolves past the last live row: the explicit clamp +
    # col>=0 mask (not XLA's drop-OOB scatter mode) must keep them inert
    rows = np.asarray(a.row_ids())
    assert (rows[int(a.nnz):] >= 2).all()
    np.testing.assert_array_equal(np.asarray(a.to_dense()), d)
    # and under jit (scatter lowered, same contract)
    out = jax.jit(lambda v: CSR(v, a.col_id, a.row_ptr, a.shape).to_dense())(
        a.value)
    np.testing.assert_array_equal(np.asarray(out), d)
    # a hand-built container honouring the contract round-trips too
    b = CSR(value=jnp.asarray([5.0, 0.0, 0.0]),
            col_id=jnp.asarray([2, -1, -1], jnp.int32),
            row_ptr=jnp.asarray([0, 1, 1, 1], jnp.int32), shape=(3, 3))
    b.check_pad_contract()
    expect = np.zeros((3, 3), np.float32)
    expect[0, 2] = 5.0
    np.testing.assert_array_equal(np.asarray(b.to_dense()), expect)
    # the validator actually fires on a violating container
    bad = CSR(value=jnp.asarray([5.0, 1.0, 0.0]),   # pad value != 0
              col_id=jnp.asarray([2, -1, -1], jnp.int32),
              row_ptr=jnp.asarray([0, 1, 1, 1], jnp.int32), shape=(3, 3))
    with pytest.raises(ValueError):
        bad.check_pad_contract()


@pytest.mark.slow
@settings(max_examples=12, deadline=None)
@given(
    m=st.integers(1, 16), n=st.integers(1, 16),
    density=st.floats(0.0, 0.6), seed=st.integers(0, 2**16),
    pad=st.integers(0, 6),
)
def test_csr_transpose_property(m, n, density, seed, pad):
    rng = np.random.default_rng(seed)
    d = random_sparse(rng, m, n, density)
    a = CSR.from_dense(d, nnz_max=max(int((d != 0).sum()), 1) + pad)
    at = csr_transpose(a)
    np.testing.assert_array_equal(np.asarray(at.to_dense()), d.T)
    # pattern involution
    aa = csr_transpose(at, nnz_max=a.nnz_max)
    np.testing.assert_array_equal(np.asarray(aa.col_id),
                                  np.asarray(a.col_id))
    np.testing.assert_array_equal(np.asarray(aa.row_ptr),
                                  np.asarray(a.row_ptr))


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(
    gm=st.integers(1, 4), gk=st.integers(1, 4),
    density=st.floats(0.0, 1.0), seed=st.integers(0, 2**16),
)
def test_blockcsr_roundtrip_property(gm, gk, density, seed):
    rng = np.random.default_rng(seed)
    bm = bk = 8
    mask = rng.random((gm, gk)) < density
    d = np.zeros((gm * bm, gk * bk), np.float32)
    for i in range(gm):
        for j in range(gk):
            if mask[i, j]:
                blk = rng.standard_normal((bm, bk)).astype(np.float32)
                blk[0, 0] = blk[0, 0] or 1.0  # keep block non-zero
                d[i*bm:(i+1)*bm, j*bk:(j+1)*bk] = blk
    b = BlockCSR.from_dense(d, (bm, bk), n_blocks_max=int(mask.sum()) + 2)
    np.testing.assert_array_equal(np.asarray(b.to_dense()), d)


# --------------------------------------------------------------------------
# BlockCSR pad contract + the MAPLE_VALIDATE entry-point gate
# --------------------------------------------------------------------------

def _bsr_example(pad=2):
    d = np.zeros((8, 8), np.float32)
    d[0:4, 0:4] = 1.0
    d[4:8, 4:8] = 2.0
    return BlockCSR.from_dense(d, (4, 4), n_blocks_max=2 + pad), d


def test_blockcsr_check_pad_contract_accepts_and_chains():
    b, _ = _bsr_example()
    assert b.check_pad_contract() is b           # returns self for chaining
    # degenerate single-block-row matrix: pad block_row must be 0
    d1 = np.zeros((4, 8), np.float32)
    d1[:, :4] = 3.0
    BlockCSR.from_dense(d1, (4, 4), n_blocks_max=3).check_pad_contract()


@pytest.mark.parametrize("mutate,msg", [
    (lambda b: b.__setattr__("block_col", b.block_col.at[2].set(1)),
     "pad block_col"),
    (lambda b: b.__setattr__("block_row", b.block_row.at[3].set(0)),
     "pad block_row"),
    (lambda b: b.__setattr__("blocks", b.blocks.at[2, 0, 0].set(7.0)),
     "pad blocks"),
    (lambda b: b.__setattr__("row_ptr",
                             jnp.asarray([0, 2, 1], jnp.int32)),
     "monotone"),
    (lambda b: b.__setattr__("block_col", b.block_col.at[0].set(5)),
     "block_col out of range"),
    (lambda b: b.__setattr__("block_row", b.block_row.at[0].set(1)),
     "disagrees with row_ptr"),
])
def test_blockcsr_check_pad_contract_rejects(mutate, msg):
    b, _ = _bsr_example()
    mutate(b)
    with pytest.raises(ValueError, match=msg):
        b.check_pad_contract()


def test_maple_validate_gate(monkeypatch):
    """MAPLE_VALIDATE=1 arms operand validation at the kernel entry
    points; unset/0 keeps the hot path check-free (a violating operand
    then flows through, pads being inert by the naive walk's masking)."""
    from repro.kernels import ops

    good, d = _bsr_example()
    rhs = np.eye(8, dtype=np.float32)
    bad, _ = _bsr_example()
    bad.blocks = bad.blocks.at[2, 0, 0].set(9.0)   # violate: pad payload

    # gate off (default): no check runs — the violating operand flows
    # into the kernel unvetted (and silently corrupts the output, which
    # is exactly what the gate exists to catch in CI)
    monkeypatch.delenv("MAPLE_VALIDATE", raising=False)
    ops.maple_spmm(bad, rhs, schedule="naive")     # no raise

    monkeypatch.setenv("MAPLE_VALIDATE", "1")
    np.testing.assert_allclose(
        np.asarray(ops.maple_spmm(good, rhs, schedule="naive")), d)
    with pytest.raises(ValueError, match="pad blocks"):
        ops.maple_spmm(bad, rhs, schedule="naive")

    # CSR side: maple_spgemm validates both operands under the gate
    dc = np.zeros((4, 4), np.float32)
    dc[0, 1] = 2.0
    a = CSR.from_dense(dc, nnz_max=3)
    ok = np.asarray(ops.maple_spgemm(a, a).to_dense())
    bad_csr = CSR(value=a.value.at[2].set(5.0), col_id=a.col_id,
                  row_ptr=a.row_ptr, shape=a.shape)
    with pytest.raises(ValueError, match="pad values"):
        ops.maple_spgemm(a, bad_csr)
    monkeypatch.setenv("MAPLE_VALIDATE", "0")
    np.testing.assert_array_equal(
        np.asarray(ops.maple_spgemm(a, bad_csr).to_dense()), ok)
