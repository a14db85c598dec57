"""AOT compiles for a described TPU v5e (no chip attached): the kernels of
the main path at real widths, and the qwen3-4b bf16 paged decode step
against the chip's 15.75 GB of HBM.

The compiler refuses here what interpret mode cannot see — blocks off the
(8, 128) tiling, kernels that do not lower, programs that do not fit — so
these tests guard every change at no chip time.  The topology is described
inside a module fixture (never at import: only one process may load the
TPU library), and every test skips where it cannot be described.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.csr import BlockCSR
from repro.kernels import maple_spmm, plan_spmm
from repro.kernels.maple_sddmm import maple_sddmm_bsr_pallas
from repro.kernels.maple_spgemm import maple_spgemm_pallas
from repro.models import lm
from repro.models.layers import sparse_linear

HBM_BYTES = 15.75e9          # v5e HBM as the compiler reports it


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler or topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back without it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled, name: str) -> bool:
    """A Mosaic kernel, its op named after the ``pallas_call``'s ``name``
    (what a device trace labels it by)."""
    text = compiled.as_text()
    return "tpu_custom_call" in text and f"%{name}." in text


def test_compact_spmm_lowers(one_chip):
    """Planned SpMM on the compact layout (what ``fused='auto'`` picks),
    bf16 128x128 blocks, K = 2560 (the qwen3-4b width)."""
    rng = np.random.default_rng(0)
    gm, gk, bs, n = 8, 20, 128, 256
    mask = rng.random((gm, gk)) < 0.3
    mask[0] = True                                  # one split heavy row
    rows, cols = np.nonzero(mask)
    row_ptr = np.zeros(gm + 1, np.int32)
    np.cumsum(np.bincount(rows, minlength=gm), out=row_ptr[1:])
    meta = BlockCSR(np.zeros((rows.size, 1, 1), np.float32), cols, rows,
                    row_ptr, (gm * bs, gk * bs), (bs, bs))
    plan = plan_spmm(meta, n_lanes=4)
    assert plan.fused == "compact"

    def fwd(blocks, b):
        a = BlockCSR(blocks, jnp.asarray(cols, jnp.int32),
                     jnp.asarray(rows, jnp.int32), jnp.asarray(row_ptr),
                     meta.shape, meta.block_shape)
        return maple_spmm(a, b, plan=plan, interpret=False)

    compiled = _compile(
        fwd, _shape(one_chip, (rows.size, bs, bs), jnp.bfloat16),
        _shape(one_chip, (gk * bs, n), jnp.bfloat16))
    assert _has_kernel(compiled, "maple_spmm_compact")


def test_block_sddmm_lowers(one_chip):
    """The dA half of the SpMM VJP: (dC @ B^T) sampled at 128x128 blocks."""
    g, m, k, n, nb = 2, 1024, 2560, 256, 40
    compiled = _compile(
        lambda dc, b, br, bc: maple_sddmm_bsr_pallas(
            dc, b, br, bc, bm=128, bk=128, bn=128, interpret=False),
        _shape(one_chip, (g, m, n), jnp.bfloat16),
        _shape(one_chip, (g, k, n), jnp.bfloat16),
        _shape(one_chip, (nb,), jnp.int32),
        _shape(one_chip, (nb,), jnp.int32))
    assert _has_kernel(compiled, "maple_sddmm_bsr")


def test_spgemm_numeric_lowers(one_chip):
    """The sparse-output SpGEMM numeric phase: one A value, one B row panel
    and one output row per step, none of them (8, 128)-aligned by shape."""
    m, k, la, lb, lc, lanes, steps = 2048, 2048, 32, 40, 300, 8, 1024
    compiled = _compile(
        lambda *a: maple_spgemm_pallas(*a, m=m, lc=lc, interpret=False),
        _shape(one_chip, (m * la, 1), jnp.float32),
        _shape(one_chip, (k, lb), jnp.float32),
        _shape(one_chip, (m * la, lb), jnp.int32),
        *[_shape(one_chip, (lanes, steps), jnp.int32)] * 3)
    assert _has_kernel(compiled, "maple_spgemm")


def test_qwen3_4b_bf16_decode_step_fits_one_chip(one_chip):
    """The continuous batcher's fused step at published widths: bf16
    parameters plus a bf16 KV pool for 8 slots x 2048 tokens, compiled
    from eval_shape shapes, fits one v5e's HBM."""
    cfg = get_config("qwen3-4b")
    slots, page, seq = 8, 16, 2048
    params = jax.eval_shape(lambda k: lm.init_params(cfg, k, jnp.bfloat16),
                            jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda: lm.init_paged_state(
        cfg, slots, slots * seq // page + 1, page, seq // page,
        dtype=jnp.bfloat16))
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda x: _shape(one_chip, x.shape, x.dtype), t)
    compiled = _compile(
        lambda p, s, t: lm.decode_step_paged(p, cfg, s, t,
                                             return_hidden=True),
        on_chip(params), on_chip(state),
        _shape(one_chip, (slots, 1), jnp.int32))
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, f"decode step needs {used / 1e9:.2f} GB"


def test_qwen3_4b_head_decode_scores_all_slots_in_one_tile(one_chip,
                                                         pallas_grids):
    """``jit_head_decode`` of the qwen3-4b serving head (128x128 blocks at
    density 0.5, bf16) on 14 slots of one token: the compact kernel runs
    one token tile for all slots, and its temporaries are under a tenth
    of scoring each slot as its own padded right-hand side."""
    cfg = get_config("qwen3-4b")
    slots, bs = 14, 128
    gm, gk = cfg.vocab_padded // bs, cfg.d_model // bs
    mask = np.random.default_rng(0).random((gm, gk)) < 0.5
    mask[np.arange(gm), np.arange(gm) % gk] = True    # no dead block-row
    rows, cols = np.nonzero(mask)
    row_ptr = np.zeros(gm + 1, np.int32)
    np.cumsum(np.bincount(rows, minlength=gm), out=row_ptr[1:])
    meta = BlockCSR(np.zeros((rows.size, 1, 1), np.float32), cols, rows,
                    row_ptr, (gm * bs, gk * bs), (bs, bs))
    plan = plan_spmm(meta)

    def weight(blocks):
        return BlockCSR(blocks, jnp.asarray(cols, jnp.int32),
                        jnp.asarray(rows, jnp.int32), jnp.asarray(row_ptr),
                        meta.shape, meta.block_shape)

    def head_decode(blocks, hidden):
        return sparse_linear(weight(blocks), hidden, plan=plan,
                             interpret=False)

    def per_slot(blocks, hidden):     # each slot its own 128-token tile
        y = maple_spmm(weight(blocks), jnp.swapaxes(hidden, 1, 2),
                       plan=plan, interpret=False)
        return jnp.swapaxes(y, 1, 2)

    args = (_shape(one_chip, (rows.size, bs, bs), jnp.bfloat16),
            _shape(one_chip, (slots, 1, cfg.d_model), jnp.bfloat16))
    (grid,) = pallas_grids(jax.make_jaxpr(head_decode)(*args).jaxpr)
    assert (grid[0], grid[2]) == (1, 1)           # (G, lanes, tiles, steps)
    folded = _compile(head_decode, *args)
    assert _has_kernel(folded, "maple_spmm_compact")
    temp = folded.memory_analysis().temp_size_in_bytes
    unfolded = _compile(per_slot, *args).memory_analysis().temp_size_in_bytes
    assert temp * 10 < unfolded, (temp, unfolded)
