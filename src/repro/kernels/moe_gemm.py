"""MoE ragged grouped GEMM — the production integration of the Maple engine.

Routed MoE expert compute *is* a row-wise product on CSR metadata
(DESIGN §2-B): the sorted token→expert assignment is the ``col_id`` stream,
each token-tile's expert id selects which expert weight panel to fetch
(the BRB fill), and the per-tile accumulator is the PSB.  Zero-sized expert
groups — the "zero blocks" of the sparse matrix — are never touched.

Layout contract (enforced by ops.py):
  * ``x`` is ``(T, D)`` with tokens *sorted by expert* and each expert's
    segment padded to a multiple of the token tile ``bt`` (padding rows are
    zero and their outputs are dropped by the caller).
  * ``expert_of_tile`` is ``(T/bt,)`` int32: the expert that owns each tile.
  * ``w`` is ``(E, D, F)`` stacked expert weights.

Grid ``(T/bt, F/bf, D/bd)``, contraction index innermost: the PSB
``(bt, bf)`` accumulates D-panels and flushes once per (token-tile, F-tile) —
one HBM write per output tile, no partial sums in HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(
    expert_of_tile,   # (T/bt,) int32 scalar prefetch
    x_ref,            # (bt, bd)
    w_ref,            # (1, bd, bf) — the selected expert's D-panel
    out_ref,          # (bt, bf)
    psb_ref,          # (bt, bf) f32
    *,
    k_steps: int,
):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        psb_ref[...] = jnp.zeros_like(psb_ref)

    psb_ref[...] += jnp.dot(
        x_ref[...], w_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(kk == k_steps - 1)
    def _flush():
        out_ref[...] = psb_ref[...].astype(out_ref.dtype)


def moe_gemm_pallas(
    x: jax.Array,               # (T, D) expert-sorted, tile-padded
    expert_of_tile: jax.Array,  # (T/bt,) int32
    w: jax.Array,               # (E, D, F)
    *,
    bt: int = 128,
    bf: int = 128,
    bd: int = 128,
    interpret: bool,
) -> jax.Array:
    t, d = x.shape
    e, dw, f = w.shape
    if d != dw:
        raise ValueError(f"D mismatch {d} vs {dw}")
    if t % bt or f % bf or d % bd:
        raise ValueError(f"(T,F,D)=({t},{f},{d}) not divisible by "
                         f"({bt},{bf},{bd})")
    grid = (t // bt, f // bf, d // bd)

    kernel = functools.partial(_kernel, k_steps=d // bd)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bt, bd), lambda i, j, kk, eot: (i, kk)),
                pl.BlockSpec((1, bd, bf), lambda i, j, kk, eot: (eot[i], kk, j)),
            ],
            out_specs=pl.BlockSpec((bt, bf), lambda i, j, kk, eot: (i, j)),
            scratch_shapes=[pltpu.VMEM((bt, bf), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((t, f), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(expert_of_tile, x, w)
