"""Element-granular Maple kernel: regularized-CSR ``A`` × row-addressable
``B`` with a literal 1×N PSB — the paper-faithful port (DESIGN §2-B).

This kernel keeps the paper's *element* granularity: each grid step consumes
one non-zero ``A[i, k']`` (one ARB slot), fetches the B row-panel ``B[k',:]``
selected by its ``col_id`` (the BRB fill of Eq. (5)), multiplies the whole
row by the scalar on the VPU and accumulates into a ``(1, N)`` f32 VMEM
scratch — *exactly* the ``PSB[j'] += A.value · B.value`` of Eq. (8), with the
scatter by ``j'`` realized positionally because the panel is row-addressable.

It exists for fidelity and for genuinely element-sparse small problems; the
block-granular ``maple_spmm`` is the TPU-correct grain for production (the
MXU does 128×128 MACs per issue — DESIGN §7 has the napkin math).

Format: ELL-regularized CSR — ``values``/``col_ids`` are ``(M, L)`` with L =
max row length, padded with ``col_id = -1`` / ``value = 0``.  The ops.py
wrapper converts from the padded CSR container.

Grid ``(M, L)``, slot index innermost.  Per step ``(i, t)``:
  t == 0      → zero the PSB        (new output row)
  always      → PSB += value[i,t] · B[col_ids[i,t], :]
  t == L-1    → flush PSB to C[i,:] (single HBM write per output row)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(
    col_ids,          # (M*L,) int32 scalar prefetch, -1 pads clamped by caller
    a_row_ref,        # (1, L) values of A row i (the ARB)
    b_row_ref,        # (1, N) B row selected by col_ids[i*L + t] (the BRB)
    out_ref,          # (1, N) output row (revisited across t)
    psb_ref,          # (1, N) f32 — the literal 1×N partial-sum buffer
    *,
    slots: int,
):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _zero():
        psb_ref[...] = jnp.zeros_like(psb_ref)

    # one MAC lane-group: scalar a × row of B (padded slots have a == 0)
    a = a_row_ref[0, t]
    psb_ref[...] += a * b_row_ref[...]

    @pl.when(t == slots - 1)
    def _flush():
        out_ref[...] = psb_ref[...].astype(out_ref.dtype)


def maple_spmspm_pallas(
    values: jax.Array,    # (M, L) ELL values, 0 on pads
    col_ids: jax.Array,   # (M, L) int32, -1 on pads
    b_rows: jax.Array,    # (K, N) row-addressable B (densified rows)
    *,
    interpret: bool,
) -> jax.Array:
    m, slots = values.shape
    k, n = b_rows.shape
    flat_cols = jnp.maximum(col_ids.reshape(-1), 0)  # pads → row 0 (a == 0)

    kernel = functools.partial(_kernel, slots=slots)
    return pl.pallas_call(
        kernel,
        name="maple_spmspm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m, slots),
            in_specs=[
                pl.BlockSpec((1, slots), lambda i, t, c: (i, 0)),
                pl.BlockSpec((1, n), lambda i, t, c: (c[i * slots + t], 0)),
            ],
            out_specs=pl.BlockSpec((1, n), lambda i, t, c: (i, 0)),
            scratch_shapes=[pltpu.VMEM((1, n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), values.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(flat_cols, values, b_rows)
