"""Sparse-output SpGEMM numeric kernel: the second half of the two-phase
symbolic/numeric Maple protocol (C = A·B with *both* operands and the
result in compressed form — the paper's headline row-wise product).

The symbolic phase (``kernels.schedule.plan_spgemm``) has already walked
A and B *metadata* on the host: it knows the exact output pattern, the
width ``lc`` of the longest output row, and — for every partial product
A[i,k']·B[k',u] — the position of its target column j' inside output row
i.  What remains for the device is pure numerics, and that is all this
kernel does:

* grid ``(n_lanes, steps)``, lane-major; each step consumes one live A
  non-zero (one ARB slot, gathered through the plan's ``order``) and the
  **ELL panel of the B row** its ``col_id`` selects — B rows stay
  compressed ``(1, lb)`` value strips (the BRB fill of Eq. (5)); the dense
  ``(K, N)`` matrix is never materialized;
* the **PSB** is a bounded ``(1, lc)`` f32 scratch *indexed by output-column
  position*, not by absolute column: the paper's Eq. (8) scatter
  ``PSB[j'] += A.value · B.value`` made explicit.  The scatter itself is a
  precomputed-position one-hot matmul — ``contrib @ onehot(pos, lc)`` —
  which is how a j'-indexed register file looks when expressed on a
  matrix/vector unit (dead positions are ``-1`` and match no PSB slot);
* consecutive steps of the same output row revisit the same PSB (zero on
  first visit, flush on last — detected from ``step_row`` metadata exactly
  like the SpMM kernels), and each row is flushed **once** into its row of
  the ELL-shaped output, which the ops wrapper compacts into padded CSR.

Pad steps (``step_col == -1``) contribute nothing and their ``step_row``
points at a sacrificial extra output row (row ``m``), so an idle lane can
never clobber a real row; the wrapper slices it off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.accum import run_bounds


def _kernel(
    # scalar prefetch, flattened (n_lanes * steps,)
    order,            # flat ELL slot of A consumed per step (0 on pads)
    step_row,         # output row per step; pads -> sacrificial row m
    step_col,         # B row (= A col id) per step, -1 on pads
    # VMEM operands (the leading, squeezed axis is the gathered row)
    a_val_ref,        # (1, 1) A value of this step's slot (the ARB slot)
    b_row_ref,        # (1, lb) compressed B row panel (the BRB)
    pos_ref,          # (1, lb) int32 PSB positions for this slot's partials
    out_ref,          # (1, lc) output row values (ELL, revisited per row)
    # scratch
    psb_ref,          # (1, lc) f32 — the bounded column-indexed PSB
    *,
    steps: int,
    lb: int,
    lc: int,
):
    l = pl.program_id(0)
    s = pl.program_id(1)
    base = l * steps
    # run boundaries within this lane: the plan sorts each lane's rows, so
    # a (lane, row) run is contiguous — zero once, flush once (the shared
    # accumulation protocol of kernels.accum).
    _, is_first, is_last = run_bounds(step_row, base, s, steps)

    @pl.when(is_first)
    def _zero():
        psb_ref[...] = jnp.zeros_like(psb_ref)

    # one ARB slot × one B row panel -> lb partial products, scattered to
    # their precomputed positions in the output row.  Pad steps (col == -1)
    # zero the scalar; dead panel lanes carry pos == -1 and match nothing.
    live = step_col[base + s] >= 0
    a = jnp.where(live, a_val_ref[...].astype(jnp.float32), 0.0)
    contrib = a * b_row_ref[...].astype(jnp.float32)        # (1, lb)
    # onehot^T[j, u] = (pos[u] == j): built with positions along lanes, so
    # no relayout; the "NT" contraction over u lands partial u on column
    # pos[u].  Positions within one B row are distinct, so each PSB column
    # receives at most one partial per step and HIGHEST keeps it exact.
    onehot_t = (jax.lax.broadcasted_iota(jnp.int32, (lc, lb), 0)
                == pos_ref[...]).astype(jnp.float32)        # (lc, lb)
    psb_ref[...] += jax.lax.dot_general(
        contrib, onehot_t, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                 # (1, lc)

    @pl.when(is_last)
    def _flush():
        out_ref[...] = psb_ref[...].astype(out_ref.dtype)


def _pad_last(x: jax.Array, width: int, fill) -> jax.Array:
    pad = width - x.shape[-1]
    if not pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)],
                   constant_values=fill)


def maple_spgemm_pallas(
    a_val_flat: jax.Array,   # (m * la, 1) ELL-regularized A values, 0 dead
    b_ell_val: jax.Array,    # (k, lb) ELL-regularized B row values, 0 dead
    scatter_pos: jax.Array,  # (m * la, lb) int32 PSB positions, -1 dead
    order: jax.Array,        # (n_lanes, steps) int32 flat A slots
    step_row: jax.Array,     # (n_lanes, steps) int32, pads -> m
    step_col: jax.Array,     # (n_lanes, steps) int32, -1 pads
    *,
    m: int,
    lc: int,
    interpret: bool,
) -> jax.Array:
    """Raw plan-driven kernel (no pattern logic — see ops.maple_spgemm).

    Returns ``(m + 1, lc)`` ELL output-row values — row ``m`` is the
    sacrificial pad-step target, sliced off by the wrapper, which also
    compacts rows into the padded-CSR value vector using the plan's
    pattern.  Accumulation is f32 regardless of the value dtype.

    TPU layout: every per-step operand is one row of a gathered array.
    Each array is viewed as ``(rows, 1, width)`` so a block's last two
    dims equal the array's (Mosaic's tiling rule), and the panel widths
    ``lb``/``lc`` are zero-padded to whole 128-lane vregs (pad B values
    are 0, pad positions -1 — they contribute nothing and the pad output
    columns are sliced off).
    """
    _, lb = b_ell_val.shape
    lanes, steps = order.shape
    lbp = -(-lb // 128) * 128
    lcp = -(-lc // 128) * 128

    flat_order = order.reshape(-1).astype(jnp.int32)
    flat_row = step_row.reshape(-1).astype(jnp.int32)
    flat_col = step_col.reshape(-1).astype(jnp.int32)
    a3 = a_val_flat.reshape(-1, 1, 1)
    b3 = _pad_last(b_ell_val, lbp, 0)[:, None, :]
    pos3 = _pad_last(scatter_pos.astype(jnp.int32), lbp, -1)[:, None, :]

    kernel = functools.partial(_kernel, steps=steps, lb=lbp, lc=lcp)
    out = pl.pallas_call(
        kernel,
        name="maple_spgemm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(lanes, steps),
            in_specs=[
                pl.BlockSpec(
                    (None, 1, 1),
                    lambda l, s, o, r, c: (o[l * steps + s], 0, 0)),
                # pad steps clamp their col to 0: a panel is still fetched
                # (pads cost bandwidth, not correctness) but the zeroed
                # scalar annihilates it.
                pl.BlockSpec(
                    (None, 1, lbp),
                    lambda l, s, o, r, c: (
                        jnp.maximum(c[l * steps + s], 0), 0, 0)),
                pl.BlockSpec(
                    (None, 1, lbp),
                    lambda l, s, o, r, c: (o[l * steps + s], 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (None, 1, lcp),
                lambda l, s, o, r, c: (r[l * steps + s], 0, 0)),
            scratch_shapes=[pltpu.VMEM((1, lcp), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m + 1, 1, lcp), a_val_flat.dtype),
        interpret=interpret,
        # lanes write disjoint real rows but share the sacrificial pad row,
        # so the lane axis stays "arbitrary" rather than "parallel".
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
    )(flat_order, flat_row, flat_col, a3, b3, pos3)
    return out[:, 0, :lc]
