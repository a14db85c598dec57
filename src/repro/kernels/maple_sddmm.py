"""Pattern-sampled dense-dense products (SDDMM) — the ``dA`` half of the
Maple VJPs.

The backward of a row-wise product w.r.t. its *sparse* operand never needs
a dense gradient: for ``C = A @ B``,

    dA[i, k] = Σ_j dC[i, j] · B[k, j]        restricted to (i, k) ∈ nnz(A)

— a sampled product that touches exactly the coordinates A's (fixed)
pattern names.  Both kernels here gather only those coordinates and write
one output slot per live non-zero; a dense ``dA`` is never materialized
(structure/metadata carries no gradient — only payloads do).

* :func:`maple_sddmm_bsr_pallas` — block granularity, the ``maple_spmm``
  VJP.  Grid ``(n_blocks, G, N/bn)`` with the block index **outermost**:
  the per-block ``(bm, bk)`` f32 PSB accumulates over the batch and
  output-tile axes contiguously (zero on the first ``(g, j)`` visit, flush
  once at the last), mirroring how the forward kernels detect row runs.
  Each step fetches the ``dC`` row-tile the block's row names and the
  ``B`` row-panel its column names — the same scalar-prefetch metadata
  walk as the forward, with dC standing in for the output.
* :func:`maple_sddmm_csr_pallas` — element granularity, plan-driven, the
  ``maple_spgemm`` VJP.  Same ``(n_lanes, steps)`` grid as the numeric
  SpGEMM kernel and the *same* ``scatter_pos`` map run in reverse: where
  the forward scattered partial ``u`` of A-slot ``s`` into position
  ``pos[s, u]`` of its output row, the backward gathers ``dC`` from those
  positions and contracts with the B row panel —
  ``dA[s] = Σ_u B[k', u] · dC_row[pos[s, u]]`` (dead positions are ``-1``
  and match nothing).  Pad steps write a sacrificial output slot so idle
  lanes can never clobber a real gradient.

**Partitioned backward** (``kernels.partition`` plans): the block SDDMM
follows the *forward's* row ownership.  :func:`sddmm_shard_meta` reindexes
the global block pattern through a partitioned plan's payload gather maps
into per-shard ``(D, slot_cap)`` row/col metadata; each shard then runs
:func:`maple_sddmm_bsr_pallas` on only the blocks it owns, with its dC
row-tiles fetched from the (replicated-over-shard) cotangent and — on a
2-D mesh — its B row-panels sliced along the column axis, the per-panel
partials completed by a ``psum`` over that axis (the one collective the
2-D layout needs: N is the SDDMM's *contraction* axis, so column panels
sum rather than concatenate).  The shard-axis merge back to global block
slots is pure placement — gather maps are disjoint by construction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.accum import tile_bounds


def sddmm_shard_meta(gather: np.ndarray, gather_live: np.ndarray,
                     block_row: np.ndarray, block_col: np.ndarray,
                     ) -> tuple[np.ndarray, np.ndarray]:
    """Per-shard block metadata for the partitioned dA SDDMM.

    ``gather``/``gather_live`` are a ``PartitionedSpmmPlan``'s payload
    maps (``(D, slot_cap)``: global block slot per shard-local slot);
    ``block_row``/``block_col`` the *global* pattern.  Returns
    ``(sd_row, sd_col)`` of shape ``(D, slot_cap)``: the rows/cols each
    shard's local slots name, with dead slots clamped to row 0 / col -1 —
    exactly the pad convention :func:`maple_sddmm_bsr_pallas` masks on,
    so a per-shard kernel call computes zeros for them.
    """
    gat = np.asarray(gather)
    live = np.asarray(gather_live)
    br = np.asarray(block_row)[gat]
    bc = np.asarray(block_col)[gat]
    sd_row = np.where(live, br, 0).astype(np.int32)
    sd_col = np.where(live, bc, -1).astype(np.int32)
    return sd_row, sd_col


# --------------------------------------------------------------------------
# block granularity (BSR pattern × two dense operands)
# --------------------------------------------------------------------------

def _bsr_kernel(
    # scalar prefetch
    block_row,          # (n_blocks,) int32, pads -> last real row
    block_col,          # (n_blocks,) int32, -1 on pads
    # VMEM operands
    dc_ref,             # (1, bm, bn) dC tile of this block's row
    b_ref,              # (1, bk, bn) B row-panel of this block's column
    out_ref,            # (1, bm, bk) — dA block (revisited across g, j)
    # scratch
    psb_ref,            # (bm, bk) f32 accumulator
    *,
    n_g: int,
    n_j: int,
):
    s = pl.program_id(0)
    g = pl.program_id(1)
    j = pl.program_id(2)

    is_first, is_last = tile_bounds(g, j, n_g, n_j)

    @pl.when(is_first)
    def _zero():
        psb_ref[...] = jnp.zeros_like(psb_ref)

    # (bm, bn) · (bk, bn) contracted over the tile axis -> (bm, bk).
    # Pads clamp their column to 0, so a panel is still fetched; unlike the
    # forward (where a zero payload annihilates it) the operands here are
    # dense, so the pad contribution is masked explicitly.
    live = block_col[s] >= 0
    contrib = jax.lax.dot_general(
        dc_ref[0], b_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    psb_ref[...] += jnp.where(live, contrib, 0.0)

    @pl.when(is_last)
    def _flush():
        out_ref[0] = psb_ref[...]


def maple_sddmm_bsr_pallas(
    dc: jax.Array,          # (G, M, N) output cotangent
    b_dense: jax.Array,     # (G, K, N) forward dense operand
    block_row: jax.Array,   # (n_blocks,) int32
    block_col: jax.Array,   # (n_blocks,) int32, -1 pads
    *,
    bm: int,
    bk: int,
    bn: int = 128,
    interpret: bool,
) -> jax.Array:
    """``dA.blocks = (dC @ B^T)`` sampled at the block pattern.

    Returns ``(n_blocks, bm, bk)`` **f32** block gradients (pad slots are
    written as zeros via the in-kernel mask; the ops wrapper re-masks on
    ``block_col >= 0`` out of defensiveness and casts).  Raw kernel — the
    wrapper owns padding and dtype policy.
    """
    g, m, n = dc.shape
    _, k, _ = b_dense.shape
    n_blocks = block_row.shape[0]
    if n % bn:
        raise ValueError(f"N={n} not divisible by bn={bn}")
    if m % bm or k % bk:
        raise ValueError(f"({m},{k}) not divisible by block ({bm},{bk})")
    grid = (n_blocks, g, n // bn)

    kernel = functools.partial(_bsr_kernel, n_g=g, n_j=n // bn)
    return pl.pallas_call(
        kernel,
        name="maple_sddmm_bsr",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bm, bn),
                             lambda s, gi, j, br, bc: (gi, br[s], j)),
                # pads clamp their column in the *index map* only — the
                # kernel body still sees -1 and masks the contribution
                pl.BlockSpec((1, bk, bn),
                             lambda s, gi, j, br, bc: (
                                 gi, jnp.maximum(bc[s], 0), j)),
            ],
            out_specs=pl.BlockSpec((1, bm, bk),
                                   lambda s, gi, j, br, bc: (s, 0, 0)),
            scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_blocks, bm, bk), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
    )(jnp.asarray(block_row, jnp.int32),
      jnp.asarray(block_col, jnp.int32), dc, b_dense)


# --------------------------------------------------------------------------
# element granularity (plan-driven, the SpGEMM dA)
# --------------------------------------------------------------------------

def _csr_kernel(
    # scalar prefetch, flattened (n_lanes * steps,)
    order,            # A ELL slot per step; pads redirected by index maps
    step_row,         # output row per step; pads -> sacrificial dC row m
    step_col,         # B row per step, -1 on pads
    # VMEM operands
    dc_row_ref,       # (1, lc) dC values of this step's output row (ELL)
    b_row_ref,        # (1, lb) compressed B row panel
    pos_ref,          # (1, lb) int32 forward scatter positions, -1 dead
    out_ref,          # (1, 1) — dA of this step's A slot
    *,
    steps: int,
    lb: int,
    lc: int,
):
    l = pl.program_id(0)
    s = pl.program_id(1)
    base = l * steps

    live = step_col[base + s] >= 0
    pos = pos_ref[0]                                        # (lb,) int32
    onehot = (pos[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (lb, lc), 1)).astype(jnp.float32)
    # gather dC from the forward's scatter positions: dcg[u] = dC_row[pos[u]]
    dcg = jnp.dot(onehot, dc_row_ref[0].astype(jnp.float32),
                  preferred_element_type=jnp.float32)        # (lb,)
    val = jnp.dot(b_row_ref[0].astype(jnp.float32), dcg,
                  preferred_element_type=jnp.float32)
    out_ref[0, 0] = jnp.where(live, val, 0.0)


def maple_sddmm_csr_pallas(
    dc_ell: jax.Array,       # (m + 1, lc) dC row values, sacrificial row m
    b_ell_val: jax.Array,    # (k, lb) ELL-regularized B rows, 0 dead
    scatter_pos: jax.Array,  # (m * la, lb) int32 forward positions, -1 dead
    order: jax.Array,        # (n_lanes, steps) int32 flat A slots
    step_row: jax.Array,     # (n_lanes, steps) int32, pads -> m
    step_col: jax.Array,     # (n_lanes, steps) int32, -1 pads
    *,
    n_slots: int,            # m * la
    interpret: bool,
) -> jax.Array:
    """``dA`` per A ELL slot, sampled through the forward plan.

    Returns ``(n_slots + 1, 1)`` f32 — one gradient per A ELL slot plus
    the sacrificial slot pad steps write (sliced off by the wrapper, which
    also maps live slots back onto the padded-CSR value vector).  Slots the
    plan never schedules (dead ELL lanes) are never written; the wrapper
    must gather only live ones.
    """
    _, lb = b_ell_val.shape
    lc = dc_ell.shape[1]
    lanes, steps = order.shape

    flat_order = order.reshape(-1).astype(jnp.int32)
    flat_row = step_row.reshape(-1).astype(jnp.int32)
    flat_col = step_col.reshape(-1).astype(jnp.int32)

    kernel = functools.partial(_csr_kernel, steps=steps, lb=lb, lc=lc)
    return pl.pallas_call(
        kernel,
        name="maple_sddmm_csr",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(lanes, steps),
            in_specs=[
                # pad steps point step_row at the sacrificial dC row m
                pl.BlockSpec(
                    (1, lc),
                    lambda l, s, o, r, c: (r[l * steps + s], 0)),
                pl.BlockSpec(
                    (1, lb),
                    lambda l, s, o, r, c: (
                        jnp.maximum(c[l * steps + s], 0), 0)),
                pl.BlockSpec(
                    (1, lb),
                    lambda l, s, o, r, c: (o[l * steps + s], 0)),
            ],
            # pad steps (col == -1) are redirected to the sacrificial
            # output slot n_slots — writing 0 at `order`'s placeholder 0
            # would clobber a real slot's gradient.
            out_specs=pl.BlockSpec(
                (1, 1),
                lambda l, s, o, r, c, _n=n_slots: (
                    jnp.where(c[l * steps + s] < 0, _n, o[l * steps + s]),
                    0)),
            scratch_shapes=[],
        ),
        out_shape=jax.ShapeDtypeStruct((n_slots + 1, 1), jnp.float32),
        interpret=interpret,
        # lanes write disjoint live slots but share the sacrificial one
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
    )(flat_order, flat_row, flat_col, dc_ell, b_ell_val, scatter_pos)
