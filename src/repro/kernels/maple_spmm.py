"""Maple SpMM Pallas kernel: block-CSR ``A`` × dense ``B`` → dense ``C``.

This is the TPU-granularity realization of the Maple PE (DESIGN §2-B/§3):

* the *unit of non-zero* is a ``(bm, bk)`` block — the MXU's natural grain —
  instead of a scalar; ``block_col`` plays the role of ``col_id``;
* the **ARB** is the VMEM tile of the current A block (streamed by the grid);
* the **BRB** is the VMEM tile of the B row-panel selected by the block's
  column id — fetched through a scalar-prefetch-driven ``index_map`` so that
  *zero blocks are never moved* (the CSR-metadata walk of the paper, done by
  the Pallas pipeline machinery);
* the **PSB** is a ``(bm, bn)`` f32 VMEM scratch accumulator that is revisited
  across consecutive grid steps of the same block-row and leaves the PE
  exactly once per output tile — partial sums never leave the PE, which is
  the paper's entire energy argument restated for the HBM↔VMEM boundary.

Padding protocol (see ``core.csr.BlockCSR``): padded slots carry
``block_col = -1`` and a zero payload, and their ``block_row`` points at the
last real block-row, so they are harmless accumulations into a tile that is
flushed anyway.

Three kernels live here (the wrappers in ops.py pick one):

* :func:`maple_spmm_batched_pallas` — the naive walk lifted to a **3D grid**
  ``(G, N/bn, n_blocks)`` over a batch of dense right-hand sides sharing
  one A structure (one unsplit block-row after the next — row-atomic;
  kept as the ``naive`` schedule and the jit-friendly path);
* :func:`maple_spmm_planned_pallas` — the load-balanced **fused "rmw"**
  grid ``(G, N/bn, n_lanes, steps)`` driven by a
  ``kernels.schedule.SpmmPlan``: lanes are a *sequential* ("arbitrary")
  grid dimension and every (lane, row) PSB run flushes straight into the
  single ``(G, M, N)`` f32 output.  The first lane to flush a row
  overwrites; later lanes (chunks of a split row) read-modify-write,
  merging in f32 — the cross-lane reduction happens **here**, not in an
  epilogue, so no ``(G, L, M, N)`` lane buffer ever exists;
* :func:`maple_spmm_compact_pallas` — the fused **"compact"** layout for
  pipelines that need the lane axis parallel (revisited output tiles
  cannot be re-fetched there): lanes flush into compact per-lane tiles
  ``(G, L, r_max·bm, N)`` sized by the plan's ``written`` map (``r_max``
  = most rows any lane flushes, typically ≪ M/bm), and the ops wrapper
  merges them with one scatter-add.

Both fused layouts keep partials in f32 until the merge, so a split row
rounds to the output dtype exactly once — like the naive
single-accumulator walk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.accum import run_bounds


# --------------------------------------------------------------------------
# batched 3D grid: one A structure × G dense right-hand sides
# --------------------------------------------------------------------------

def _batched_kernel(
    block_row,          # (n_blocks,) int32 scalar prefetch
    block_col,          # (n_blocks,) int32, pads clamped by caller
    a_blk_ref,          # (1, bm, bk)
    b_panel_ref,        # (1, bk, bn) — panel of B[g]
    out_ref,            # (1, bm, bn) — tile of C[g]
    psb_ref,            # (bm, bn) f32
    *,
    n_blocks: int,
):
    s = pl.program_id(2)
    _, is_first, is_last = run_bounds(block_row, 0, s, n_blocks)

    @pl.when(is_first)
    def _zero():
        psb_ref[...] = jnp.zeros_like(psb_ref)

    psb_ref[...] += jnp.dot(
        a_blk_ref[0], b_panel_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(is_last)
    def _flush():
        out_ref[0] = psb_ref[...].astype(out_ref.dtype)


def maple_spmm_batched_pallas(
    blocks: jax.Array,      # (n_blocks, bm, bk)
    block_row: jax.Array,   # (n_blocks,) int32
    block_col: jax.Array,   # (n_blocks,) int32
    b_dense: jax.Array,     # (G, K, N)
    *,
    m: int,
    bn: int = 128,
    interpret: bool,
) -> jax.Array:
    """Naive-schedule SpMM over a batch of RHS (raw; padding in ops.py)."""
    n_blocks, bm, bk = blocks.shape
    g, k, n = b_dense.shape
    if n % bn:
        raise ValueError(f"N={n} not divisible by bn={bn}")
    if m % bm or k % bk:
        raise ValueError(f"({m},{k}) not divisible by block ({bm},{bk})")
    grid = (g, n // bn, n_blocks)
    safe_col = jnp.maximum(block_col, 0)

    kernel = functools.partial(_batched_kernel, n_blocks=n_blocks)
    return pl.pallas_call(
        kernel,
        name="maple_spmm_batched",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bm, bk), lambda gi, j, s, br, bc: (s, 0, 0)),
                pl.BlockSpec((1, bk, bn),
                             lambda gi, j, s, br, bc: (gi, bc[s], j)),
            ],
            out_specs=pl.BlockSpec((1, bm, bn),
                                   lambda gi, j, s, br, bc: (gi, br[s], j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((g, m, n), b_dense.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(block_row, safe_col, blocks, b_dense)


# --------------------------------------------------------------------------
# planned fused "rmw" grid: sequential lanes, in-kernel cross-lane merge
# --------------------------------------------------------------------------

def _planned_rmw_kernel(
    order,              # (L*S,) int32 scalar prefetch: gather into blocks
    step_row,           # (L*S,) int32: output block-row per step
    step_col,           # (L*S,) int32: B block-col per step, -1 on pads
    step_acc,           # (L*S,) int32: 1 -> flush accumulates, 0 -> inits
    a_blk_ref,          # (1, bm, bk) block selected by order
    b_panel_ref,        # (1, bk, bn) panel selected by step_col
    out_ref,            # (1, bm, bn) — (g, row, j) tile of C, revisited
    psb_ref,            # (bm, bn) f32 — the PSB
    *,
    steps: int,
):
    l = pl.program_id(2)
    s = pl.program_id(3)
    base = l * steps
    _, is_first, is_last = run_bounds(step_row, base, s, steps)

    @pl.when(is_first)
    def _zero():
        psb_ref[...] = jnp.zeros_like(psb_ref)

    # pad steps (col == -1) re-fetch block 0 / panel 0 but contribute 0
    live = step_col[base + s] >= 0
    a = jnp.where(live, a_blk_ref[0], jnp.zeros_like(a_blk_ref[0]))
    psb_ref[...] += jnp.dot(
        a, b_panel_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(is_last)
    def _flush():
        # the cross-lane merge: the row's first flusher (plan-designated)
        # overwrites whatever the tile held, later flushers of a split row
        # read the previous flush back and add in f32.  Phantom runs (idle
        # lanes) carry acc = 1 and a zero PSB — they can't clobber anything.
        prev = jnp.where(step_acc[base + s] > 0, out_ref[0], 0.0)
        out_ref[0] = prev + psb_ref[...]


def maple_spmm_planned_pallas(
    blocks: jax.Array,      # (n_blocks, bm, bk)
    order: jax.Array,       # (L, S) int32
    step_row: jax.Array,    # (L, S) int32
    step_col: jax.Array,    # (L, S) int32, -1 pads
    step_acc: jax.Array,    # (L, S) int32, 1 where a flush accumulates
    b_dense: jax.Array,     # (G, K, N)
    *,
    m: int,
    bn: int = 128,
    interpret: bool,
) -> jax.Array:
    """Plan-driven fused SpMM.  Returns the merged ``(G, M, N)`` output in
    **f32** — partials of a split row are combined at full accumulator
    precision inside the kernel (first flush overwrites, later flushes
    read-modify-write), so the planned schedule rounds once exactly like
    the naive walk.  The lane axis is *sequential* ("arbitrary"): flush
    order across lanes is the plan's lane order, which is what makes the
    plan's ``step_acc`` initializer flags exact.  Rows no lane ever
    flushes are left untouched — the ops wrapper zero-masks them with the
    plan's cached ``row_mask`` (raw kernel — no padding/masking here)."""
    if not interpret:
        # the accumulating flush reads a *previously flushed* output tile
        # back at a non-consecutive grid revisit.  The interpreter's
        # per-step block load/store guarantees that; Mosaic's write-only
        # output pipelining does not — refuse loudly rather than compute
        # garbage split rows on a compiled target.
        raise NotImplementedError(
            "the rmw fused layout requires interpret mode (revisited "
            "output tiles must be re-fetched); build the plan with "
            "fused='compact' for compiled TPU targets")
    n_blocks, bm, bk = blocks.shape
    g, k, n = b_dense.shape
    lanes, steps = order.shape
    if n % bn:
        raise ValueError(f"N={n} not divisible by bn={bn}")
    if m % bm or k % bk:
        raise ValueError(f"({m},{k}) not divisible by block ({bm},{bk})")
    grid = (g, n // bn, lanes, steps)

    flat_order = order.reshape(-1).astype(jnp.int32)
    flat_row = step_row.reshape(-1).astype(jnp.int32)
    flat_col = step_col.reshape(-1).astype(jnp.int32)
    flat_acc = step_acc.reshape(-1).astype(jnp.int32)

    kernel = functools.partial(_planned_rmw_kernel, steps=steps)
    return pl.pallas_call(
        kernel,
        name="maple_spmm_rmw",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, bm, bk),
                    lambda gi, j, l, s, o, r, c, a: (o[l * steps + s], 0, 0)),
                pl.BlockSpec(
                    (1, bk, bn),
                    lambda gi, j, l, s, o, r, c, a: (
                        gi, jnp.maximum(c[l * steps + s], 0), j)),
            ],
            out_specs=pl.BlockSpec(
                (1, bm, bn),
                lambda gi, j, l, s, o, r, c, a: (gi, r[l * steps + s], j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((g, m, n), jnp.float32),
        interpret=interpret,
        # lanes merge into shared output tiles -> sequential, NOT parallel;
        # the batch and output-tile axes stay parallel (disjoint tiles)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
        ),
    )(flat_order, flat_row, flat_col, flat_acc, blocks, b_dense)


# --------------------------------------------------------------------------
# planned fused "compact" grid: parallel lanes, plan-sized flush tiles
# --------------------------------------------------------------------------

def _planned_compact_kernel(
    order,              # (L*S,) int32 scalar prefetch: gather into blocks
    step_row,           # (L*S,) int32: output block-row per step
    step_col,           # (L*S,) int32: B block-col per step, -1 on pads
    flush_slot,         # (L*S,) int32: compact slot this run flushes to
    a_blk_ref,          # (1, bm, bk) block selected by order
    b_panel_ref,        # (1, bk, bn) panel selected by step_col
    out_ref,            # (1, 1, bm, bn) — (g, lane, slot, j) compact tile
    psb_ref,            # (bm, bn) f32 — this lane's PSB
    *,
    steps: int,
):
    l = pl.program_id(1)
    s = pl.program_id(3)
    base = l * steps
    _, is_first, is_last = run_bounds(step_row, base, s, steps)

    @pl.when(is_first)
    def _zero():
        psb_ref[...] = jnp.zeros_like(psb_ref)

    live = step_col[base + s] >= 0
    a = jnp.where(live, a_blk_ref[0], jnp.zeros_like(a_blk_ref[0]))
    psb_ref[...] += jnp.dot(
        a, b_panel_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(is_last)
    def _flush():
        out_ref[0, 0] = psb_ref[...]


def maple_spmm_compact_pallas(
    blocks: jax.Array,      # (n_blocks, bm, bk)
    order: jax.Array,       # (L, S) int32
    step_row: jax.Array,    # (L, S) int32
    step_col: jax.Array,    # (L, S) int32, -1 pads
    flush_slot: jax.Array,  # (L, S) int32 compact flush slots
    b_dense: jax.Array,     # (G, K, N)
    *,
    r_max: int,
    bn: int = 128,
    interpret: bool,
) -> jax.Array:
    """Plan-driven fused SpMM, compact-flush layout.  Returns per-lane
    flush tiles ``(G, L, r_max·bm, N)`` in **f32**, sized by the plan's
    ``written`` map — lane ``l``'s ``t``-th flushed row lands in slot
    ``t`` (``plan.slot_row`` inverts the map; dead slots are never
    written).  The ops wrapper scatter-adds slots into the ``(G, M, N)``
    result in f32 — the cross-lane merge — and only then casts.  Lanes
    write disjoint slices, so the lane axis stays parallel (raw kernel —
    no padding/masking logic here)."""
    n_blocks, bm, bk = blocks.shape
    g, k, n = b_dense.shape
    lanes, steps = order.shape
    if n % bn:
        raise ValueError(f"N={n} not divisible by bn={bn}")
    if k % bk:
        raise ValueError(f"K={k} not divisible by block k={bk}")
    grid = (g, lanes, n // bn, steps)

    flat_order = order.reshape(-1).astype(jnp.int32)
    flat_row = step_row.reshape(-1).astype(jnp.int32)
    flat_col = step_col.reshape(-1).astype(jnp.int32)
    flat_slot = flush_slot.reshape(-1).astype(jnp.int32)

    kernel = functools.partial(_planned_compact_kernel, steps=steps)
    return pl.pallas_call(
        kernel,
        name="maple_spmm_compact",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, bm, bk),
                    lambda gi, l, j, s, o, r, c, f: (o[l * steps + s], 0, 0)),
                pl.BlockSpec(
                    (1, bk, bn),
                    lambda gi, l, j, s, o, r, c, f: (
                        gi, jnp.maximum(c[l * steps + s], 0), j)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, bm, bn),
                lambda gi, l, j, s, o, r, c, f: (gi, l, f[l * steps + s], j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((g, lanes, r_max * bm, n),
                                       jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
    )(flat_order, flat_row, flat_col, flat_slot, blocks, b_dense)
