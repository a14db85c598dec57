"""Public jit'd entry points for the Maple kernels.

These wrappers own everything that is *not* the kernel: metadata
construction, padding to tile multiples, empty-row masking, format
conversion, and the interpret-mode switch — the only place it is decided:
interpret on any backend but the TPU (the CPU test suite), compiled
through Mosaic on a TPU.

API:
  * :func:`maple_spmm`       — BlockCSR A × dense B      (MXU grain)
  * :func:`maple_spgemm`     — CSR A × CSR B → padded CSR (two-phase
                               symbolic/numeric; the paper's sparse-output
                               row-wise product)
  * :func:`maple_spmspm`     — padded-CSR A × CSR/dense B → dense
                               (legacy; routes through maple_spgemm for
                               CSR B)
  * :func:`moe_expert_gemm`  — expert-sorted tokens × stacked expert weights
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import formats
from repro.core.csr import CSR, BlockCSR, grow_nnz_max
from repro.distributed.sharding import partition_mesh
from repro.kernels.block_attn import (block_attention_pallas,
                                      local_window_kv_map)
from repro.kernels.maple_sddmm import (maple_sddmm_bsr_pallas,
                                       maple_sddmm_csr_pallas,
                                       sddmm_shard_meta)
from repro.kernels.maple_spgemm import maple_spgemm_pallas
from repro.kernels.maple_spmm import (maple_spmm_batched_pallas,
                                      maple_spmm_compact_pallas,
                                      maple_spmm_planned_pallas)
from repro.kernels.maple_spmspm import maple_spmspm_pallas
from repro.kernels.moe_gemm import moe_gemm_pallas
from repro.kernels.partition import (PartitionedSpmmPlan,
                                     plan_partitioned_spmm,
                                     plan_partitioned_spmm_vjp)
from repro.kernels.reorder import apply_reorder
from repro.kernels.schedule import (SpgemmPlan, SpmmPlan, SpmmTrainPlan,
                                    plan_spgemm, plan_spmm, plan_spmm_vjp)


def _float0(x):
    """Symbolic-zero cotangent for integer (metadata) primals."""
    return np.zeros(x.shape, jax.dtypes.float0)


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _validate_enabled() -> bool:
    """``MAPLE_VALIDATE=1`` arms operand pad-contract checks at the kernel
    entry points.  Off by default: the checks read values on the host, so
    they would force a device sync (and break under jit) in production —
    the gate is for vetting checkpoint-loaded or hand-assembled operands
    in tests/CI, where every call is eager anyway."""
    return os.environ.get("MAPLE_VALIDATE", "0") not in ("", "0")


def _maybe_validate(*operands) -> None:
    """Run ``check_pad_contract`` on each CSR/BlockCSR operand when the
    ``MAPLE_VALIDATE`` gate is armed and the metadata is concrete (traced
    operands are skipped — their producers were validated eagerly)."""
    if not _validate_enabled():
        return
    for op in operands:
        if isinstance(op, CSR):
            if not _has_traced_metadata(op.value, op.col_id, op.row_ptr):
                op.check_pad_contract()
        elif isinstance(op, (BlockCSR, formats.EllPack,
                             formats.BitmapBlocked)):
            if not _has_traced_metadata(
                    *jax.tree_util.tree_leaves(op)):
                op.check_pad_contract()


# --------------------------------------------------------------------------
# BSR × dense
# --------------------------------------------------------------------------

def _pad_cols(b: jax.Array, bn: int) -> tuple[jax.Array, int]:
    """Zero-pad the last axis up to a multiple of ``bn``."""
    n = b.shape[-1]
    pad = (-n) % bn
    if pad:
        width = [(0, 0)] * (b.ndim - 1) + [(0, pad)]
        b = jnp.pad(b, width)
    return b, n


def maple_spmm(a: "formats.BlockFormat", b_dense: jax.Array, *,
               bn: int = 128,
               schedule: str = "balanced", n_lanes: int = 8,
               chunk: int | None = None, n_shards: int | None = None,
               n_col_shards: int | None = None,
               plan: SpmmPlan | SpmmTrainPlan | PartitionedSpmmPlan
               | None = None,
               reorder: bool | str = False,
               interpret: bool | None = None) -> jax.Array:
    """C = A_bsr @ B with the Maple block dataflow.  Differentiable.

    ``a`` is any blocked :class:`~repro.core.formats.SparseFormat` —
    ``BlockCSR``, ``EllPack`` or ``BitmapBlocked``.  Non-BlockCSR
    operands lower onto the canonical metadata via
    ``core.formats.as_block_csr`` at entry (host pattern walk + one
    traced payload gather, never a dense round trip), so all three
    formats execute bit-identically through the same kernels.

    ``b_dense`` is one ``(K, N)`` right-hand side or a batch ``(G, K, N)``
    of them sharing A's structure (the inference shape — one kernel launch,
    no host loop over the batch).  ``N`` may be ragged; it is zero-padded to
    the ``bn`` tile internally and sliced back.

    ``schedule`` selects the execution plan:

    * ``"balanced"`` (default) — heavy block-rows split into ≤ ``chunk``
      sized row-chunks LPT-packed onto ``n_lanes`` lanes (see
      ``kernels.schedule``); removes the heaviest-row bound that
      ``core.maple.maple_pe_cycles`` predicts for row-atomic walks.
    * ``"row_atomic"`` — whole rows pinned to lanes (MatRaptor baseline;
      same kernel, different plan).
    * ``"naive"`` — the seed single-stream walk in BlockCSR construction
      order.  Metadata stays traced, so this path always composes with
      jit; the planned schedules read the (host-static) pattern at call
      time, so under jit they require a prebuilt ``plan``.
    * ``"partitioned"`` — block-rows LPT-split across ``n_shards``
      devices (default: every ``jax.local_devices()``), one shard-local
      plan each, executed with ``shard_map`` over the
      ``distributed.sharding.partition_mesh`` axis (sparse operand and
      plan metadata sharded along ``"shard"``; the dense operand is
      replicated at ``n_col_shards=1`` or panel-split along the second
      ``"col"`` mesh axis when ``n_col_shards > 1``; row-offset
      epilogue reassembling the disjoint row slices — see
      ``kernels.partition``).  With fewer devices than the
      ``n_shards × n_col_shards`` request the same plan runs as a
      stacked single-device loop, bit-identically.

    Pass a prebuilt ``plan`` (``kernels.schedule.plan_spmm`` or, for
    training, ``plan_spmm_vjp``) to amortize planning across calls and to
    jit the planned path — serving builds it once per weight and closes a
    jitted call over it.  ``plan="auto"`` autotunes instead of planning
    with the hand-tuned defaults: a budgeted ``kernels.autotune``
    search over the schedule knob space, memoized per sparsity pattern
    (repeat calls on a seen pattern reuse the cached winner).  Eager
    only — the search walks host metadata, so under jit run it outside
    the trace and close the jitted call over the returned plan.  With
    ``plan="auto"``, ``n_shards`` bounds the searched device axis rather
    than pinning it (the search may conclude one device wins).

    ``reorder`` rides ``plan="auto"`` only: it is the autotuner's
    similarity-based row-reordering knob (``kernels.reorder``) —
    ``True`` forces the permuted schedule, ``"auto"`` lets the surrogate
    accept or reject it, ``False`` (default) disables it.  A winning
    reordered plan carries its :class:`~repro.kernels.reorder.RowReorder`;
    this wrapper permutes A's block-rows before the kernel and inverts
    the permutation on the output rows after it, so results stay equal to
    the unpermuted execution (see ``kernels/README.md`` for the exact
    bitwise contract).  Prebuilt reordered plans
    (``kernels.reorder.plan_reordered_spmm``) are accepted through
    ``plan=`` like any other.

    **Autodiff** (``jax.custom_vjp``): ``dB = A^T @ dC`` runs the same
    planned kernel on the transposed block pattern, and ``dA`` is the
    pattern-sampled ``(dC @ B^T)|_{nnz(A)}`` block SDDMM
    (``kernels.maple_sddmm``) — dense ``dA`` is never materialized and
    metadata carries no gradient.  The kernel backward needs host
    pattern metadata: it is armed whenever the metadata is concrete
    (eager) or an :class:`~repro.kernels.schedule.SpmmTrainPlan` is
    passed (the jit path — the transpose-side plan rides the forward
    plan).  A traced naive call without a train plan falls back to a
    jnp gather/scatter backward at block granularity (same contraction,
    no kernel, O(nnz_blocks × bn) gather buffers).

    **Fused output dataflow**: the cross-lane reduction that merges
    chunks of a split row happens *inside the planned kernel* (see
    ``kernels.maple_spmm`` and ``SpmmPlan.fused``) — no full ``(G,
    lanes, M, N)`` per-lane buffer is materialized, forward or backward.
    The default (and, compiled, the only) layout is compact, whose
    flush tiles are bounded by the plan's ``written`` map
    (``G·L·r_max·bm·N`` — typically ≪ the retired buffer, equal to it
    only in the degenerate worst case where some lane flushes every
    row); the interpret-only rmw layout (``fused="rmw"``) keeps just the
    ``(G, M, N)`` result.

    Empty block-rows never flush a PSB; their output tiles are explicitly
    zero-masked (naive path: from row_ptr; rmw planned path: from the
    plan's cached ``row_mask``; the compact path's scatter-add leaves
    them zero by construction).
    """
    if interpret is None:
        interpret = _default_interpret()
    _maybe_validate(a)
    if not isinstance(a, BlockCSR):
        # ELL / bitmap operands lower onto the canonical metadata here —
        # one host pattern walk plus one traced payload gather
        a = formats.as_block_csr(a)
    if schedule not in ("balanced", "row_atomic", "naive", "partitioned"):
        raise ValueError(f"unknown schedule {schedule!r}")
    if schedule == "naive" and plan is not None:
        raise ValueError("schedule='naive' does not execute a plan; "
                         "drop `plan` or pick a planned schedule")
    if reorder is not False and not (isinstance(plan, str)
                                     and plan == "auto"):
        raise ValueError(
            "reorder is an autotune knob and requires plan='auto'; to "
            "run a reordered schedule directly, prebuild it with "
            "kernels.reorder.plan_reordered_spmm and pass it as `plan`")
    auto_planned = False
    if isinstance(plan, str):
        if plan != "auto":
            raise ValueError(f"unknown plan {plan!r}; pass a prebuilt plan "
                             f"or 'auto'")
        if _has_traced_metadata(a.row_ptr, a.block_row, a.block_col):
            raise ValueError(
                "plan='auto' searches host metadata and cannot run under "
                "jit — autotune outside the trace "
                "(kernels.autotune.plan_search) and close the jitted call "
                "over the returned plan")
        # lazy import: autotune builds on this module's executor
        from repro.kernels.autotune import auto_plan
        plan = auto_plan(a, n_shards=n_shards, n_col_shards=n_col_shards,
                         reorder=reorder)
        auto_planned = True
    if (n_shards is not None or n_col_shards is not None) \
            and not auto_planned:
        # shard counts must never be silently ignored: with a prebuilt
        # plan they are a cross-check against the plan's own mesh shape,
        # without one they only mean something on the partitioned schedule
        got = plan.fwd if isinstance(plan, SpmmTrainPlan) else plan
        if got is not None:
            if not isinstance(got, PartitionedSpmmPlan):
                raise ValueError(
                    "n_shards/n_col_shards was given but the prebuilt "
                    "plan is single-device — build it with "
                    "plan_partitioned_spmm / plan_spmm_vjp(n_shards=...) "
                    "instead")
            if n_shards is not None and got.n_shards != n_shards:
                raise ValueError(
                    f"n_shards={n_shards} but the prebuilt plan has "
                    f"{got.n_shards} shards")
            if n_col_shards is not None \
                    and got.n_col_shards != n_col_shards:
                raise ValueError(
                    f"n_col_shards={n_col_shards} but the prebuilt plan "
                    f"has {got.n_col_shards} column shards")
        elif schedule != "partitioned":
            raise ValueError("n_shards/n_col_shards only applies to "
                             "schedule='partitioned' (or pass a prebuilt "
                             "PartitionedSpmmPlan)")
    if b_dense.ndim not in (2, 3):
        raise ValueError(f"B must be (K, N) or (G, K, N), got {b_dense.shape}")
    if b_dense.shape[-2] != a.shape[1]:
        raise ValueError(
            f"contraction mismatch: A is {a.shape}, B has K={b_dense.shape[-2]}")
    m = a.shape[0]
    batched = b_dense.ndim == 3
    b3 = b_dense if batched else b_dense[None]
    b3, n_orig = _pad_cols(b3, bn)

    train: SpmmTrainPlan | None = None
    if isinstance(plan, SpmmTrainPlan):
        train = plan
        plan = train.fwd

    # a reordered plan carries its RowReorder: permute A's block-rows
    # before the kernel (host metadata + one traced payload gather; the
    # gather sits outside the custom_vjp, so autodiff scatters dA back
    # to the original slots for free) and invert the permutation on the
    # output rows after it
    rr = getattr(plan, "reorder", None) if plan is not None else None
    if rr is not None:
        if rr.shape != a.shape or rr.block_shape != a.block_shape:
            raise ValueError(
                f"reordered plan was built for {rr.shape} / blocks "
                f"{rr.block_shape}, operand is {a.shape} / blocks "
                f"{a.block_shape} — was it built for this weight?")
        a = apply_reorder(a, rr)

    # planning walks host metadata; under jit (traced row_ptr) a planned
    # schedule needs a prebuilt plan — otherwise fall back to the naive
    # walk instead of crashing on the tracer.
    traced_meta = _has_traced_metadata(a.row_ptr, a.block_row, a.block_col)
    if plan is None and traced_meta:
        schedule = "naive"
    if plan is not None:
        if plan.n_block_rows != a.n_block_rows:
            raise ValueError(
                f"plan is for {plan.n_block_rows} block-rows, "
                f"operand has {a.n_block_rows}")
        if isinstance(plan, PartitionedSpmmPlan):
            # order indexes shard-local slots; the global capacity bound
            # lives on the payload gather map instead
            if plan.gather_live.any() and \
                    int(plan.gather[plan.gather_live].max()) >= a.n_blocks_max:
                raise ValueError("plan gathers blocks beyond the operand's "
                                 "capacity — was it built for this weight?")
        elif plan.order.size and int(plan.order.max()) >= a.n_blocks_max:
            raise ValueError("plan indexes blocks beyond the operand's "
                             "capacity — was it built for this weight?")
        if (plan.block_m, plan.block_k) != a.block_shape:
            raise ValueError(
                f"plan was built for blocks "
                f"({plan.block_m}, {plan.block_k}), operand blocks are "
                f"{a.block_shape} — was it built for this weight?")
    if plan is None and schedule == "partitioned":
        col = n_col_shards if n_col_shards is not None else 1
        shards = n_shards if n_shards is not None \
            else max(len(jax.local_devices()) // col, 1)
        plan = plan_partitioned_spmm(a, n_shards=shards, n_lanes=n_lanes,
                                     chunk=chunk, n_col_shards=col)
    if plan is None and schedule != "naive":
        # the fused kernels never materialize the full per-lane buffer
        # (rmw: none at all; compact: written-map-sized tiles), so auto
        # planning takes n_lanes at face value — the retired lane-buffer
        # path needed a 256 MB budget cap here
        plan = plan_spmm(a, n_lanes=n_lanes, chunk=chunk,
                         row_atomic=(schedule == "row_atomic"))

    # kernel-path VJP: armed by a prebuilt SpmmTrainPlan, or — when the
    # pattern is concrete (eager) — built LAZILY on the first backward
    # pass, so forward-only calls never pay for the transpose-side plan.
    # The eager thunk reuses the forward plan just built (no second LPT
    # walk).
    if train is not None:
        train_thunk = lambda t=train: t
    elif traced_meta:
        train_thunk = None          # jnp fallback backward (naive only)
    elif isinstance(plan, PartitionedSpmmPlan):
        memo = []

        def train_thunk(a=a, fwd=plan, lanes=n_lanes, chunk=chunk):
            if not memo:
                memo.append(plan_partitioned_spmm_vjp(
                    a, n_shards=fwd.n_shards, n_lanes=lanes, chunk=chunk,
                    fwd=fwd))
            return memo[0]
    else:
        memo = []

        def train_thunk(a=a, fwd=plan, lanes=n_lanes, chunk=chunk,
                        ra=(schedule == "row_atomic")):
            if not memo:
                memo.append(plan_spmm_vjp(a, n_lanes=lanes, chunk=chunk,
                                          row_atomic=ra, fwd=fwd))
            return memo[0]

    out = _spmm_call(a, b3, plan=plan, train_thunk=train_thunk, bn=bn,
                     interpret=interpret)
    out = out[..., :n_orig]
    if rr is not None:
        # undo the row permutation: permuted-output row p holds true row
        # rr.perm[p], so true row i is gathered from position rr.inv[i]
        out = jnp.take(out, jnp.asarray(rr.inv), axis=-2)
    return out if batched else out[0]


def _scatter_merge_f32(tiles, slot_row, *, gm: int, bm: int) -> jax.Array:
    """Compact-flush merge shared by the single-device compact path and
    the partitioned row-offset epilogue: scatter ``(G, n_slots, bm, N)``
    flush tiles into their block-rows in f32.  Dead slots
    (``slot_row < 0``) target a sacrificial block-row that is sliced off;
    duplicate row targets are split rows (within a lane pool, or across
    devices), merged at accumulator precision so they round once."""
    g, _, _, n = tiles.shape
    rows = np.where(slot_row < 0, gm, slot_row).reshape(-1)
    merged = jnp.zeros((g, gm + 1, bm, n), jnp.float32)
    merged = merged.at[:, jnp.asarray(rows)].add(tiles)
    return merged[:, :gm].reshape(g, gm * bm, n)


def _partitioned_spmm_f32(blocks, b3, plan: PartitionedSpmmPlan, *,
                          bn: int, interpret: bool) -> jax.Array:
    """Mesh-partitioned planned SpMM → merged ``(G, m, N)`` **f32**.

    Every shard runs the existing compact kernel on its own row slice:
    payload (gathered per-shard blocks) and plan metadata are sharded
    along the leading device axis, and the compact flush tiles come back
    device-stacked.  With ``plan.n_col_shards == 1`` the dense operand is
    replicated on every shard (the 1-D layout); with ``n_col_shards > 1``
    the mesh grows a ``COL_AXIS`` and B's N dimension is **panel-split**
    along it instead — each ``(shard, col)`` device computes its
    row-slice × column-panel, and the panels reassemble by placement in
    the ``out_specs`` (disjoint slices of N: a concat, no collective).
    The row-offset epilogue then scatters each shard's ``slot_row`` slots
    into its rows of the global output — rows are disjoint across shards
    by default, so that merge is a plain placement too; only split-row
    boundary slots (``plan.split_rows``) actually accumulate, in f32,
    inside the same scatter-add.

    Mesh resolution is ``distributed.sharding.partition_mesh``: with a
    live mesh the shard loop is a ``shard_map``; without one (fewer
    devices than the request) the same per-shard computation runs as a
    stacked loop on one device — bit-identical, because the kernel's
    output-column tiles are independent (a full-N pass computes exactly
    what the per-panel passes concatenate to) and both paths execute the
    identical per-shard kernel and the identical epilogue.
    """
    d_, cap = plan.gather.shape
    bm = plan.block_m
    gm = plan.n_block_rows
    c_ = plan.n_col_shards
    gat = jnp.asarray(plan.gather)                    # (D, cap)
    live = jnp.asarray(plan.gather_live)
    shard_blocks = jnp.where(live[..., None, None], blocks[gat], 0)
    order = jnp.asarray(plan.order)
    row = jnp.asarray(plan.step_row)
    col = jnp.asarray(plan.step_col)
    slot = jnp.asarray(plan.flush_slot)

    def one_shard(blk, o, r, c, f, bb):
        return maple_spmm_compact_pallas(
            blk, o, r, c, f, bb, r_max=plan.r_max, bn=bn,
            interpret=interpret)                      # (G, L, r_max*bm, N)

    n_in = b3.shape[-1]
    mesh, axes = partition_mesh(d_, c_)
    if mesh is not None and c_ > 1:
        # 2-D: panels must each be a bn multiple, so N pads to c_*bn here
        # (zero columns; sliced back after the merge)
        ax_s, ax_c = axes
        b3p, _ = _pad_cols(b3, c_ * bn)
        shard_fn = shard_map(
            lambda blk, o, r, c, f, bb:
                one_shard(blk[0], o[0], r[0], c[0], f[0], bb)[None],
            mesh=mesh,
            in_specs=(P(ax_s), P(ax_s), P(ax_s), P(ax_s), P(ax_s),
                      P(None, None, ax_c)),
            out_specs=P(ax_s, None, None, None, ax_c), check_vma=False)
        tiles = shard_fn(shard_blocks, order, row, col, slot, b3p)
    elif mesh is not None:
        axis = axes
        shard_fn = shard_map(
            lambda blk, o, r, c, f, bb:
                one_shard(blk[0], o[0], r[0], c[0], f[0], bb)[None],
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P()),
            out_specs=P(axis), check_vma=False)
        tiles = shard_fn(shard_blocks, order, row, col, slot, b3)
    else:
        # stacked loop: full-N per shard — output-column tiles are
        # independent, so this equals the panel concat bit-for-bit
        tiles = jnp.stack([
            one_shard(shard_blocks[d], order[d], row[d], col[d], slot[d],
                      b3)
            for d in range(d_)])                      # (D, G, L, r_max*bm, N)

    g, n = tiles.shape[1], tiles.shape[-1]
    tiles = jnp.moveaxis(tiles, 1, 0)                 # (G, D, L, r_max*bm, N)
    tiles = tiles.reshape(g, d_ * plan.n_lanes * plan.r_max, bm, n)
    # row-offset epilogue: duplicate row targets exist only for split-row
    # boundary slots
    out = _scatter_merge_f32(tiles, plan.slot_row, gm=gm, bm=bm)
    return out[..., :n_in]


def _partitioned_sddmm_f32(dc, b3, train: SpmmTrainPlan, *, bn: int,
                           interpret: bool) -> jax.Array:
    """Mesh-partitioned dA block SDDMM → ``(n_blocks_max, bm, bk)`` f32.

    dA ownership follows the *forward* plan's payload gather maps: each
    shard computes the ``(dC @ B^T)`` blocks it owns, fetching dC
    row-tiles from the (shard-replicated) cotangent — dC rows follow the
    forward's row split automatically because a shard only names rows it
    owns.  On a 2-D mesh dC and B are both panel-split along ``COL_AXIS``;
    N is the SDDMM's *contraction* axis, so the per-panel partials are
    completed by a ``psum`` over that axis (the forward's concat becomes
    the backward's one collective).  The merge back to global block slots
    is pure placement — gather maps are disjoint by construction — done
    as a scatter to a sacrificial-slot-extended buffer so live values
    land bit-exactly (no ``+ 0.0`` rounding of the placement).

    Without a mesh the same math runs as a stacked loop: the full-N
    kernel per shard when ``n_col_shards == 1`` (bit-identical to the
    single-device SDDMM — per-block accumulation order over ``(g, j)``
    is launch-set independent), else per-panel partials summed in panel
    order, mimicking the psum (allclose, not bitwise, to a one-pass
    contraction — exactly as on the mesh).
    """
    fwd = train.fwd
    bm, bk = train.block_shape
    d_, cap = fwd.gather.shape
    c_ = fwd.n_col_shards
    sd_row, sd_col = sddmm_shard_meta(fwd.gather, fwd.gather_live,
                                      train.block_row, train.block_col)
    rowd = jnp.asarray(sd_row)
    cold = jnp.asarray(sd_col)

    def one_shard(r, c, dcl, bl):
        return maple_sddmm_bsr_pallas(dcl, bl, r, c, bm=bm, bk=bk, bn=bn,
                                      interpret=interpret)  # (cap, bm, bk)

    mesh, axes = partition_mesh(d_, c_)
    if mesh is not None and c_ > 1:
        ax_s, ax_c = axes
        dcp, _ = _pad_cols(dc, c_ * bn)
        b3p, _ = _pad_cols(b3, c_ * bn)

        def shard_body(r, c, dcl, bl):
            part = one_shard(r[0], c[0], dcl, bl)
            return jax.lax.psum(part, ax_c)[None]

        parts = shard_map(
            shard_body, mesh=mesh,
            in_specs=(P(ax_s), P(ax_s), P(None, None, ax_c),
                      P(None, None, ax_c)),
            out_specs=P(ax_s), check_vma=False)(rowd, cold, dcp, b3p)
    elif mesh is not None:
        axis = axes
        parts = shard_map(
            lambda r, c, dcl, bl: one_shard(r[0], c[0], dcl, bl)[None],
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(), P()),
            out_specs=P(axis), check_vma=False)(rowd, cold, dc, b3)
    else:
        if c_ > 1:
            dcp, _ = _pad_cols(dc, c_ * bn)
            b3p, _ = _pad_cols(b3, c_ * bn)
            w = dcp.shape[-1] // c_
            per = []
            for d in range(d_):
                acc = None
                for ci in range(c_):
                    sl = slice(ci * w, (ci + 1) * w)
                    p = one_shard(rowd[d], cold[d], dcp[..., sl],
                                  b3p[..., sl])
                    acc = p if acc is None else acc + p
                per.append(acc)
        else:
            per = [one_shard(rowd[d], cold[d], dc, b3) for d in range(d_)]
        parts = jnp.stack(per)                        # (D, cap, bm, bk)

    # placement merge: live slots are disjoint across shards; dead slots
    # all target the sacrificial slot (their kernel output is zero anyway)
    cap_global = train.n_blocks_max
    live = np.asarray(fwd.gather_live)
    gat_safe = np.where(live, np.asarray(fwd.gather), cap_global)
    da = jnp.zeros((cap_global + 1, bm, bk), jnp.float32)
    da = da.at[jnp.asarray(gat_safe.reshape(-1))].set(
        parts.reshape(d_ * cap, bm, bk))
    return da[:cap_global]


def _planned_spmm_f32(blocks, b3, plan: SpmmPlan, *, bn: int,
                      interpret: bool) -> jax.Array:
    """Fused planned SpMM → merged ``(G, m, N)`` **f32** (cast is the
    caller's).  Output geometry (``m``, ``bm``) comes from the plan
    itself — the one place it is authoritative for both the forward and
    the transpose-side (bwd) pass, so a mis-built plan cannot silently
    mis-reshape the merge.  The cross-lane reduction happens in-kernel (``"rmw"``) or
    via the compact-tile scatter-add (``"compact"``); either way no
    ``(G, lanes, m, N)`` intermediate exists.

    The layout is dispatched **per call**: every plan carries both
    layouts' metadata, and ``plan.fused`` is only a preference — rmw's
    accumulating flush needs the interpreter's revisited-output-tile
    re-fetch, so compiled (``interpret=False``) calls always take the
    compact path, forward and backward alike (no layout can mismatch
    between the two passes of one VJP).  Plan arrays become device
    constants *here*, inside the custom_vjp bodies that call this — see
    the grad-of-jit note in :func:`_spgemm_value_call`.

    A :class:`PartitionedSpmmPlan` dispatches to the mesh-partitioned
    executor — same contract (merged f32 output, geometry authoritative
    on the plan), forward and transpose-side (bwd) pass alike."""
    if isinstance(plan, PartitionedSpmmPlan):
        return _partitioned_spmm_f32(blocks, b3, plan, bn=bn,
                                     interpret=interpret)
    bm = plan.block_m
    m = plan.n_block_rows * bm
    if plan.fused == "compact" or not interpret:
        tiles = maple_spmm_compact_pallas(
            blocks, jnp.asarray(plan.order), jnp.asarray(plan.step_row),
            jnp.asarray(plan.step_col), jnp.asarray(plan.flush_slot),
            b3, r_max=plan.r_max, bn=bn, interpret=interpret)
        g, n = b3.shape[0], b3.shape[-1]
        tiles = tiles.reshape(g, plan.n_lanes * plan.r_max, bm, n)
        # dead slots were never flushed (their contents are undefined) —
        # the shared merge scatters them into the sacrificial row
        return _scatter_merge_f32(tiles, plan.slot_row,
                                  gm=plan.n_block_rows, bm=bm)
    out = maple_spmm_planned_pallas(
        blocks, jnp.asarray(plan.order), jnp.asarray(plan.step_row),
        jnp.asarray(plan.step_col), jnp.asarray(plan.step_acc),
        b3, m=m, bn=bn, interpret=interpret)
    # rows no lane flushes were never initialized — zero them from the
    # row mask the plan cached at construction
    mask = jnp.asarray(plan.row_mask)                     # (m,)
    return jnp.where(mask[None, :, None], out, 0)


def _spmm_forward(blocks, block_row, block_col, row_ptr, b3, *,
                  plan: SpmmPlan | None, m: int, bm: int, bn: int,
                  interpret: bool) -> jax.Array:
    """Primal SpMM: fused planned grid when a plan is given, else the naive
    batched walk over (possibly traced) container metadata."""
    if plan is not None:
        out = _planned_spmm_f32(blocks, b3, plan, bn=bn,
                                interpret=interpret)
        # split-row partials merged in f32 above; round once, like the
        # naive single-accumulator walk
        return out.astype(b3.dtype)
    out = maple_spmm_batched_pallas(
        blocks, block_row, block_col, b3, m=m, bn=bn, interpret=interpret)
    # mask tiles of block-rows that own no non-zero block
    row_len = row_ptr[1:] - row_ptr[:-1]                # (gm,)
    mask = jnp.repeat(row_len > 0, bm)                  # (M,)
    return jnp.where(mask[None, :, None], out, 0)


def _spmm_bwd_kernel_path(blocks, b3, dc, train: SpmmTrainPlan, *,
                          bn: int, interpret: bool):
    """(dA.blocks, dB) through the Maple kernels — the paper-machinery
    backward: dB = A^T @ dC on the cached transpose-side plan, dA via the
    block SDDMM sampled at A's pattern."""
    bm, bk = train.block_shape
    cap = train.n_blocks_max
    nnzb = int(train.t_perm.size)

    # --- dB = A^T @ dC: transposed payload gather + the fused planned
    # kernel on the cached transpose-side plan (in-kernel lane merge — no
    # (G, lanes, K, N) intermediate on the backward either).
    at_blocks = jnp.zeros((cap, bk, bm), blocks.dtype)
    if nnzb:
        gathered = jnp.swapaxes(blocks[jnp.asarray(train.t_perm)], 1, 2)
        at_blocks = at_blocks.at[:nnzb].set(gathered)
    db = _planned_spmm_f32(at_blocks, dc, train.bwd, bn=bn,
                           interpret=interpret).astype(b3.dtype)

    # --- dA = (dC @ B^T) sampled at nnz(A): the block SDDMM.  With a
    # partitioned forward the SDDMM partitions over the same mesh — each
    # shard samples only the blocks its gather map owns.
    if isinstance(train.fwd, PartitionedSpmmPlan):
        da = _partitioned_sddmm_f32(dc, b3, train, bn=bn,
                                    interpret=interpret)
    else:
        da = maple_sddmm_bsr_pallas(
            dc, b3, jnp.asarray(train.block_row),
            jnp.asarray(train.block_col),
            bm=bm, bk=bk, bn=bn, interpret=interpret)
    live = jnp.asarray(train.block_col >= 0)
    da = jnp.where(live[:, None, None], da, 0).astype(blocks.dtype)
    return da, db


def _spmm_bwd_jnp(blocks, block_row, block_col, b3, dc):
    """Traced-metadata fallback backward (naive schedule under jit with no
    train plan): the same two contractions as the kernel path, expressed as
    jnp gathers/scatter-adds over block metadata.  dA is still sampled at
    the block pattern — never a dense (M, K)."""
    nb, bm, bk = blocks.shape
    g, m, n = dc.shape
    k = b3.shape[1]
    live = block_col >= 0
    br = jnp.clip(block_row, 0, m // bm - 1)
    bc = jnp.clip(block_col, 0, k // bk - 1)
    dc_t = dc.reshape(g, m // bm, bm, n)
    b_t = b3.reshape(g, k // bk, bk, n)
    dc_g = jnp.take(dc_t, br, axis=1)                     # (G, nb, bm, N)
    b_g = jnp.take(b_t, bc, axis=1)                       # (G, nb, bk, N)
    da = jnp.einsum("gsmn,gskn->smk", dc_g.astype(jnp.float32),
                    b_g.astype(jnp.float32))
    da = jnp.where(live[:, None, None], da, 0).astype(blocks.dtype)
    contrib = jnp.einsum("smk,gsmn->gskn", blocks.astype(jnp.float32),
                         dc_g.astype(jnp.float32))
    contrib = jnp.where(live[None, :, None, None], contrib, 0)
    db_t = jnp.zeros((g, k // bk, bk, n), jnp.float32).at[:, bc].add(contrib)
    return da, db_t.reshape(g, k, n).astype(b3.dtype)


def _spmm_call(a: BlockCSR, b3, *, plan, train_thunk, bn, interpret):
    """custom_vjp boundary of maple_spmm.

    Inputs are the payload (``a.blocks``, ``b3``) plus the container
    metadata (so the traced naive path needs no closed-over tracers —
    custom_vjp forbids those); metadata is integer-typed and receives
    symbolic-zero (float0) cotangents: **structure is not differentiated**.

    ``train_thunk`` is the lazy transpose-side schedule: ``None`` means
    the traced jnp fallback backward, otherwise it yields the
    ``SpmmTrainPlan`` on the first backward trace (prebuilt plans return
    immediately; eager calls plan here, so forward-only use stays free).
    """
    m = a.shape[0]
    bm = a.block_shape[0]
    gm = a.n_block_rows

    def impl(blocks, block_row, block_col, row_ptr, b3):
        return _spmm_forward(blocks, block_row, block_col, row_ptr, b3,
                             plan=plan, m=m, bm=bm, bn=bn,
                             interpret=interpret)

    call = jax.custom_vjp(impl)

    def fwd(blocks, block_row, block_col, row_ptr, b3):
        return impl(blocks, block_row, block_col, row_ptr, b3), (
            blocks, block_row, block_col, b3)

    def bwd(res, dc):
        blocks, block_row, block_col, b3 = res
        if train_thunk is not None:
            da, db = _spmm_bwd_kernel_path(blocks, b3, dc, train_thunk(),
                                           bn=bn, interpret=interpret)
        else:
            da, db = _spmm_bwd_jnp(blocks, block_row, block_col, b3, dc)
        rptr0 = np.zeros((gm + 1,), jax.dtypes.float0)
        return da, _float0(block_row), _float0(block_col), rptr0, db

    call.defvjp(fwd, bwd)
    return call(a.blocks, a.block_row, a.block_col, a.row_ptr, b3)


# --------------------------------------------------------------------------
# element-granular CSR × CSR (paper protocol C = A×A)
# --------------------------------------------------------------------------

def csr_to_ell(a: CSR, max_row_len: int | None = None, *,
               truncate: bool = False):
    """Deprecated shim — CSR → ELL regularization now lives in
    :func:`repro.core.formats.csr_to_ell` (the format layer's canonical
    home, shared with ``maple_spgemm``'s ELL panels).  Import from
    there; this alias stays for older callers."""
    from repro.core.formats import csr_to_ell as _csr_to_ell
    return _csr_to_ell(a, max_row_len, truncate=truncate)


def _has_traced_metadata(*arrays) -> bool:
    return any(isinstance(x, jax.core.Tracer) for x in arrays)


def maple_spgemm(a: CSR, b: CSR, *, schedule: str = "balanced",
                 n_lanes: int = 8, plan: SpgemmPlan | None = None,
                 nnz_max: int | None = None,
                 interpret: bool | None = None) -> CSR:
    """C = A_csr @ B_csr → **padded CSR** via the two-phase Maple SpGEMM.

    Operands may also be any blocked :class:`~repro.core.formats
    .SparseFormat` (``BlockCSR`` / ``EllPack`` / ``BitmapBlocked``);
    they lower to the element pattern they store via
    ``core.formats.as_element_csr`` at entry.

    The symbolic phase (``kernels.schedule.plan_spgemm``) walks A and B
    metadata on the host: exact output pattern, bounded PSB width, and the
    Eq. (8) scatter position of every partial product.  The numeric phase
    (``kernels.maple_spgemm``) then executes the row-wise product with B
    held as compressed row panels — **B is never densified** — and the
    result is compacted into a padded ``CSR`` (``col_id = -1`` pads,
    capacity from ``core.csr.grow_nnz_max`` unless ``nnz_max`` pins it).

    ``schedule`` selects how A rows are packed onto lanes:

    * ``"balanced"`` (default) — LPT by *work* (Σ nnz(B[k',:]) per row,
      the partial-product count that actually prices a row);
    * ``"row_atomic"`` — LPT by nnz(A[i,:]) (the fiber-count proxy the
      MatRaptor-style baseline would use; rows are atomic under every
      SpGEMM schedule — the names mirror ``maple_spmm`` dispatch);
    * ``"naive"`` — one lane, rows in order.

    Planning (the symbolic phase) reads host metadata, so under ``jax.jit``
    pass a prebuilt ``plan`` for the jitted call to close over; without one
    this raises instead of silently densifying.
    """
    if interpret is None:
        interpret = _default_interpret()

    def _as_csr(op):
        if isinstance(op, CSR):
            return op
        if isinstance(op, formats.BLOCK_FORMATS):
            # blocked operands expand to the element pattern they store
            # (host metadata + one traced value gather — never dense)
            return formats.as_element_csr(op)
        raise TypeError(
            "maple_spgemm takes CSR (or blocked SparseFormat) operands; "
            "for dense B use maple_spmm / gustavson.spmm_rowwise")

    _maybe_validate(a, b)
    a = _as_csr(a)
    b = _as_csr(b)
    if a.shape[1] != b.shape[0]:
        raise ValueError(
            f"contraction mismatch: A is {a.shape}, B is {b.shape}")
    if schedule not in ("balanced", "row_atomic", "naive"):
        raise ValueError(f"unknown schedule {schedule!r}")

    if plan is None:
        if _has_traced_metadata(a.row_ptr, a.col_id, b.row_ptr, b.col_id):
            raise ValueError(
                "maple_spgemm's symbolic phase needs host metadata; under "
                "jit, prebuild the plan with kernels.schedule.plan_spgemm "
                "and pass it so the jitted call closes over it")
        balance = {"balanced": "work", "row_atomic": "fibers",
                   "naive": "none"}[schedule]
        plan = plan_spgemm(a, b, n_lanes=n_lanes, balance=balance)
    else:
        if plan.shape_a != a.shape or plan.shape_b != b.shape:
            raise ValueError(
                f"plan is for {plan.shape_a} @ {plan.shape_b}, operands "
                f"are {a.shape} @ {b.shape}")
        if plan.a_gather.size and \
                int(plan.a_gather.max(initial=0)) >= a.nnz_max:
            raise ValueError("plan indexes A slots beyond the operand's "
                             "capacity — was it built for this pattern?")
        if plan.b_gather.size and \
                int(plan.b_gather.max(initial=0)) >= b.nnz_max:
            raise ValueError("plan indexes B slots beyond the operand's "
                             "capacity — was it built for this pattern?")
    m, n = a.shape[0], b.shape[1]
    nnz_c = plan.nnz_c
    cap = grow_nnz_max(nnz_c) if nnz_max is None else nnz_max
    if cap < nnz_c:
        raise ValueError(f"nnz_max={cap} < nnz(C)={nnz_c}")

    value = _spgemm_value_call(a.value, b.value, plan=plan, cap=cap,
                               interpret=interpret)
    col_id = np.full(cap, -1, np.int32)
    col_id[:nnz_c] = plan.out_cols
    return CSR(value=value, col_id=jnp.asarray(col_id),
               row_ptr=jnp.asarray(plan.out_row_ptr.astype(np.int32)),
               shape=(m, n))


def _spgemm_compaction_maps(plan: SpgemmPlan, cap: int):
    """Host (row, offset) of each output value slot — the forward's
    ELL→padded-CSR compaction map and the backward's scatter for dC."""
    m = plan.shape_a[0]
    nnz_c = plan.nnz_c
    lens = np.diff(plan.out_row_ptr)
    rows = np.zeros(cap, np.int32)
    offs = np.zeros(cap, np.int32)
    rows[:nnz_c] = np.repeat(np.arange(m, dtype=np.int32), lens)
    offs[:nnz_c] = (np.arange(nnz_c, dtype=np.int64)
                    - np.repeat(plan.out_row_ptr[:-1], lens)
                    ).astype(np.int32)
    return rows, offs


def _spgemm_value_call(a_value, b_value, *, plan: SpgemmPlan, cap: int,
                       interpret: bool):
    """custom_vjp boundary of maple_spgemm: (A values, B values) → C values.

    The pattern side (``col_id`` / ``row_ptr`` of all three matrices) is
    host metadata on the plan and is **not** differentiated; only the
    payload flows.  Backward stays inside the compressed machinery:

    * ``dA`` — the plan-driven element SDDMM
      (``kernels.maple_sddmm.maple_sddmm_csr_pallas``): the forward's
      ``scatter_pos`` run in reverse gathers ``dC`` at exactly the
      positions row i's partials landed, one dot with the B row panel per
      live A slot;
    * ``dB = (A^T @ dC)|_{nnz(B)}`` — a transposed-operand pass expressed
      over the same plan metadata: per live A slot, its value scales the
      gathered ``dC`` positions and scatter-adds into the ELL row of the B
      row it consumed (a segment-sum over A's column fibers — A^T's rows —
      with no transposed container materialized).

    Neither side ever forms a dense (M, K) or (K, N).
    """
    m = plan.shape_a[0]
    k = plan.shape_b[0]
    nnz_c = plan.nnz_c
    la, lb, lc = plan.la, plan.lb, plan.lc
    n_slots = m * la
    a_cap = a_value.shape[0]
    b_cap = b_value.shape[0]

    rows, offs = _spgemm_compaction_maps(plan, cap)

    def impl(a_value, b_value):
        if nnz_c == 0:
            # nothing to compute (all-zero pattern, or a zero-dimension
            # operand the kernel's >= 1-row panels could not represent)
            return jnp.zeros((cap,), a_value.dtype)
        # numeric phase: traced value gathers over the plan's (static)
        # slot maps — ELL-regularized operands, no host copies, no
        # densification.  (Device constants are materialized *inside* the
        # vjp bodies: custom_vjp's fwd/bwd are retraced lazily, and arrays
        # hoisted to the enclosing scope would be baked into a trace that
        # may be dead by then — the grad-of-jit leak.)
        a_vals = jnp.where(jnp.asarray(plan.a_live),
                           a_value[jnp.asarray(plan.a_gather)], 0)
        b_ell = jnp.where(jnp.asarray(plan.b_live),
                          b_value[jnp.asarray(plan.b_gather)], 0)
        ell_out = maple_spgemm_pallas(
            a_vals.reshape(-1, 1), b_ell, jnp.asarray(plan.scatter_pos),
            jnp.asarray(plan.order), jnp.asarray(plan.step_row),
            jnp.asarray(plan.step_col), m=m, lc=lc,
            interpret=interpret)[:m]                   # drop sacrificial row
        # compact ELL rows into the padded-CSR value vector (pattern is
        # host metadata from the symbolic phase; only the values gather
        # is traced)
        live = np.arange(cap) < nnz_c
        return jnp.where(jnp.asarray(live),
                         ell_out[jnp.asarray(rows), jnp.asarray(offs)], 0)

    call = jax.custom_vjp(impl)

    def fwd(a_value, b_value):
        return impl(a_value, b_value), (a_value, b_value)

    def bwd(res, dvalue):
        a_value, b_value = res
        if nnz_c == 0:
            return jnp.zeros_like(a_value), jnp.zeros_like(b_value)
        # dC back to ELL row layout (+ sacrificial row m for pad steps)
        dc_ell = jnp.zeros((m + 1, lc), jnp.float32)
        dc_ell = dc_ell.at[jnp.asarray(rows[:nnz_c]),
                           jnp.asarray(offs[:nnz_c])].set(
            dvalue[:nnz_c].astype(jnp.float32))

        # --- dA: plan-driven element SDDMM over the forward schedule.
        b_ell = jnp.where(jnp.asarray(plan.b_live),
                          b_value[jnp.asarray(plan.b_gather)],
                          0).astype(jnp.float32)
        ell_da = maple_sddmm_csr_pallas(
            dc_ell, b_ell, jnp.asarray(plan.scatter_pos),
            jnp.asarray(plan.order), jnp.asarray(plan.step_row),
            jnp.asarray(plan.step_col), n_slots=n_slots,
            interpret=interpret)[:n_slots, 0]
        live_idx = np.nonzero(plan.a_live)[0]
        da = jnp.zeros((a_cap,), jnp.float32)
        if live_idx.size:
            da = da.at[jnp.asarray(plan.a_gather[live_idx])].set(
                ell_da[jnp.asarray(live_idx)])

        # --- dB: transposed-operand pass over plan metadata (A^T's rows
        # are A's column fibers — a scatter-add by consumed B row).
        slot_col = np.full(n_slots, -1, np.int32)
        live_steps = plan.step_col >= 0
        slot_col[plan.order[live_steps]] = plan.step_col[live_steps]
        pos_live = plan.scatter_pos >= 0                   # (n_slots, lb)
        safe_pos = np.maximum(plan.scatter_pos, 0)
        row_of_slot = np.repeat(np.arange(m, dtype=np.int32), la)
        dcg = dc_ell[jnp.asarray(row_of_slot)[:, None],
                     jnp.asarray(safe_pos)]
        dcg = jnp.where(jnp.asarray(pos_live), dcg, 0)     # (n_slots, lb)
        a_ell = jnp.where(jnp.asarray(plan.a_live),
                          a_value[jnp.asarray(plan.a_gather)],
                          0).astype(jnp.float32)
        contrib = a_ell[:, None] * dcg
        contrib = jnp.where(jnp.asarray(slot_col >= 0)[:, None], contrib, 0)
        db_ell = jnp.zeros((k, lb), jnp.float32)
        db_ell = db_ell.at[jnp.asarray(np.maximum(slot_col, 0))].add(contrib)
        rb, cb = np.nonzero(plan.b_live)
        db = jnp.zeros((b_cap,), jnp.float32)
        if rb.size:
            db = db.at[jnp.asarray(plan.b_gather[rb, cb])].set(
                db_ell[jnp.asarray(rb), jnp.asarray(cb)])
        return da.astype(a_value.dtype), db.astype(b_value.dtype)

    call.defvjp(fwd, bwd)
    return call(a_value, b_value)


def maple_spmspm(a: CSR, b, *, interpret: bool | None = None) -> jax.Array:
    """C = A_csr @ B via the element-granular Maple walk → dense (M, N).

    .. deprecated:: prefer :func:`maple_spgemm`, which keeps the output
       sparse — densifying C here is exactly the traffic the row-wise
       product exists to avoid, and callers that only need C's values
       should consume the padded CSR it returns.  When ``b`` is a CSR
       with host metadata this routes through the two-phase SpGEMM kernel
       (B stays compressed) and densifies the *result* directly from the
       padded-CSR payload: the pattern is host metadata from the symbolic
       phase, so only the live ``nnz(C)`` prefix is scattered once — not
       the old ``CSR.to_dense()`` round trip, which re-scattered every
       capacity slot through pad clamping and masking.  The legacy
       positional-PSB kernel remains for explicitly dense ``b`` — the
       BRB-after-fill view — and for traced metadata under jit.
    """
    if interpret is None:
        interpret = _default_interpret()
    if isinstance(b, CSR) and not _has_traced_metadata(
            a.row_ptr, a.col_id, b.row_ptr, b.col_id):
        c = maple_spgemm(a, b, interpret=interpret)
        m, n = a.shape[0], b.shape[1]
        rptr = np.asarray(c.row_ptr)
        nnz_c = int(rptr[-1])
        rows = np.repeat(np.arange(m, dtype=np.int32), np.diff(rptr))
        cols = np.asarray(c.col_id)[:nnz_c]
        dense = jnp.zeros((m, n), c.value.dtype)
        if nnz_c:
            dense = dense.at[jnp.asarray(rows), jnp.asarray(cols)].set(
                c.value[:nnz_c])
        return dense
    values, col_ids = formats.csr_to_ell(a)
    b_rows = b.to_dense() if isinstance(b, CSR) else b
    return maple_spmspm_pallas(values, col_ids, b_rows, interpret=interpret)


# --------------------------------------------------------------------------
# MoE grouped GEMM
# --------------------------------------------------------------------------

def moe_expert_gemm(x_sorted: jax.Array, group_sizes: jax.Array,
                    w: jax.Array, *, bt: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """y[t] = x[t] @ w[expert(t)] for expert-sorted tokens.

    ``group_sizes`` must already be multiples of ``bt`` (capacity-padded —
    the MoE layer pads each expert's segment with zero rows).  Static expert
    count and T; the tile→expert map is computed with jnp (works under jit).
    """
    if interpret is None:
        interpret = _default_interpret()
    t, _ = x_sorted.shape
    n_tiles = t // bt
    # expert of each tile: searchsorted over the group offsets
    offsets = jnp.cumsum(group_sizes)                  # (E,)
    tile_starts = jnp.arange(n_tiles, dtype=group_sizes.dtype) * bt
    expert_of_tile = jnp.searchsorted(offsets, tile_starts, side="right")
    expert_of_tile = expert_of_tile.astype(jnp.int32)
    return moe_gemm_pallas(
        x_sorted, expert_of_tile, w, bt=bt, interpret=interpret
    )


# --------------------------------------------------------------------------
# block-sparse local attention
# --------------------------------------------------------------------------

def local_block_attention(q, k, v, *, window: int, bq: int = 128,
                          bk: int = 128, interpret: bool | None = None):
    """Causal local-window attention with banded-BSR tile skipping.

    q/k/v: (B, S, H, hd).  Tiles outside the window band are never fetched
    (the Maple zero-block skip); within-band masking is elementwise.
    """
    if interpret is None:
        interpret = _default_interpret()
    s = q.shape[1]
    kv_map = jnp.asarray(local_window_kv_map(s, window, bq, bk))
    fn = lambda qq, kk, vv: block_attention_pallas(
        qq, kk, vv, kv_map, bq=bq, bk=bk, causal=True, window=window,
        interpret=interpret)
    return jax.vmap(fn)(q, k, v)
