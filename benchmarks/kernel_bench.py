"""Kernel micro-benchmarks: Maple Pallas kernels (interpret mode on CPU —
correctness-grade timing; real perf numbers come from the TPU target) vs
their jnp twins, plus the block-sparsity skip-rate table that corresponds
to the paper's P/nnz analysis at MXU granularity.

Output: a ``name,us_per_call,derived`` CSV on stdout and — with
``--json PATH`` — machine-readable records (per-sweep best-of time,
predicted cycles from the shared ``core.maple`` model, and an output-side
HBM bytes estimate) so the perf trajectory is tracked across PRs.  The
checked-in ``BENCH_kernels.json`` at the repo root is the baseline;
``--check BASELINE`` fails when a golden config's *predicted cycles*
regress more than ``--tol`` (deterministic — wall time is never gated).

``--smoke`` runs the reduced golden subset (schedule + fused-dataflow +
partitioned + partitioned_2d + autotune sweeps) for CI.  The partitioned
sweep prices the mesh-partitioned plans (``kernels.partition``) across
device counts — per-device predicted cycles plus a deterministic
device-count scaling column; the partitioned_2d sweep adds the
``(shard, col)`` mesh shapes, tracking per-device dense-operand bytes
(shrinks ``n_col_shards``×) and SPMD ``padding_waste`` with/without the
repack pass.

The ``fused_dataflow`` sweep is the measured trajectory of this repo's
output-dataflow work: the fused planned kernels (in-kernel cross-lane
merge; ``rmw`` and ``compact`` layouts) against a *frozen reference copy*
of the retired per-lane-buffer path — the ``(G, L, M, N)`` flush +
mask + tree-sum epilogue that the library deleted.  The reference lives
only here, for comparison; it is not a fallback.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import sparsity
from repro.core.csr import CSR, BlockCSR
from repro.core.formats import as_block_csr, to_bitmap, to_ell
from repro.core.gustavson import dense_oracle, spmm_rowwise, spmspm_rowwise
from repro.kernels import (local_block_attention, maple_spgemm, maple_spmm,
                           maple_spmspm, moe_expert_gemm,
                           plan_partitioned_spmm, plan_search, plan_spgemm,
                           plan_spmm, plan_spmm_vjp, reorder_rows)
from repro.kernels.autotune import fit_calibration, time_interleaved
from repro.launch import compile_cache

RECORDS: list = []


def emit(name: str, us: float, derived: str = "", **metrics):
    """One benchmark row: CSV line + structured record for --json."""
    rec = {"name": name, "us_per_call": round(float(us), 1)}
    rec.update(metrics)
    RECORDS.append(rec)
    print(f"{name},{us:.0f},{derived}")


def _time(fn, *args, reps=3):
    """Best-of-``reps`` wall time in µs (min is the stable statistic for
    regression tracking on a noisy shared CPU)."""
    jax.block_until_ready(fn(*args))  # compile/warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


# canonical copy lives in kernels.autotune (its measured-refinement rung
# and these comparative sweeps must time identically — the calibration
# fit is trained on these records); same contract as before
_time_interleaved = time_interleaved

# one source of truth with the autotune smoke and the autotuner tests:
# the golden block patterns live in core.sparsity
_pattern_mask = sparsity.block_pattern_mask


def _masked_dense(rng, mask: np.ndarray, bm: int, bk: int) -> np.ndarray:
    gm, gk = mask.shape
    d = rng.standard_normal((gm * bm, gk * bk)).astype(np.float32)
    return d * np.repeat(np.repeat(mask, bm, axis=0), bk, axis=1)


# --------------------------------------------------------------------------
# frozen reference: the retired per-lane-buffer planned SpMM
# --------------------------------------------------------------------------

def _lane_buffer_kernel(order, step_row, step_col, a_blk_ref, b_panel_ref,
                        out_ref, psb_ref, *, steps):
    """Pre-fusion planned kernel (reference only): each lane flushes its
    PSB runs into its own slice of a (G, L, M, N) buffer."""
    l = pl.program_id(1)
    s = pl.program_id(3)
    base = l * steps
    row = step_row[base + s]
    is_first = jnp.logical_or(
        s == 0, row != step_row[base + jnp.maximum(s - 1, 0)])
    is_last = jnp.logical_or(
        s == steps - 1, row != step_row[base + jnp.minimum(s + 1, steps - 1)])

    @pl.when(is_first)
    def _zero():
        psb_ref[...] = jnp.zeros_like(psb_ref)

    live = step_col[base + s] >= 0
    a = jnp.where(live, a_blk_ref[0], jnp.zeros_like(a_blk_ref[0]))
    psb_ref[...] += jnp.dot(a, b_panel_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(is_last)
    def _flush():
        out_ref[0, 0] = psb_ref[...]


def _lane_buffer_reference(a: BlockCSR, plan, bn: int):
    """The deleted dataflow, reconstructed for trajectory measurement:
    per-lane (G, L, M, N) partial flushes + the mask-and-tree-sum epilogue
    the ops wrapper used to run.  Returns a jittable fn of (blocks, b3)."""
    n_blocks, bm, bk = a.blocks.shape
    m = a.shape[0]
    lanes, steps = plan.order.shape
    order = jnp.asarray(plan.order.reshape(-1).astype(np.int32))
    row = jnp.asarray(plan.step_row.reshape(-1).astype(np.int32))
    col = jnp.asarray(plan.step_col.reshape(-1).astype(np.int32))
    written = jnp.asarray(plan.written)

    def call(blocks, b3):
        g, k, n = b3.shape
        kernel = functools.partial(_lane_buffer_kernel, steps=steps)
        lanes_out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(g, lanes, n // bn, steps),
                in_specs=[
                    pl.BlockSpec(
                        (1, bm, bk),
                        lambda gi, l, j, s, o, r, c: (
                            o[l * steps + s], 0, 0)),
                    pl.BlockSpec(
                        (1, bk, bn),
                        lambda gi, l, j, s, o, r, c: (
                            gi, jnp.maximum(c[l * steps + s], 0), j)),
                ],
                out_specs=pl.BlockSpec(
                    (1, 1, bm, bn),
                    lambda gi, l, j, s, o, r, c: (
                        gi, l, r[l * steps + s], j)),
                scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((g, lanes, m, n), jnp.float32),
            interpret=True,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
        )(order, row, col, blocks, b3)
        # the retired epilogue: mask never-flushed tiles, sum over lanes
        mask = jnp.repeat(written, bm, axis=1)           # (L, M)
        lanes_masked = jnp.where(mask[None, :, :, None], lanes_out, 0)
        return lanes_masked.sum(axis=1).astype(b3.dtype)

    return call


def fused_dataflow_sweep(rng, *, smoke: bool = False):
    """Fused planned SpMM (rmw / compact) vs the retired lane-buffer +
    epilogue reference, across patterns and lane counts.

    ``bytes_out`` is the model-level output-side HBM traffic
    (``SpmmPlan.output_traffic_bytes``); the retired path multiplies it
    by the lane count, which is the measured gap's mechanism.
    """
    gm = gk = 16
    bm = bk = 16
    n, g, bn = 256, 2, 128
    reps = 5 if smoke else 10
    # multi-lane only: at 1-2 lanes the retired buffer was barely bigger
    # than the output, so the comparison there measures CPU noise
    lane_counts = (8,) if smoke else (4, 8)
    for kind in ("uniform", "power_law", "banded"):
        mask = _pattern_mask(kind, rng, gm, gk)
        d = _masked_dense(rng, mask, bm, bk)
        a = BlockCSR.from_dense(d, (bm, bk))
        b3 = jnp.asarray(
            rng.standard_normal((g, gk * bk, n)).astype(np.float32))
        for lanes in lane_counts:
            plans = {f: plan_spmm(a, n_lanes=lanes, fused=f)
                     for f in ("rmw", "compact")}
            pc = plans["rmw"].predicted_cycles()
            fns = {f: jax.jit(lambda aa, bb, p=p: maple_spmm(aa, bb, plan=p))
                   for f, p in plans.items()}
            fns["epilogue"] = jax.jit(
                _lane_buffer_reference(a, plans["rmw"], bn))
            call_args = {f: (a, b3) for f in plans}
            call_args["epilogue"] = (a.blocks, b3)
            times = _time_interleaved(fns, call_args, reps=reps)
            for f in ("rmw", "compact"):
                # the retired path's entries carry a `legacy_` prefix in
                # the record schema: the --check gate refuses to treat
                # legacy keys as golden (it compares live dataflows only)
                emit(f"fused_{kind}_L{lanes}_{f}", times[f],
                     f"legacy_epilogue_us={times['epilogue']:.0f}"
                     f"/speedup={times['epilogue'] / times[f]:.2f}x"
                     f"/pred_plan={pc['plan']:.0f}",
                     pred_plan=pc["plan"], pred_maple=pc["maple"],
                     pred_row_atomic=pc["row_atomic"],
                     legacy_epilogue_us=round(times["epilogue"], 1),
                     speedup_vs_legacy_epilogue=round(
                         times["epilogue"] / times[f], 3),
                     bytes_out=plans[f].output_traffic_bytes(g, n, mode=f),
                     bytes_out_legacy_epilogue=plans[f].output_traffic_bytes(
                         g, n, mode="legacy_epilogue"))


def partitioned_sweep(rng, *, smoke: bool = False):
    """Mesh-partitioned planned SpMM across device counts.

    ``pred_plan`` is the slowest shard's lane makespan (what bounds the
    device array — deterministic, golden-gated), ``per_shard_pred`` the
    full per-device breakdown, and ``scaling`` the device-count scaling
    column: single-shard makespan / this shard count's makespan (ideal =
    n_shards; the gap is LPT quantization on skewed patterns).  Wall time
    is the usual correctness-grade interpret-mode number — on a 1-device
    box the shards run as a stacked loop, so it tracks total work, not
    the mesh speedup; ``devices_present`` records which regime timed it.
    """
    gm = gk = 16
    bm = bk = 16
    n, g = 128, 2
    reps = 3 if smoke else 8
    for kind in ("uniform", "power_law", "banded"):
        mask = _pattern_mask(kind, rng, gm, gk)
        d = _masked_dense(rng, mask, bm, bk)
        a = BlockCSR.from_dense(d, (bm, bk))
        b3 = jnp.asarray(
            rng.standard_normal((g, gk * bk, n)).astype(np.float32))
        base = None
        for shards in (1, 2, 4, 8):
            plan = plan_partitioned_spmm(a, n_shards=shards, n_lanes=4)
            pc = plan.predicted_cycles()
            if base is None:
                base = pc["plan"]
            scaling = base / max(pc["plan"], 1.0)
            fn = jax.jit(lambda aa, bb, p=plan: maple_spmm(aa, bb, plan=p))
            us = _time(fn, a, b3, reps=reps)
            emit(f"part_{kind}_D{shards}", us,
                 f"pred_plan={pc['plan']:.0f}/scaling={scaling:.2f}x",
                 pred_plan=pc["plan"], pred_maple=pc["maple"],
                 pred_row_atomic=pc["row_atomic"], n_shards=shards,
                 scaling=round(scaling, 3),
                 per_shard_pred=[round(c, 1)
                                 for c in plan.per_shard_cycles()],
                 devices_present=len(jax.local_devices()))


def partitioned_2d_sweep(rng, *, smoke: bool = False):
    """2-D ``(shard, col)`` mesh plans: the dense-operand memory axis.

    Column panels change *placement*, not the schedule — ``pred_plan``
    (golden-gated) is per-output-column-tile and must match the 1-D plan
    at the same shard count exactly; what moves is ``b_bytes_per_device``
    (each device holds ``ceil(N / C)`` columns of B instead of all of
    it — asserted to shrink by exactly the panel ratio) and
    ``padding_waste`` (the SPMD pad overhead the repack pass attacks,
    recorded pre/post so the trajectory shows what repack buys).
    ``scaling`` stays the device-count column vs the (1, 1) mesh.
    """
    gm = gk = 16
    bm = bk = 16
    n, g = 128, 2
    reps = 3 if smoke else 8
    for kind in ("uniform", "power_law", "banded"):
        mask = _pattern_mask(kind, rng, gm, gk)
        d = _masked_dense(rng, mask, bm, bk)
        a = BlockCSR.from_dense(d, (bm, bk))
        b3 = jnp.asarray(
            rng.standard_normal((g, gk * bk, n)).astype(np.float32))
        base = None
        base_bytes = None
        for shards, cols in ((1, 1), (2, 1), (2, 2), (4, 2)):
            plan = plan_partitioned_spmm(a, n_shards=shards, n_lanes=4,
                                         n_col_shards=cols)
            raw = plan_partitioned_spmm(a, n_shards=shards, n_lanes=4,
                                        n_col_shards=cols, repack=False)
            pc = plan.predicted_cycles()
            if base is None:
                base = pc["plan"]
                base_bytes = plan.dense_operand_bytes(n, g=g)
            b_bytes = plan.dense_operand_bytes(n, g=g)
            # column panels are a pure layout: per-device B bytes shrink
            # by exactly the panel ratio, never the schedule
            assert b_bytes * cols == base_bytes, (b_bytes, cols, base_bytes)
            onedim = plan_partitioned_spmm(a, n_shards=shards, n_lanes=4)
            assert pc["plan"] <= onedim.predicted_cycles()["plan"], \
                f"2-D plan slower than 1-D at D={shards}"
            scaling = base / max(pc["plan"], 1.0)
            fn = jax.jit(lambda aa, bb, p=plan: maple_spmm(aa, bb, plan=p))
            us = _time(fn, a, b3, reps=reps)
            emit(f"part2d_{kind}_D{shards}x{cols}", us,
                 f"pred_plan={pc['plan']:.0f}/b_kb={b_bytes / 1024:.0f}"
                 f"/waste={plan.padding_waste:.3f}",
                 pred_plan=pc["plan"], pred_maple=pc["maple"],
                 pred_row_atomic=pc["row_atomic"], n_shards=shards,
                 n_col_shards=cols, scaling=round(scaling, 3),
                 b_bytes_per_device=b_bytes,
                 padding_waste=round(plan.padding_waste, 4),
                 padding_waste_no_repack=round(raw.padding_waste, 4),
                 devices_present=len(jax.local_devices()))


def autotune_sweep(rng, *, smoke: bool = False):
    """Autotuned plan (``kernels.autotune.plan_search``, surrogate-only)
    vs the hand-tuned default plan on every golden pattern.

    The acceptance bar is asserted right here, not just recorded: the
    searched plan's predicted cycles must be ≤ the default's on every
    uniform / power-law / banded record (the search always scores the
    default config, so a violation means the autotuner is broken, not
    unlucky).  ``pred_plan`` (the autotuned makespan) is golden-gated
    like every other deterministic surrogate number; the measured columns
    come from the interleaved timer.
    """
    gm = gk = 16
    bm = bk = 16
    n = 128
    reps = 5 if smoke else 10
    budget = 24
    for kind in ("uniform", "power_law", "banded"):
        mask = _pattern_mask(kind, rng, gm, gk)
        d = _masked_dense(rng, mask, bm, bk)
        a = BlockCSR.from_dense(d, (bm, bk))
        b = jnp.asarray(rng.standard_normal((gk * bk, n)).astype(np.float32))
        default = plan_spmm(a)
        tuned, rep = plan_search(a, budget=budget, use_cache=False,
                                 full=True)
        pred_def = default.predicted_cycles()["plan"]
        pred_auto = tuned.predicted_cycles()["plan"]
        if pred_auto > pred_def:
            raise RuntimeError(
                f"autotune_{kind}: searched plan predicts {pred_auto:.0f} "
                f"cycles vs default {pred_def:.0f} — the never-worse "
                f"guarantee is broken")
        times = _time_interleaved(
            {"default": jax.jit(
                lambda aa, bb, p=default: maple_spmm(aa, bb, plan=p)),
             "auto": jax.jit(
                 lambda aa, bb, p=tuned: maple_spmm(aa, bb, plan=p))},
            {"default": (a, b), "auto": (a, b)}, reps=reps)
        cfg = rep.best_config
        emit(f"autotune_{kind}", times["auto"],
             f"pred_auto={pred_auto:.0f}/pred_default={pred_def:.0f}"
             f"/default_us={times['default']:.0f}"
             f"/lanes={cfg['n_lanes']}/chunk={cfg['chunk']}"
             f"/atomic={int(cfg['row_atomic'])}",
             pred_plan=pred_auto, pred_default=pred_def,
             default_us=round(times["default"], 1),
             pred_speedup=round(pred_def / max(pred_auto, 1.0), 3),
             n_built=rep.n_built, n_candidates=rep.n_candidates,
             tuned_n_lanes=cfg["n_lanes"], tuned_chunk=cfg["chunk"],
             tuned_row_atomic=bool(cfg["row_atomic"]),
             tuned_fused=cfg["fused"])


def formats_sweep(rng, *, smoke: bool = False):
    """Format layer (``core.formats``) + similarity reorder knob
    (``kernels.reorder``), per golden pattern.

    Two contracts are asserted right here, not just recorded:

    * **cross-format bit-identity** — the ELL and bitmap containers lower
      onto the same canonical-order compact payload as BlockCSR, so one
      plan executes all three and the outputs must be ``np.array_equal``
      (any mismatch is a converter ordering bug, not noise);
    * **reorder never-worse** — ``plan_search(reorder="auto")`` searches a
      strict superset of the unreordered space at a budget covering the
      full enumeration, so its winner's predicted cycles must be ≤ the
      unreordered winner's on every pattern.

    The payload is thinned *inside* live blocks (element occupancy ~60%)
    so the reorder pass has real intra-block sparsity to exploit;
    ``density_before``/``density_after`` record the intra-block fill the
    permutation buys and ``pred_plan`` (golden-gated) the cycles the
    surrogate credits it with.  The ``_ell`` / ``_bitmap`` rows record the
    **one-time lowering cost** (host pattern walk + payload gather into
    canonical order) — per-call the formats are the identical plan on the
    identical payload, and the repo idiom converts once outside jit and
    closes the jitted step over the result (the containers' pattern
    metadata is a pytree leaf, so they cannot be jit arguments).  Those
    rows are deliberately not golden.
    """
    gm = gk = 16
    bm = bk = 16
    n = 128
    reps = 5 if smoke else 10
    for kind in ("uniform", "power_law", "banded"):
        mask = _pattern_mask(kind, rng, gm, gk)
        d = _masked_dense(rng, mask, bm, bk)
        d *= (rng.random(d.shape) < 0.6)   # intra-block element sparsity
        a = BlockCSR.from_dense(d, (bm, bk))
        ell = to_ell(a)
        bmp = to_bitmap(a)
        b = jnp.asarray(rng.standard_normal((gk * bk, n)).astype(np.float32))

        plan = plan_spmm(a)
        pc = plan.predicted_cycles()
        outs = {f: np.asarray(maple_spmm(op, b, plan=plan))
                for f, op in (("bcsr", a), ("ell", ell), ("bitmap", bmp))}
        for f in ("ell", "bitmap"):
            if not np.array_equal(outs["bcsr"], outs[f]):
                raise RuntimeError(
                    f"formats_{kind}: {f} output is not bit-identical to "
                    f"BlockCSR — canonical-order lowering broken")

        p_no, rep_no = plan_search(a, use_cache=False, full=True,
                                   budget=256)
        p_auto, rep_auto = plan_search(a, use_cache=False, full=True,
                                       budget=256, reorder="auto")
        pred_no = p_no.predicted_cycles()["plan"]
        pred_auto = p_auto.predicted_cycles()["plan"]
        if pred_auto > pred_no:
            raise RuntimeError(
                f"formats_{kind}: reorder='auto' winner predicts "
                f"{pred_auto:.0f} cycles vs {pred_no:.0f} without — the "
                f"never-worse guarantee is broken")
        rr = reorder_rows(a)

        fns = {
            "bcsr": jax.jit(lambda op, bb, p=plan: maple_spmm(op, bb, plan=p)),
            "reorder_auto": jax.jit(
                lambda op, bb, p=p_auto: maple_spmm(op, bb, plan=p))}
        times = _time_interleaved(
            fns, {"bcsr": (a, b), "reorder_auto": (a, b)}, reps=reps)
        emit(f"formats_{kind}_bcsr", times["bcsr"],
             f"pred_plan={pc['plan']:.0f}", pred_plan=pc["plan"],
             pred_maple=pc["maple"], pred_row_atomic=pc["row_atomic"])
        for f, op in (("ell", ell), ("bitmap", bmp)):
            lower_us = _time(
                lambda op=op: as_block_csr(op).blocks, reps=reps)
            emit(f"formats_{kind}_{f}", lower_us, "lowering_once",
                 lowering_us=round(lower_us, 1))
        cfg = rep_auto.best_config
        emit(f"formats_{kind}_reorder_auto", times["reorder_auto"],
             f"pred_auto={pred_auto:.0f}/pred_no_reorder={pred_no:.0f}"
             f"/reorder={int(bool(cfg['reorder']))}"
             f"/density={rr.density_before:.2f}->{rr.density_after:.2f}",
             pred_plan=pred_auto, pred_no_reorder=pred_no,
             reorder_chosen=bool(cfg["reorder"]),
             density_before=round(rr.density_before, 4),
             density_after=round(rr.density_after, 4),
             n_candidates=rep_auto.n_candidates, n_built=rep_auto.n_built)

    # structured occupancy where the permutation provably wins: even
    # element rows live in the left block-column half, odd rows in the
    # right, so every original block is half-filled — grouping even and
    # odd rows halves the live block count (density 0.5 -> 1.0).  The
    # random-occupancy patterns above keep the knob honest (no structure,
    # no win); this row pins that the surrogate takes the win when the
    # structure exists.
    m, k = gm * bm, gk * bk
    d = rng.standard_normal((m, k)).astype(np.float32)
    colmask = np.zeros((m, k), bool)
    colmask[0::2, :k // 2] = True
    colmask[1::2, k // 2:] = True
    a = BlockCSR.from_dense(d * colmask, (bm, bk))
    b = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
    rr = reorder_rows(a)
    if not rr.density_after > rr.density_before:
        raise RuntimeError(
            f"formats_interleaved: reorder found no density win "
            f"({rr.density_before:.2f} -> {rr.density_after:.2f}) on the "
            f"pattern built to have one")
    p_no, _ = plan_search(a, use_cache=False, full=True, budget=256)
    p_auto, rep_auto = plan_search(a, use_cache=False, full=True,
                                   budget=256, reorder="auto")
    pred_no = p_no.predicted_cycles()["plan"]
    pred_auto = p_auto.predicted_cycles()["plan"]
    if pred_auto > pred_no:
        raise RuntimeError(
            f"formats_interleaved: reorder='auto' winner predicts "
            f"{pred_auto:.0f} cycles vs {pred_no:.0f} without")
    times = _time_interleaved(
        {"no": jax.jit(lambda aa, bb, p=p_no: maple_spmm(aa, bb, plan=p)),
         "auto": jax.jit(
             lambda aa, bb, p=p_auto: maple_spmm(aa, bb, plan=p))},
        {"no": (a, b), "auto": (a, b)}, reps=reps)
    cfg = rep_auto.best_config
    emit("formats_interleaved_reorder_auto", times["auto"],
         f"pred_auto={pred_auto:.0f}/pred_no_reorder={pred_no:.0f}"
         f"/reorder={int(bool(cfg['reorder']))}"
         f"/density={rr.density_before:.2f}->{rr.density_after:.2f}",
         pred_plan=pred_auto, pred_no_reorder=pred_no,
         no_reorder_us=round(times["no"], 1),
         reorder_chosen=bool(cfg["reorder"]),
         density_before=round(rr.density_before, 4),
         density_after=round(rr.density_after, 4))


def schedule_sweep(rng, *, smoke: bool = False):
    """Planned vs row-atomic vs naive schedules across sparsity patterns.

    Predicted cycles come from the SAME ``core.maple`` model the analytics
    use (`SpmmPlan.predicted_cycles`): `plan` is the realized lane
    makespan, `maple`/`row_atomic` the analytical schedules.  Plans are
    built once and closed over by a jitted call — what serving does — so
    us_per_call measures compiled execution, which tracks total grid
    steps: the load-balanced plan's makespan win over row-atomic shows up
    directly.  The three schedules are timed interleaved (round-robin)
    so drifting CPU load cannot bias one variant's column.
    """
    gm = gk = 16
    bm = bk = 16
    n, n_lanes = 128, 8
    reps = 5 if smoke else 20
    for kind in ("uniform", "power_law", "banded"):
        mask = _pattern_mask(kind, rng, gm, gk)
        d = _masked_dense(rng, mask, bm, bk)
        a = BlockCSR.from_dense(d, (bm, bk))
        b = jnp.asarray(rng.standard_normal((gk * bk, n)).astype(np.float32))
        plans = {sched: plan_spmm(a, n_lanes=n_lanes,
                                  row_atomic=(sched == "row_atomic"))
                 for sched in ("row_atomic", "balanced")}
        fns = {"naive": jax.jit(lambda aa, bb: maple_spmm(
            aa, bb, schedule="naive"))}
        fns.update({sched: jax.jit(
            lambda aa, bb, p=p: maple_spmm(aa, bb, plan=p))
            for sched, p in plans.items()})
        times = _time_interleaved(fns, {s: (a, b) for s in fns}, reps=reps)
        for sched in ("naive", "row_atomic", "balanced"):
            if sched == "naive":
                emit(f"spmm_{kind}_{sched}", times[sched],
                     f"blocks={int(mask.sum())}", blocks=int(mask.sum()))
            else:
                pc = plans[sched].predicted_cycles()
                emit(f"spmm_{kind}_{sched}", times[sched],
                     f"pred_plan={pc['plan']:.0f}"
                     f"/maple={pc['maple']:.0f}"
                     f"/row_atomic={pc['row_atomic']:.0f}",
                     pred_plan=pc["plan"], pred_maple=pc["maple"],
                     pred_row_atomic=pc["row_atomic"],
                     bytes_out=plans[sched].output_traffic_bytes(1, n))
    if smoke:
        return

    # batched RHS: one grid launch vs the host loop it replaces.  NB in
    # interpret mode XLA fuses the jitted loop into one program, so the
    # loop can even win here; the batched grid's advantage — a single
    # dispatch whose G axis is megacore-parallel — is a TPU property.
    # What this row pins on CPU is correctness and call-count, not speed.
    mask = _pattern_mask("power_law", rng, gm, gk)
    d = _masked_dense(rng, mask, bm, bk)
    a = BlockCSR.from_dense(d, (bm, bk))
    g = 4
    b3 = jnp.asarray(rng.standard_normal((g, gk * bk, n)).astype(np.float32))
    plan = plan_spmm(a, n_lanes=n_lanes)
    times = _time_interleaved(
        {"batched": jax.jit(lambda aa, bb: maple_spmm(aa, bb, plan=plan)),
         "hostloop": jax.jit(lambda aa, bb: jnp.stack(
             [maple_spmm(aa, bb[i], plan=plan) for i in range(g)]))},
        {"batched": (a, b3), "hostloop": (a, b3)}, reps=20)
    emit(f"spmm_batched_g{g}", times["batched"], "one_launch")
    emit(f"spmm_hostloop_g{g}", times["hostloop"], "per_rhs_launch")


def spgemm_sweep(rng):
    """Two-phase sparse-output SpGEMM, paper protocol C = A·A, across the
    same pattern axes as the SpMM sweep and priced with the same
    ``core.maple`` model (matching table format): ``pred_plan`` is the
    work makespan the lane schedule realizes, ``maple``/``row_atomic`` the
    analytical schedules at equal MAC budget.  The gustavson/dense rows
    are the jnp oracle twins; ``max_err`` pins the kernel to the dense
    oracle.  B is never densified on the kernel path — the plan holds B as
    compressed row panels.
    """
    m, n_lanes = 96, 8
    for kind in ("uniform", "power_law", "banded"):
        mask = sparsity.element_pattern_mask(kind, rng, m, m)
        d = (mask * rng.standard_normal((m, m))).astype(np.float32)
        a = CSR.from_dense(d)
        plans = {sched: plan_spgemm(
            a, a, n_lanes=n_lanes,
            balance={"balanced": "work", "row_atomic": "fibers",
                     "naive": "none"}[sched])
            for sched in ("naive", "row_atomic", "balanced")}
        # all five rows of one pattern timed round-robin: the schedule
        # comparison AND the oracle twins share any contention window
        fns = {sched: jax.jit(
            lambda aa, p=p: maple_spgemm(aa, aa, plan=p).value)
            for sched, p in plans.items()}
        fns["gustavson"] = lambda aa: spmspm_rowwise(aa, aa)
        fns["dense"] = lambda aa: dense_oracle(aa, aa)
        times = _time_interleaved(fns, {s: (a,) for s in fns}, reps=5)
        for sched, plan in plans.items():
            pc = plan.predicted_cycles()
            emit(f"spgemm_{kind}_{sched}", times[sched],
                 f"pred_plan={pc['plan']:.0f}"
                 f"/maple={pc['maple']:.0f}"
                 f"/row_atomic={pc['row_atomic']:.0f}",
                 pred_plan=pc["plan"], pred_maple=pc["maple"],
                 pred_row_atomic=pc["row_atomic"])
        c = maple_spgemm(a, a)
        err = float(np.abs(np.asarray(c.to_dense())
                           - np.asarray(dense_oracle(a, a))).max())
        emit(f"spgemm_{kind}_gustavson", times["gustavson"], "oracle")
        emit(f"spgemm_{kind}_dense", times["dense"], f"max_err={err:.1e}",
             max_err=err)


def autodiff_sweep(rng):
    """Fwd+bwd through the differentiable kernels, per sparsity pattern.

    The backward of the SpMM is two more sparse passes — ``dB = A^T @ dC``
    on the cached transpose-side plan and the block SDDMM for ``dA`` — so
    the interesting number next to measured time is the *predicted* cycle
    count from the same ``core.maple`` model the forward sweep prints,
    now **counting the A^T pass** (``SpmmTrainPlan.predicted_cycles``:
    ``plan = fwd + A^T`` lane makespans; the SDDMM revisits the forward's
    block set, priced by the fwd entry).  The SpGEMM rows time the
    value-level VJP (element SDDMM + transposed-operand scatter) under a
    prebuilt symbolic plan.
    """
    gm = gk = 16
    bm = bk = 16
    n, n_lanes = 128, 8
    for kind in ("uniform", "power_law", "banded"):
        mask = _pattern_mask(kind, rng, gm, gk)
        d = _masked_dense(rng, mask, bm, bk)
        a = BlockCSR.from_dense(d, (bm, bk))
        b = jnp.asarray(rng.standard_normal((gk * bk, n)).astype(np.float32))
        # forward-only vs fwd+bwd on the same train plan: the gap is the
        # A^T pass + SDDMM the VJP adds.
        tp = plan_spmm_vjp(a, n_lanes=n_lanes)
        fwd = jax.jit(lambda blk, bb, w=a: maple_spmm(
            BlockCSR(blk, w.block_col, w.block_row, w.row_ptr, w.shape,
                     w.block_shape), bb, plan=tp))
        grad = jax.jit(jax.grad(
            lambda blk, bb, w=a: jnp.sum(maple_spmm(
                BlockCSR(blk, w.block_col, w.block_row, w.row_ptr, w.shape,
                         w.block_shape), bb, plan=tp) ** 2),
            argnums=(0, 1)))
        # fwd vs fwd+bwd interleaved: their *gap* is the reported number
        # (the A^T pass + SDDMM), so load drift between the two loops
        # would land straight in the column of interest
        times = _time_interleaved(
            {"fwd": fwd, "grad": lambda blk, bb: grad(blk, bb)[0]},
            {"fwd": (a.blocks, b), "grad": (a.blocks, b)}, reps=10)
        us_f, us = times["fwd"], times["grad"]
        pc = tp.predicted_cycles()
        emit(f"spmm_grad_{kind}", us,
             f"fwd_us={us_f:.0f}/pred_fwd={pc['fwd_plan']:.0f}"
             f"/pred_at={pc['at_plan']:.0f}",
             fwd_us=round(us_f, 1), pred_fwd=pc["fwd_plan"],
             pred_at=pc["at_plan"])

    m = 96
    for kind in ("uniform", "power_law", "banded"):
        mask = sparsity.element_pattern_mask(kind, rng, m, m)
        d = (mask * rng.standard_normal((m, m))).astype(np.float32)
        a = CSR.from_dense(d)
        plan = plan_spgemm(a, a, n_lanes=8)
        grad = jax.jit(jax.grad(
            lambda av, w=a: jnp.sum(maple_spgemm(
                CSR(av, w.col_id, w.row_ptr, w.shape),
                CSR(av, w.col_id, w.row_ptr, w.shape),
                plan=plan).value ** 2)))
        us = _time(grad, a.value, reps=5)
        pc = plan.predicted_cycles()
        emit(f"spgemm_grad_{kind}", us,
             f"pred_plan={pc['plan']:.0f}/maple={pc['maple']:.0f}",
             pred_plan=pc["plan"], pred_maple=pc["maple"])


def misc_sweeps(rng):
    # BSR spmm across block densities (the Maple skip-rate table)
    m = k = n = 256
    bm = bk = 64
    for density in (0.1, 0.3, 0.6, 1.0):
        d = rng.standard_normal((m, k)).astype(np.float32)
        mask = rng.random((m // bm, k // bk)) < density
        for i in range(m // bm):
            for j in range(k // bk):
                if not mask[i, j]:
                    d[i*bm:(i+1)*bm, j*bk:(j+1)*bk] = 0
        a = BlockCSR.from_dense(d, (bm, bk),
                                n_blocks_max=max(int(mask.sum()), 1))
        b = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
        # seed-era table: keep the seed kernel so rows stay comparable
        us = _time(lambda: maple_spmm(a, b, schedule="naive"))
        blocks_moved = int(mask.sum())
        total_blocks = (m // bm) * (k // bk)
        emit(f"maple_spmm_d{density}", us,
             f"blocks={blocks_moved}/{total_blocks}",
             blocks=blocks_moved, total_blocks=total_blocks)

    # element-granular spmspm (paper protocol C=A×A, small clone)
    ad = ((rng.random((128, 128)) < 0.05)
          * rng.standard_normal((128, 128))).astype(np.float32)
    a = CSR.from_dense(ad)
    us = _time(lambda: maple_spmspm(a, a))
    emit("maple_spmspm_csr", us, f"nnz={int(a.nnz)}", nnz=int(a.nnz))

    # jnp twin for reference
    us = _time(lambda: spmm_rowwise(a, a.to_dense()))
    emit("gustavson_jnp_ref", us, "oracle")

    # block-sparse local attention (banded BSR tile skipping)
    from repro.kernels.block_attn import local_window_kv_map
    q = jnp.asarray(rng.standard_normal((1, 512, 4, 32)).astype(np.float32))
    for w_win in (64, 128, 256):
        us = _time(lambda: local_block_attention(q, q, q, window=w_win,
                                                 bq=64, bk=64))
        kvm = local_window_kv_map(512, w_win, 64, 64)
        touched = int((kvm >= 0).sum())
        emit(f"local_block_attn_w{w_win}", us,
             f"tiles={touched}/{(512//64)**2}", tiles=touched)

    # MoE grouped GEMM
    sizes = jnp.asarray([256, 128, 0, 384], jnp.int32)
    t = int(sizes.sum())
    x = jnp.asarray(rng.standard_normal((t, 256)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((4, 256, 256)).astype(np.float32))
    us = _time(lambda: moe_expert_gemm(x, sizes, w))
    emit("moe_expert_gemm", us, f"groups={sizes.tolist()}")


GOLDEN_KEYS = ("pred_plan", "pred_fwd", "pred_at")

# the golden configs every gated run (smoke included) MUST emit — the
# reverse half of the coverage guarantee: a sweep that stops emitting
# these fails the gate instead of silently shrinking it
SMOKE_GOLDEN_NAMES = tuple(
    [f"spmm_{k}_{s}" for k in ("uniform", "power_law", "banded")
     for s in ("row_atomic", "balanced")]
    + [f"fused_{k}_L8_{f}" for k in ("uniform", "power_law", "banded")
       for f in ("rmw", "compact")]
    + [f"part_{k}_D{d}" for k in ("uniform", "power_law", "banded")
       for d in (1, 2, 4, 8)]
    + [f"part2d_{k}_D{d}x{c}" for k in ("uniform", "power_law", "banded")
       for d, c in ((1, 1), (2, 1), (2, 2), (4, 2))]
    + [f"autotune_{k}" for k in ("uniform", "power_law", "banded")]
    + [f"formats_{k}_bcsr" for k in ("uniform", "power_law", "banded")]
    + [f"formats_{k}_reorder_auto"
       for k in ("uniform", "power_law", "banded", "interleaved")])


def check_against(baseline_path: str, tol: float) -> int:
    """Golden-config gate: predicted cycles are deterministic, so any
    drift is a planner change.  The gate is two-sided and rename-proof:

    * a config regressing more than ``tol`` fails outright;
    * an *improvement* beyond ``tol`` also fails, demanding a baseline
      refresh — otherwise the ratchet silently loosens (ship a 2x win
      without refreshing and a later 2x regression hides inside the old
      bound);
    * coverage is checked both ways: every golden config this run
      produced must exist in the baseline (renames can't dodge the
      gate), and every ``SMOKE_GOLDEN_NAMES`` entry must appear in this
      run (a sweep that stops emitting can't silently shrink it).

    Wall time is reported but never gated (CI boxes are noisy).  Refresh
    with: ``python benchmarks/kernel_bench.py --json BENCH_kernels.json``.
    """
    with open(baseline_path) as f:
        baseline = {r["name"]: r for r in json.load(f)["records"]}
    failures = []
    checked = 0
    produced = {r["name"] for r in RECORDS}
    for name in SMOKE_GOLDEN_NAMES:
        if name not in produced:
            failures.append(f"{name}: expected golden config was not "
                            f"emitted this run — sweep dropped?")
    for rec in RECORDS:
        # `legacy_`-prefixed keys price retired dataflows (record schema
        # contract) — they must never become golden comparisons
        golden = [k for k in GOLDEN_KEYS if k in rec and "legacy" not in k]
        if not golden:
            continue
        base = baseline.get(rec["name"])
        if base is None:
            failures.append(
                f"{rec['name']}: golden config missing from baseline — "
                f"renamed sweep? refresh {baseline_path}")
            continue
        for key in golden:
            if key not in base:
                failures.append(f"{rec['name']}.{key}: missing from "
                                f"baseline — refresh {baseline_path}")
                continue
            checked += 1
            if rec[key] > base[key] * (1.0 + tol):
                failures.append(
                    f"{rec['name']}.{key}: {rec[key]:.0f} vs baseline "
                    f"{base[key]:.0f} (>{tol:.0%} regression)")
            elif rec[key] < base[key] * (1.0 - tol):
                failures.append(
                    f"{rec['name']}.{key}: {rec[key]:.0f} vs baseline "
                    f"{base[key]:.0f} (>{tol:.0%} improvement — refresh "
                    f"{baseline_path} so the ratchet keeps the win)")
    print(f"# check: {checked} golden predicted-cycle values vs "
          f"{baseline_path}", file=sys.stderr)
    if failures:
        for msg in failures:
            print(f"# REGRESSION {msg}", file=sys.stderr)
        return 1
    if checked == 0:
        print("# REGRESSION check matched no golden configs "
              "(baseline stale?)", file=sys.stderr)
        return 1
    return 0


SWEEP_NAMES = ("schedule", "fused", "partitioned", "partitioned_2d",
               "autotune", "formats", "spgemm", "autodiff", "misc")


def run(smoke: bool = False, only: str | None = None):
    # each sweep owns a fixed-seed rng so the smoke subset draws the SAME
    # workloads as the full baseline run — the --check gate compares
    # predicted cycles across runs, which only means something when the
    # patterns match bit-for-bit
    def want(name):
        return only is None or only == name

    print("name,us_per_call,derived")
    if want("schedule"):
        schedule_sweep(np.random.default_rng(0), smoke=smoke)
    if want("fused"):
        fused_dataflow_sweep(np.random.default_rng(1), smoke=smoke)
    if want("partitioned"):
        partitioned_sweep(np.random.default_rng(5), smoke=smoke)
    if want("partitioned_2d"):
        partitioned_2d_sweep(np.random.default_rng(7), smoke=smoke)
    if want("autotune"):
        autotune_sweep(np.random.default_rng(6), smoke=smoke)
    if want("formats"):
        formats_sweep(np.random.default_rng(8), smoke=smoke)
    if smoke:
        return
    if want("spgemm"):
        spgemm_sweep(np.random.default_rng(2))
    if want("autodiff"):
        autodiff_sweep(np.random.default_rng(3))
    if want("misc"):
        misc_sweeps(np.random.default_rng(4))


def _git_rev() -> str:
    """Short revision stamp for --json records (perf trajectory
    attribution); "unknown" outside a git checkout."""
    import subprocess
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, timeout=10, check=True).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", metavar="PATH",
                    help="write machine-readable records to PATH")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced golden subset (CI)")
    ap.add_argument("--check", metavar="BASELINE",
                    help="fail if predicted cycles regress vs BASELINE json")
    ap.add_argument("--tol", type=float, default=0.10,
                    help="allowed predicted-cycle regression (default 0.10)")
    ap.add_argument("--only", metavar="SWEEP", choices=SWEEP_NAMES,
                    help="run a single sweep (its in-sweep assertions are "
                         "the gate; incompatible with --check, whose "
                         "coverage contract needs every golden sweep)")
    args = ap.parse_args(argv)

    if args.check and args.only:
        ap.error("--check needs the full golden set; drop --only")
    compile_cache.enable()

    run(smoke=args.smoke, only=args.only)

    if args.json:
        payload = {"schema": 2, "smoke": bool(args.smoke),
                   "backend": jax.default_backend(),
                   "git_rev": _git_rev(), "records": RECORDS}
        # the surrogate-to-wall-clock affine fit: what objective="us"
        # searches load (kernels.autotune), and the rank correlation that
        # validates trusting the surrogate ordering.  Fit ONLY over the
        # planned-SpMM family sharing one RHS geometry (the schedule +
        # autotune sweeps: K=256, N=128, single RHS) — an affine
        # cycles→µs map is per-workload-shape, and mixing the fused
        # sweep's (G=2, N=256) records in yields a nonsense (negative-
        # slope) fit dominated by geometry, not schedule quality
        cal_family = [r for r in RECORDS
                      if (r["name"].startswith("spmm_")
                          and r["name"].split("_")[-1] in ("atomic",
                                                           "balanced"))
                      or r["name"].startswith("autotune_")]
        cal = fit_calibration(cal_family, backend=jax.default_backend())
        if cal is not None:
            payload["calibration"] = cal
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
        print(f"# wrote {len(RECORDS)} records to {args.json}"
              f" (rev {payload['git_rev']})", file=sys.stderr)
    if args.check:
        return check_against(args.check, args.tol)
    return 0


if __name__ == "__main__":
    sys.exit(main())
