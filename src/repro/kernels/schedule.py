"""Load-balanced execution planning for the Maple kernels — the unified
plan layer shared by SpMM (BSR × dense) and SpGEMM (CSR × CSR → CSR).

The analytical model (``core.maple.maple_pe_cycles``) makes the paper's
central point quantitative: a row-wise product schedule is lower-bounded by
its heaviest row unless row work can be split, and the ``m``-MAC Maple PE
drains a row's partial-product pool in ``ceil(p/m)`` cycles precisely
because it is *not* row-atomic.  The seed Pallas kernel, however, walked
blocks in BlockCSR construction order — one unsplit block-row after the
next — which is the MatRaptor-style row-atomic baseline, not Maple.

This module closes that gap at kernel granularity with one abstraction:

:class:`ExecutionPlan` — a static lane schedule.  Per lane ``l`` / step
``s`` it records which operand slot to consume (``order``), which output
row the step accumulates into (``step_row``), which panel of B to fetch
(``step_col``, ``-1`` on pad steps) and which rows each lane flushes
(``written``).  Work items are LPT-packed (longest first onto the
least-loaded lane, a ``(2 - 1/L)×``-optimal greedy) and each lane is
row-sorted so every (lane, row) PSB run zeroes once and flushes once.
Padded container slots are dropped from the plan entirely instead of being
streamed as zero work.

Two specializations:

* :class:`SpmmPlan` (:func:`plan_spmm`) — block granularity.  Heavy
  block-rows are **split into bounded-size row-chunks** (the multi-MAC
  ``m`` knob realized as parallel accumulation lanes; chunks of one row
  accumulate concurrently and are merged *inside the kernel* — the plan
  derives the first/last-flush flags and compact flush-slot maps the
  fused output dataflow runs on — removing the ``max_row`` term of the
  cycle model without ever materializing a per-lane output buffer).
* :class:`SpgemmPlan` (:func:`plan_spgemm`) — element granularity, the
  sparse-output C = A·B path.  Construction *is* the **symbolic phase** of
  the two-phase SpGEMM protocol: it computes the exact output sparsity
  pattern (``out_row_ptr`` / ``out_cols``) and the per-partial PSB scatter
  positions from A and B metadata alone, then balances whole A rows over
  lanes by **work** — Σ nnz(B[k',:]) per row, the quantity
  ``core.maple.analyze_spgemm`` already counts — rather than by nnz(A)
  alone.  (Rows stay atomic here because each output row owns one
  column-indexed PSB; the balancing axis is which lane gets which rows.)

Plans are host-side numpy over *static metadata* (the sparsity pattern),
so planning composes with jit the same way container construction does:
the pattern is fixed at trace time, the payload is traced.

One source of truth with the analytics: :meth:`ExecutionPlan.predicted_cycles`
prices the realized schedule and both paper schedules with the *same*
:func:`core.maple.maple_pe_cycles` / :func:`core.maple.baseline_pe_cycles`
used by the event model, over :func:`core.maple.analyze_spgemm` stats
(:func:`bsr_stats` lifts them to the block pattern for SpMM).
"""

from __future__ import annotations

import dataclasses
import heapq
from typing import Dict, List, Optional, Sequence, Tuple, TypeVar

import numpy as np

from repro.core.csr import (CSR, BlockCSR, bsr_transpose_meta,
                            spgemm_row_upper_bounds)
from repro.core.formats import (as_block_csr, as_element_csr,
                                block_pattern_meta, ell_slots)
from repro.core.maple import (SpGEMMStats, analyze_spgemm,
                              baseline_pe_cycles, expand_partials,
                              maple_pe_cycles)

_T = TypeVar("_T")


def bsr_stats(a: BlockCSR) -> SpGEMMStats:
    """Block-granular workload statistics of one BSR × dense-panel run.

    Lifts ``analyze_spgemm`` to MXU granularity by analyzing the *block
    pattern* against an identity B: every non-zero (bm, bk) block is one
    block-MAC against the B row-panel its block-column selects, so
    ``row_partials[i]`` = non-zero blocks in block-row i and
    ``partial_products`` = total non-zero blocks — exactly the per-step
    work units the Pallas kernels execute per output-column tile.
    """
    gm, gk = a.n_block_rows, a.n_block_cols
    rptr = np.asarray(a.row_ptr).astype(np.int32)
    nnzb = int(rptr[-1])
    cols = np.asarray(a.block_col).astype(np.int32)[:max(nnzb, 1)]
    pattern = CSR(value=np.zeros(max(nnzb, 1), np.float32),
                  col_id=cols, row_ptr=rptr, shape=(gm, gk))
    eye = CSR(value=np.ones(gk, np.float32),
              col_id=np.arange(gk, dtype=np.int32),
              row_ptr=np.arange(gk + 1, dtype=np.int32), shape=(gk, gk))
    return analyze_spgemm(pattern, eye)


def _lpt_pack(weighted: Sequence[Tuple[int, _T]],
              n_lanes: int) -> Tuple[List[List[_T]], np.ndarray]:
    """LPT greedy: pre-sorted ``(weight, item)`` onto the least-loaded lane.

    Caller sorts (longest first, deterministic tie-break); ties across
    equally-loaded lanes resolve to the lowest lane index.  Returns the
    per-lane item lists and the realized per-lane loads.
    """
    heap = [(0, l) for l in range(n_lanes)]  # already heap-ordered
    lanes: List[List[_T]] = [[] for _ in range(n_lanes)]
    loads = np.zeros(n_lanes, np.int64)
    for w, item in weighted:
        load, l = heapq.heappop(heap)
        lanes[l].append(item)
        loads[l] += int(w)
        heapq.heappush(heap, (load + int(w), l))
    return lanes, loads


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """A static lane schedule for one Maple kernel launch.

    Arrays are host numpy (they parameterize the grid and the scalar
    prefetch, like the sparse containers' metadata).  Layout, per lane
    ``l`` and step ``s``:

    * ``order[l, s]``    — operand slot to consume at this step (an index
      into ``a.blocks`` for SpMM, a flat ELL slot ``i·La + t`` for SpGEMM;
      0 on pad steps — pad steps are identified by ``step_col == -1`` and
      contribute nothing),
    * ``step_row[l, s]`` — output row the step accumulates into (pad-step
      conventions are per-specialization — see the subclasses),
    * ``step_col[l, s]`` — which B panel to fetch, ``-1`` on pad steps
      (the container padding protocol),
    * ``written[l, r]``  — True iff lane ``l`` flushes a PSB for row ``r``.

    ``n_real_steps`` counts live steps; ``utilization`` the live fraction
    of issued slots.  ``predicted_cycles`` prices the realized schedule
    and both paper schedules with the shared ``core.maple`` model.
    """

    order: np.ndarray      # (n_lanes, steps) int32
    step_row: np.ndarray   # (n_lanes, steps) int32
    step_col: np.ndarray   # (n_lanes, steps) int32, -1 on pads
    written: np.ndarray    # (n_lanes, n_rows) bool
    chunk: int             # max slots per row-chunk (0 = rows atomic)
    n_rows: int
    n_real_steps: int      # live steps scheduled
    stats: SpGEMMStats

    @property
    def n_lanes(self) -> int:
        return self.order.shape[0]

    @property
    def steps(self) -> int:
        """Realized makespan: slots issued per lane (incl. bubbles)."""
        return self.order.shape[1]

    @property
    def utilization(self) -> float:
        """Live fraction of issued slots."""
        return self.n_real_steps / max(self.n_lanes * self.steps, 1)

    def _realized_makespan(self) -> float:
        """What the grid actually executes, in the plan's work unit."""
        return float(self.steps)

    def predicted_cycles(self) -> Dict[str, float]:
        """Cycle predictions that share the analytical model's arithmetic.

        ``plan``       — this schedule's realized makespan (work per lane,
                         what the kernel grid actually executes);
        ``maple``      — ``maple_pe_cycles`` with the lane array acting as
                         one m = n_lanes Maple PE (row pools drained at
                         n_lanes work-units/cycle — the paper's §IV
                         schedule);
        ``row_atomic`` — ``baseline_pe_cycles`` with rows pinned to lanes
                         (the MatRaptor bound).
        """
        return {
            "plan": self._realized_makespan(),
            "maple": maple_pe_cycles(self.stats, macs_per_pe=self.n_lanes,
                                     n_pes=1),
            "row_atomic": baseline_pe_cycles(self.stats, n_pes=self.n_lanes),
        }


class SpmmPlan(ExecutionPlan):
    """Block-granular plan for ``maple_spmm`` over one BlockCSR operand.

    The work unit is one non-zero (bm, bk) block-MAC; ``order`` gathers
    into ``a.blocks`` and ``step_col`` selects B block-columns.  Pad steps
    repeat the lane's last real row so each (lane, row) run stays one
    contiguous zero-once/flush-once PSB visit.

    The cross-lane reduction that merges chunks of a split row happens
    **inside the kernel** (the fused output dataflow — the per-lane
    ``(G, L, M, N)`` partial buffer of earlier revisions is gone), driven
    by metadata this plan derives once at construction:

    * ``fused`` — which fused output layout the kernel executes:

      - ``"rmw"`` — lanes run as a *sequential* grid dimension and flush
        straight into the single ``(G, M, N)`` output; the first lane to
        flush a row overwrites, later lanes read-modify-write in f32.
      - ``"compact"`` — lanes stay parallel and flush into compact
        per-lane tiles ``(G, L, r_max·bm, N)`` sized by ``written``
        (``r_max`` = most rows any lane flushes), merged by one
        scatter-add; no full-size lane buffer exists in either mode.

    * ``step_acc[l, s]`` — 1 where a flush must accumulate into the
      already-written output tile, 0 where this lane is the row's
      initializer (the lowest-indexed lane that flushes the row — grid
      traversal order).  Phantom runs (idle lanes draining pad steps)
      always accumulate, so they can never clobber a real tile.
    * ``flush_slot[l, s]`` / ``slot_row[l, t]`` — the compact layout's
      flush-slot map: lane ``l`` flushes its ``t``-th distinct row into
      slot ``t``; ``slot_row`` inverts that (``-1`` on dead slots, which
      the wrapper scatters into a sacrificial row).
    * ``row_mask`` — the ``(M,)`` rows-ever-flushed mask at *element*
      granularity, cached here so the rmw wrapper never rebuilds the
      ``jnp.repeat`` per call (empty block-rows are zero-masked with it).

    All of this is derived from ``order``/``step_row``/``written`` alone,
    so hand-built or lane-permuted plans stay self-consistent.
    """

    def __init__(self, *, order: np.ndarray, step_row: np.ndarray,
                 step_col: np.ndarray, written: np.ndarray, chunk: int,
                 n_block_rows: int, n_real_steps: int, stats: SpGEMMStats,
                 block_m: int, block_k: int, fused: str = "rmw"):
        # the full block shape is required (not defaulted): the cached
        # row_mask and traffic model are sized by block_m, step_col
        # indexes B panels at block_k granularity, and a silently wrong
        # default would only surface later as a confusing call-time
        # mismatch — or, for block_k, as silently wrong panels
        super().__init__(order=order, step_row=step_row, step_col=step_col,
                         written=written, chunk=chunk, n_rows=n_block_rows,
                         n_real_steps=n_real_steps, stats=stats)
        if fused not in ("rmw", "compact"):
            raise ValueError(f"unknown fused mode {fused!r}")
        n_lanes = order.shape[0]
        gm = n_block_rows
        rows = np.clip(step_row, 0, max(gm - 1, 0))
        any_writer = written.any(axis=0) if gm else np.zeros(0, bool)
        # lowest-indexed lane flushing each row == first flush in the
        # rmw grid traversal (lanes are a sequential axis there)
        first_lane = np.where(any_writer, written.argmax(axis=0), -1)
        lane_idx = np.arange(n_lanes, dtype=np.int64)[:, None]
        if gm:
            owns = np.take_along_axis(written, rows, axis=1)
            is_init = owns & (first_lane[rows] == lane_idx)
        else:
            is_init = np.zeros(step_row.shape, bool)
        step_acc = (~is_init).astype(np.int32)
        # compact flush slots: lane l's t-th distinct flushed row -> slot t
        r_max = max(int(written.sum(axis=1).max(initial=0)), 1)
        slot_of = np.zeros((n_lanes, max(gm, 1)), np.int32)
        slot_row = np.full((n_lanes, r_max), -1, np.int32)
        for l in range(n_lanes):
            rows_l = np.nonzero(written[l])[0]
            slot_of[l, rows_l] = np.arange(rows_l.size, dtype=np.int32)
            slot_row[l, :rows_l.size] = rows_l
        flush_slot = (np.take_along_axis(slot_of, rows, axis=1)
                      if gm else np.zeros(step_row.shape, np.int32))
        object.__setattr__(self, "fused", fused)
        object.__setattr__(self, "block_m", int(block_m))
        object.__setattr__(self, "block_k", int(block_k))
        object.__setattr__(self, "step_acc", step_acc)
        object.__setattr__(self, "flush_slot", flush_slot.astype(np.int32))
        object.__setattr__(self, "slot_row", slot_row)
        object.__setattr__(self, "r_max", r_max)
        object.__setattr__(self, "row_mask", np.repeat(any_writer, block_m))

    @property
    def n_block_rows(self) -> int:
        return self.n_rows

    def output_traffic_bytes(self, g: int, n_cols: int, *,
                             itemsize: int = 4,
                             mode: Optional[str] = None) -> int:
        """Output-side HBM bytes the dataflow moves (model estimate).

        ``mode`` defaults to the plan's ``fused`` layout.
        ``"legacy_epilogue"`` prices the *retired* full lane-buffer path
        for trajectory comparisons (write + re-read of ``(G, L, M, N)``
        plus the merged result) — it is not executable anymore, only
        priced, and the ``legacy_`` prefix is load-bearing: benchmark
        records derived from it carry the same prefix so the ``--check``
        regression gate can never mistake the dead mode for a live
        dataflow.  The old ``"epilogue"`` spelling raises, pointing here.
        """
        mode = mode or self.fused
        bm = self.block_m
        m = self.n_rows * bm
        tile_rows_flushed = int(self.written.sum())
        rows_written = int(self.written.any(axis=0).sum())
        final = g * m * n_cols * itemsize
        if mode == "rmw":
            # flushes write straight into the (G, M, N) result; every
            # accumulating flush re-reads the tile it merges into
            writes = g * tile_rows_flushed * bm * n_cols * itemsize
            rereads = g * max(tile_rows_flushed - rows_written, 0) \
                * bm * n_cols * itemsize
            return writes + rereads
        if mode == "compact":
            buf = g * self.n_lanes * self.r_max * bm * n_cols * itemsize
            return 2 * buf + final
        if mode == "legacy_epilogue":
            buf = g * self.n_lanes * m * n_cols * itemsize
            return 2 * buf + final
        if mode == "epilogue":
            raise ValueError(
                "the 'epilogue' dataflow was deleted; to price the "
                "retired lane-buffer path for trajectory comparison, ask "
                "for mode='legacy_epilogue' explicitly")
        raise ValueError(f"unknown traffic mode {mode!r}")


def _default_chunk(nnzb: int, n_lanes: int) -> int:
    # Bound the heaviest chunk near the balanced shard so LPT can always
    # level the lanes: ~4 chunks per lane of slack keeps the final-chunk
    # quantization error under a quarter shard.
    return max(1, -(-nnzb // (4 * n_lanes))) if nnzb else 1


def plan_spmm(a: BlockCSR, *, n_lanes: int = 8,
              chunk: Optional[int] = None,
              row_atomic: bool = False,
              fused: str = "auto") -> SpmmPlan:
    """Build a load-balanced lane schedule from BlockCSR metadata.

    ``a`` may be any blocked :class:`~repro.core.formats.SparseFormat`
    (``BlockCSR`` / ``EllPack`` / ``BitmapBlocked``) — non-BlockCSR
    operands lower onto the canonical metadata via
    ``core.formats.as_block_csr`` first, so one plan layer serves every
    storage format (the resulting plan's ``order`` indexes canonical
    packed slots, which is exactly what the execution wrapper lowers the
    payload to).

    ``row_atomic=True`` keeps every block-row whole (one chunk per row) —
    the MatRaptor-style baseline schedule, exposed so benchmarks and tests
    can price both on identical machinery.  It is **incompatible with an
    explicit ``chunk``**: the splitter would keep rows whole while the
    plan recorded the ignored chunk size, so a cache or search key built
    from the plan's knobs would alias distinct schedules — the
    combination raises instead.  Row-atomic plans record ``chunk = 0``
    (the rows-are-atomic convention ``SpgemmPlan`` already uses).

    ``fused`` selects the *preferred* in-kernel cross-lane merge layout
    (see :class:`SpmmPlan`); every plan derives both layouts' metadata,
    and the executing wrapper honors the preference only where it is
    valid: ``"rmw"`` needs the interpreter's revisited-output-tile
    re-fetch, so compiled (``interpret=False``) calls always run
    ``"compact"`` whatever the plan prefers.  ``"auto"`` resolves to
    ``"compact"`` — the only layout that lowers for the TPU — so the
    interpret-mode tests exercise the kernel the chip runs.
    """
    if not isinstance(a, BlockCSR):
        a = as_block_csr(a)
    if n_lanes < 1:
        raise ValueError(f"n_lanes={n_lanes} < 1")
    if fused == "auto":
        fused = "compact"
    if row_atomic and chunk is not None:
        raise ValueError(
            f"row_atomic=True keeps rows whole, so chunk={chunk} would be "
            f"silently ignored (and a plan/cache key built from it would "
            f"alias distinct plans) — drop one of the two")
    rptr = np.asarray(a.row_ptr).astype(np.int64)
    cols = np.asarray(a.block_col).astype(np.int32)
    gm = a.n_block_rows
    nnzb = int(rptr[-1])
    stats = bsr_stats(a)
    if row_atomic:
        chunk = 0                       # rows atomic (SpgemmPlan convention)
    elif chunk is None:
        chunk = _default_chunk(nnzb, n_lanes)
    elif chunk < 1:
        raise ValueError(f"chunk={chunk} < 1")

    # 1. split rows into chunks of <= `chunk` blocks: (row, lo, hi) over
    #    block indices.  Row-atomic keeps rows whole.
    chunks: List[Tuple[int, int, int]] = []
    for i in range(gm):
        lo, hi = int(rptr[i]), int(rptr[i + 1])
        if hi <= lo:
            continue
        if row_atomic:
            chunks.append((i, lo, hi))
        else:
            for s in range(lo, hi, chunk):
                chunks.append((i, s, min(s + chunk, hi)))

    # 2. LPT packing: longest chunk first onto the least-loaded lane.
    chunks.sort(key=lambda c: (-(c[2] - c[1]), c[0], c[1]))
    lanes, _ = _lpt_pack([(c[2] - c[1], c) for c in chunks], n_lanes)

    # 3. PSB contiguity: same-row chunks adjacent within each lane.
    for lane in lanes:
        lane.sort(key=lambda c: (c[0], c[1]))

    steps = max(1, max((sum(c[2] - c[1] for c in lane) for lane in lanes),
                       default=0))
    order = np.zeros((n_lanes, steps), np.int32)
    step_row = np.zeros((n_lanes, steps), np.int32)
    step_col = np.full((n_lanes, steps), -1, np.int32)
    written = np.zeros((n_lanes, gm), bool)
    n_real = 0
    for l, lane in enumerate(lanes):
        t = 0
        last_row = 0
        for (i, lo, hi) in lane:
            ln = hi - lo
            order[l, t:t + ln] = np.arange(lo, hi, dtype=np.int32)
            step_row[l, t:t + ln] = i
            step_col[l, t:t + ln] = cols[lo:hi]
            written[l, i] = True
            last_row = i
            t += ln
        n_real += t
        if t < steps:
            # pads extend the last run: same row, col = -1, zero payload
            step_row[l, t:] = last_row

    return SpmmPlan(order=order, step_row=step_row, step_col=step_col,
                    written=written, chunk=chunk, n_block_rows=gm,
                    n_real_steps=n_real, stats=stats,
                    block_m=a.block_shape[0], block_k=a.block_shape[1],
                    fused=fused)


# --------------------------------------------------------------------------
# Pattern hashing + knob enumeration (the autotuner's search space)
# --------------------------------------------------------------------------

def pattern_fingerprint(a: BlockCSR) -> str:
    """Stable content hash of a blocked operand's **sparsity pattern** —
    the plan cache key (``kernels.autotune``).

    Hashes exactly what planning reads, through the format-independent
    view ``core.formats.block_pattern_meta``: logical shape, block shape,
    ``row_ptr`` and the **live prefix** of ``block_col`` in canonical
    order.  Any blocked :class:`~repro.core.formats.SparseFormat` is
    accepted, and equivalent patterns fingerprint identically whatever
    format holds them (pinned in ``tests/test_formats.py``) — so the
    autotuner cache is shared across storage formats.  Deliberately
    *excluded*: the payload (plans are pattern-only) and the container
    capacity ``n_blocks_max`` (a plan gathers only live slots
    ``< nnzb``, so the same plan is valid for any capacity holding this
    pattern — two capacities of one pattern must hit the same cache
    line).  Host-side; raises on traced metadata like every planner.
    """
    import hashlib

    shape, block_shape, rptr, live_cols = block_pattern_meta(a)
    h = hashlib.sha256()
    h.update(np.asarray(tuple(shape) + tuple(block_shape),
                        np.int64).tobytes())
    h.update(np.ascontiguousarray(rptr, dtype=np.int64).tobytes())
    h.update(np.ascontiguousarray(live_cols, dtype=np.int32).tobytes())
    return h.hexdigest()


def _chunk_candidates(row_lens: np.ndarray, n_lanes: int) -> List[Optional[int]]:
    """Chunk-knob values worth trying for one lane count: the planner's
    default heuristic (``None``), a few fixed power-of-two bounds, and the
    longest row (== no splitting).  Deduped, deterministic order."""
    nnzb = int(row_lens.sum())
    max_len = int(row_lens.max(initial=0))
    seen: List[Optional[int]] = [None]
    resolved = {_default_chunk(nnzb, n_lanes)}
    for c in (1, 2, 4, 8, max_len):
        if 1 <= c <= max(max_len, 1) and c not in resolved:
            resolved.add(c)
            seen.append(c)
    return seen


def spmm_knob_space(a: BlockCSR, *, n_lanes_max: int = 16,
                    shard_counts: Sequence[int] = (1,),
                    col_shard_counts: Sequence[int] = (1,),
                    fused_layouts: Sequence[str] = ("rmw", "compact"),
                    reorder: bool | str = False,
                    ) -> List[Dict]:
    """Enumerate the discrete SpMM schedule knob space for one pattern.

    Each entry is a config dict with the full knob set —
    ``n_lanes`` (powers of two ≤ ``n_lanes_max``), ``chunk``
    (:func:`_chunk_candidates`; ``None`` = planner default), ``row_atomic``
    (atomic configs carry ``chunk=None`` — the conflicting combination
    raises in :func:`plan_spmm`), ``fused`` layout preference, and the
    device axes ``n_shards`` / ``n_col_shards`` / ``device_chunk``
    (searched only for entries of ``shard_counts`` > 1; ``device_chunk``
    offers ``None`` = whole rows plus one half-balanced-shard bound when
    a row overflows the balanced shard; ``col_shard_counts`` varies the
    dense-operand column axis and, being schedule-neutral — predicted
    cycles are per-output-column-tile, so the makespan does not depend on
    the column split — exists so a caller can *pin* the memory layout,
    with single-device entries always at ``n_col_shards=1``).
    ``reorder`` adds the similarity row-reordering pass
    (``kernels.reorder``) as a knob: ``False`` (default) never reorders,
    ``True`` always does, ``"auto"`` enumerates both so the search
    prices them against each other.  Reordering permutes block-rows
    before planning and is undone on the output, so it composes with
    every single-device knob; it is **not** enumerated on partitioned
    entries (``n_shards > 1``) — the permutation would have to thread
    through the row-shard split maps, a follow-on recorded in
    ROADMAP.md.

    Deterministic order — the autotuner's tie-break and seeding
    contract depends on it.  Not enumerated (documented in
    kernels/README.md): the block shape (a *container* property — changing
    it reshapes the operand), ``bn`` (an execution tile, not a schedule
    property), and the SpGEMM balance axis (different planner).
    """
    if reorder not in (False, True, "auto"):
        raise ValueError(f"reorder must be False | True | 'auto', "
                         f"got {reorder!r}")
    reorder_opts = {False: (False,), True: (True,),
                    "auto": (False, True)}[reorder]
    rptr = block_pattern_meta(a)[2]
    row_lens = np.diff(rptr)
    nnzb = int(rptr[-1])
    lanes_all: List[int] = []
    l = 1
    while l <= max(n_lanes_max, 1):
        lanes_all.append(l)
        l *= 2
    cfgs: List[Dict] = []
    for n_shards in shard_counts:
        if n_shards < 1:
            raise ValueError(f"shard count {n_shards} < 1")
        dev_chunks: List[Optional[int]] = [None]
        if n_shards > 1:
            balanced = max(1, -(-nnzb // n_shards))
            half = max(1, balanced // 2)
            if int(row_lens.max(initial=0)) > balanced:
                dev_chunks.append(half)
        # partitioned execution is compact-layout by definition (shard
        # outputs are disjoint per-device tiles), so the fused knob only
        # varies on the single-device axis; likewise the column axis only
        # exists on the partitioned schedule
        layouts = fused_layouts if n_shards == 1 else ("compact",)
        col_counts = [1] if n_shards == 1 else list(col_shard_counts)
        # the reorder pass is a single-device knob (see docstring)
        ro_opts = reorder_opts if n_shards == 1 else (False,)
        for n_col_shards in col_counts:
            if n_col_shards < 1:
                raise ValueError(f"col shard count {n_col_shards} < 1")
            for ro in ro_opts:
                for device_chunk in dev_chunks:
                    for n_lanes in lanes_all:
                        for fused in layouts:
                            cfgs.append(dict(n_lanes=n_lanes, chunk=None,
                                             row_atomic=True, fused=fused,
                                             n_shards=n_shards,
                                             n_col_shards=n_col_shards,
                                             device_chunk=device_chunk,
                                             reorder=ro))
                            for chunk in _chunk_candidates(row_lens,
                                                           n_lanes):
                                cfgs.append(dict(
                                    n_lanes=n_lanes, chunk=chunk,
                                    row_atomic=False, fused=fused,
                                    n_shards=n_shards,
                                    n_col_shards=n_col_shards,
                                    device_chunk=device_chunk,
                                    reorder=ro))
    return cfgs


# --------------------------------------------------------------------------
# SpMM training plan: forward + transpose-side schedules for the VJP
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SpmmTrainPlan:
    """Forward plan plus everything the ``maple_spmm`` VJP needs, cached.

    The backward of ``C = A @ B`` stays inside the row-wise-product
    machinery: ``dB = A^T @ dC`` is the same planned kernel run on the
    **transposed block pattern**, and ``dA`` is the pattern-sampled
    product ``(dC @ B^T)|_{pattern(A)}`` (the block SDDMM in
    ``kernels.maple_sddmm``).  Both schedules are pattern-only, so —
    exactly like the forward plan — they are built **once per weight** on
    the host and closed over by jitted train steps; under trace only the
    payload gathers run.

    * ``fwd`` / ``bwd`` — lane schedules for A and A^T (same knobs);
    * ``t_perm`` — gather taking ``a.blocks`` slots to A^T live-slot
      order (the payload side of ``core.csr.bsr_transpose``, applied to
      the traced blocks at backward time);
    * ``t_block_row`` / ``t_block_col`` / ``t_row_ptr`` — A^T metadata at
      the source capacity, pad slots per the container contract;
    * ``block_row`` / ``block_col`` — host copies of A's metadata that
      drive the SDDMM grid (the container's own copies may be tracers
      inside a train step, where params — metadata included — are traced);
    * ``predicted_cycles`` — fwd + A^T passes priced with the same
      ``core.maple`` model (the SDDMM pass visits exactly the forward's
      block set — one block-MAC per live block per output tile — so its
      event count is the forward entry restated; it is not double-counted
      here).
    """

    fwd: SpmmPlan
    bwd: SpmmPlan
    t_perm: np.ndarray        # (nnzb,) int32 — A^T live slot -> A slot
    t_block_row: np.ndarray   # (n_blocks_max,) int32
    t_block_col: np.ndarray   # (n_blocks_max,) int32, -1 pads
    t_row_ptr: np.ndarray     # (n_block_cols + 1,) int32
    block_row: np.ndarray     # (n_blocks_max,) int32 — host copy of A meta
    block_col: np.ndarray     # (n_blocks_max,) int32, -1 pads
    shape: Tuple[int, int]
    block_shape: Tuple[int, int]
    n_blocks_max: int

    @property
    def n_block_rows(self) -> int:
        return self.fwd.n_block_rows

    def predicted_cycles(self) -> Dict[str, float]:
        """Fwd+bwd cycle predictions (same keys as ``ExecutionPlan``),
        plus the per-pass breakdown (``fwd_plan`` / ``at_plan``)."""
        f = self.fwd.predicted_cycles()
        b = self.bwd.predicted_cycles()
        out = {k: f[k] + b[k] for k in f}
        out["fwd_plan"] = f["plan"]
        out["at_plan"] = b["plan"]
        return out


def plan_spmm_vjp(a: BlockCSR, *, n_lanes: int = 8,
                  chunk: Optional[int] = None,
                  row_atomic: bool = False,
                  fused: str = "auto",
                  n_shards: Optional[int] = None,
                  n_col_shards: Optional[int] = None,
                  fwd: Optional[SpmmPlan] = None) -> SpmmTrainPlan:
    """Build the forward plan and cache the transpose-side plan with it.

    Host-side over metadata like :func:`plan_spmm`; raises loudly on
    traced metadata.  ``ops.maple_spmm`` accepts the result wherever a
    plain ``SpmmPlan`` fits — passing it is what arms the kernel-path VJP
    (without it, eager calls re-plan per call and traced naive calls fall
    back to a jnp backward).  Pass an already-built ``fwd`` plan for the
    same operand to skip re-planning the forward (``n_lanes``/``chunk``/
    ``row_atomic`` then only shape the transpose-side schedule).

    ``n_shards`` lifts both sides to the device array: the forward and
    the ``dB = A^T @ dC`` backward become mesh-partitioned plans, the
    backward **re-partitioned on the transposed block pattern**
    (``kernels.partition.plan_partitioned_spmm_vjp`` — A^T's block-rows
    are A's block-columns, so the forward's row split does not carry
    over).  ``n_col_shards`` adds the dense-operand column axis to both
    sides and lifts the dA SDDMM onto the same 2-D mesh
    (``ops._partitioned_sddmm_f32``).  ``None``/``1`` keeps the
    single-device schedules (``n_col_shards>1`` requires a sharded plan).
    """
    if (n_shards is not None and n_shards > 1) or \
            (n_col_shards is not None and n_col_shards > 1):
        # lazy import: partition builds on this module
        from repro.kernels.partition import (PartitionedSpmmPlan,
                                             plan_partitioned_spmm_vjp)
        if fwd is not None and not isinstance(fwd, PartitionedSpmmPlan):
            # never silently drop the caller's plan (and its knobs)
            raise ValueError(
                "n_shards>1 needs a partitioned fwd plan; the one passed "
                "is single-device — build it with plan_partitioned_spmm, "
                "or drop fwd to re-plan here")
        return plan_partitioned_spmm_vjp(
            a, n_shards=n_shards if n_shards is not None else 1,
            n_col_shards=n_col_shards if n_col_shards is not None else 1,
            n_lanes=n_lanes, chunk=chunk, row_atomic=row_atomic, fwd=fwd)
    if fwd is None:
        fwd = plan_spmm(a, n_lanes=n_lanes, chunk=chunk,
                        row_atomic=row_atomic, fused=fused)
    return transpose_train_plan(
        a, fwd, lambda at: plan_spmm(at, n_lanes=n_lanes, chunk=chunk,
                                     row_atomic=row_atomic, fused=fused))


def transpose_train_plan(a: BlockCSR, fwd, plan_at) -> SpmmTrainPlan:
    """Shared tail of the train-plan builders (single-device *and*
    partitioned — ``kernels.partition`` calls this too): A^T metadata at
    the source capacity, the metadata-only A^T stand-in handed to the
    ``plan_at`` planner, and the assembled :class:`SpmmTrainPlan`.  The
    ONE place the transpose-side conventions are encoded, so the two
    builders cannot drift.

    The pad convention for the transposed metadata itself lives in
    ``core.csr.bsr_transpose_meta(pad_to=...)`` — shared with
    ``bsr_transpose``; the stand-in's ``(cap, 1, 1)`` zero payload keeps
    plan construction O(metadata).
    """
    cap = a.n_blocks_max
    bm, bk = a.block_shape
    perm, t_block_row, t_block_col, t_rptr, nnzb = bsr_transpose_meta(
        a, pad_to=cap)
    at_pattern = BlockCSR(
        blocks=np.zeros((cap, 1, 1), np.float32),
        block_col=t_block_col, block_row=t_block_row,
        row_ptr=t_rptr, shape=(a.shape[1], a.shape[0]),
        block_shape=(bk, bm))
    return SpmmTrainPlan(
        fwd=fwd, bwd=plan_at(at_pattern), t_perm=perm[:nnzb],
        t_block_row=t_block_row, t_block_col=t_block_col, t_row_ptr=t_rptr,
        block_row=np.asarray(a.block_row).astype(np.int32).copy(),
        block_col=np.asarray(a.block_col).astype(np.int32).copy(),
        shape=a.shape, block_shape=a.block_shape, n_blocks_max=cap,
    )


# --------------------------------------------------------------------------
# SpGEMM: the symbolic phase + work-balanced lane schedule
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SpgemmPlan(ExecutionPlan):
    """Element-granular plan for ``maple_spgemm`` — symbolic phase output.

    On top of the lane schedule (one step = one live A non-zero consuming
    the whole B row its ``col_id`` selects; ``step_col`` is that B row id),
    the plan carries everything the numeric phase needs that can be derived
    from *metadata alone*:

    * ``out_row_ptr`` / ``out_cols`` — the **exact** output pattern of C,
      sorted by column within each row (padded-CSR contract: the wrapper
      pads ``col_id`` with ``-1`` up to capacity); ``row_upper`` is the
      O(nnz_a) a-priori bound (``core.csr.spgemm_row_upper_bounds``) the
      phase starts from — it gates the O(P) expansion and is kept for
      capacity planning;
    * ``lc`` — the bounded per-row PSB width = the longest output row;
    * ``scatter_pos[i·la + t, u]`` — position within output row i of the
      partial product A[i, t-th nnz] · B[k', u-th nnz], ``-1`` where dead:
      the paper's Eq. (8) scatter by j' made explicit, precomputed so the
      kernel's column-indexed PSB needs no runtime search;
    * ``a_gather``/``a_live``, ``b_gather``/``b_live`` — ELL slot maps
      (``core.formats.ell_slots``) so the numeric phase regularizes *values*
      with a traced gather, never touching host copies;
    * ``lane_work`` — realized partial products per lane (the balancing
      target).

    Pad steps point ``step_row`` at the **sacrificial row** ``n_rows`` (the
    numeric kernel allocates one extra output row and slices it off), so an
    idle lane can never clobber a real row.

    Rows are atomic here (``chunk = 0``): each output row owns one
    column-indexed PSB, so the balancing axis is which lane gets which
    rows — weighted by work, not by nnz(A).
    """

    out_row_ptr: np.ndarray   # (n_rows + 1,) int64 — exact C pattern
    out_cols: np.ndarray      # (nnz_c,) int32, column-sorted within rows
    row_upper: np.ndarray     # (n_rows,) int64 — a-priori nnz(C[i,:]) bound
    lc: int                   # PSB width = longest output row (>= 1)
    scatter_pos: np.ndarray   # (n_rows * la, lb) int32, -1 dead
    a_gather: np.ndarray      # (n_rows * la,) int32 — slot -> A nnz index
    a_live: np.ndarray        # (n_rows * la,) bool
    b_gather: np.ndarray      # (n_rows_b, lb) int32
    b_live: np.ndarray        # (n_rows_b, lb) bool
    la: int                   # ELL width of A
    lb: int                   # ELL width of B (panel width)
    lane_work: np.ndarray     # (n_lanes,) int64 — partial products per lane
    shape_a: Tuple[int, int]
    shape_b: Tuple[int, int]

    @property
    def nnz_c(self) -> int:
        return int(self.out_row_ptr[-1])

    def _realized_makespan(self) -> float:
        # Work-unit makespan: the busiest lane's partial products — each
        # scheduled slot costs its B-row length, not one flat step.
        return float(self.lane_work.max(initial=0))


def plan_spgemm(a: CSR, b: CSR, *, n_lanes: int = 8,
                balance: str = "work") -> SpgemmPlan:
    """Symbolic SpGEMM phase: exact C pattern + work-balanced lane schedule.

    ``balance`` selects the row weight for LPT lane packing:

    * ``"work"``   — Σ nnz(B[k',:]) per A row (the partial-product count
      ``analyze_spgemm`` reports; the balanced default),
    * ``"fibers"`` — nnz(A[i,:]) (the MatRaptor-style proxy that ignores B;
      exposed so benchmarks can price why work-weighting matters),
    * ``"none"``   — single lane, rows in order (the naive walk).

    Host-side over metadata; values are never read, so the plan can be
    built once per sparsity pattern and closed over by a jitted call.
    Blocked :class:`~repro.core.formats.SparseFormat` operands lower to
    the element pattern they store via ``core.formats.as_element_csr``.
    """
    if not isinstance(a, CSR):
        a = as_element_csr(a)
    if not isinstance(b, CSR):
        b = as_element_csr(b)
    if a.shape[1] != b.shape[0]:
        raise ValueError(f"shape mismatch {a.shape} @ {b.shape}")
    if n_lanes < 1:
        raise ValueError(f"n_lanes={n_lanes} < 1")
    if balance not in ("work", "fibers", "none"):
        raise ValueError(f"unknown balance {balance!r}")
    m, n = a.shape[0], b.shape[1]
    a_rptr = np.asarray(a.row_ptr).astype(np.int64)
    nnz_a = int(a_rptr[-1])
    a_cols = np.asarray(a.col_id).astype(np.int32)
    a_len = np.diff(a_rptr)
    b_len = np.diff(np.asarray(b.row_ptr).astype(np.int64))
    # the plan computes the exact pattern itself below — don't pay for the
    # O(P log P) expansion twice; stats.nnz_c is patched to exact after.
    stats = analyze_spgemm(a, b, exact_output=False)

    # -- symbolic: ELL slot maps, exact output pattern, scatter positions
    la = max(int(a_len.max(initial=0)), 1)
    lb = max(int(b_len.max(initial=0)), 1)
    a_gather, a_live = ell_slots(a.row_ptr, la)         # (m, la)
    b_gather, b_live = ell_slots(b.row_ptr, lb)         # (k, lb)

    # O(nnz_a) pre-bound: gates the O(P) expansion and caps row capacity
    row_upper = spgemm_row_upper_bounds(a, b)
    scatter = np.full((m * la, lb), -1, np.int32)
    out_row_ptr = np.zeros(m + 1, np.int64)
    if row_upper.sum() > 0:
        a_slot, out_i, out_j, b_off = expand_partials(a, b)
        keys = out_i * np.int64(n) + out_j
        uniq, gpos = np.unique(keys, return_inverse=True)
        out_cols = (uniq % n).astype(np.int32)
        np.cumsum(np.bincount((uniq // n).astype(np.int64), minlength=m),
                  out=out_row_ptr[1:])
        a_off = a_slot - a_rptr[out_i]                  # ELL lane of A slot
        scatter[out_i * la + a_off, b_off] = \
            (gpos - out_row_ptr[out_i]).astype(np.int32)
    else:
        out_cols = np.zeros(0, np.int32)
    stats = dataclasses.replace(stats, nnz_c=int(out_cols.size))
    lc = max(int(np.diff(out_row_ptr).max(initial=0)), 1)

    # -- lane schedule: whole rows, LPT by the chosen weight
    rows = [i for i in range(m) if a_len[i] > 0]
    if balance == "none":
        n_lanes = 1
        lanes: List[List[int]] = [rows]
    else:
        weight = stats.row_partials if balance == "work" else a_len
        weighted = sorted(((int(weight[i]), i) for i in rows),
                          key=lambda t: (-t[0], t[1]))
        lanes, _ = _lpt_pack(weighted, n_lanes)
        for lane in lanes:
            lane.sort()

    steps = max(1, max((sum(int(a_len[i]) for i in lane) for lane in lanes),
                       default=0))
    order = np.zeros((n_lanes, steps), np.int32)
    step_row = np.full((n_lanes, steps), m, np.int32)   # pads -> row m
    step_col = np.full((n_lanes, steps), -1, np.int32)
    written = np.zeros((n_lanes, m), bool)
    lane_work = np.zeros(n_lanes, np.int64)
    n_real = 0
    for l, lane in enumerate(lanes):
        t = 0
        for i in lane:
            ln = int(a_len[i])
            lo = int(a_rptr[i])
            order[l, t:t + ln] = i * la + np.arange(ln, dtype=np.int32)
            step_row[l, t:t + ln] = i
            step_col[l, t:t + ln] = a_cols[lo:lo + ln]
            written[l, i] = True
            lane_work[l] += int(stats.row_partials[i])
            t += ln
        n_real += t

    return SpgemmPlan(
        order=order, step_row=step_row, step_col=step_col, written=written,
        chunk=0, n_rows=m, n_real_steps=n_real, stats=stats,
        out_row_ptr=out_row_ptr, out_cols=out_cols, row_upper=row_upper,
        lc=lc,
        scatter_pos=scatter, a_gather=a_gather.reshape(-1),
        a_live=a_live.reshape(-1), b_gather=b_gather, b_live=b_live,
        la=la, lb=lb, lane_work=lane_work, shape_a=a.shape, shape_b=b.shape)
