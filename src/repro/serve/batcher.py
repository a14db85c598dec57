"""Continuous-batching serving engine over the paged decode path.

One :class:`ContinuousBatcher` owns ``max_slots`` batch slots, a paged KV
state (``models.lm.init_paged_state``), a :class:`~repro.serve.queue.
RequestQueue`, and (optionally) a plan-cached
:class:`~repro.serve.engine.SparseLogitHead`.  Each scheduling round
(:meth:`step`):

1. **Expire/shed** — in-flight slots past their ``deadline`` retire with
   ``status="deadline_exceeded"``; queued requests past theirs are shed
   before admission (an expired head must never block live work).
2. **Admit** — while a ready request, a free slot, and enough KV pages
   exist: run a batch-1 prefill (jit-cached per padded prompt length),
   scatter its caches into the slot's pages, sample the first token.
   Malformed prompts (token ids outside ``[0, vocab_size)``) are
   quarantined at the door (``status="rejected"``) — a poison request
   never reaches the fused step.  When pages run short, the engine
   **preempts** the lowest-progress slot instead of head-of-line
   blocking: the victim's pages are freed and it re-enters the queue
   carrying its generated tokens, key chain, and timestamps, so resume
   is a re-prefill and its greedy output is bit-identical to an
   uninterrupted run.
3. **Decode** — one fused ``decode_step_paged`` over all ``max_slots``
   rows (free slots ride along writing into the dead page, so the jitted
   step compiles exactly once per config); per-slot positions let slots
   sit at different depths.  The call sits inside a **bounded-retry
   wrapper**: host state (pages, block tables, token buffers, the state
   pytree) is only committed on success, so a transient failure replays
   the step exactly; after ``max_retries`` are exhausted, the round
   degrades gracefully — each live slot finishes on the static
   per-request path (``engine.complete_static``).
4. **Sample/retire** — per-slot sampling (each request carries its own
   fold_in-derived key, so its draws are independent of batch
   composition), EOS/length retirement, a **non-finite-logits guard**
   (a slot producing NaN/inf logits retires with ``status="error"``
   while every co-resident slot is untouched), page freeing, and — for
   local-window/recurrent configs — reclamation of pages that fell
   behind the attention horizon.

Every round leaves spans in the profiler's trace (``serve.round`` and,
nested in it, ``serve.admit``/``serve.prefill``, ``serve.prepare``,
``serve.decode``, ``serve.head``, ``serve.fetch``, ``serve.sample``;
``serve/README.md`` lists them).  They cost under a microsecond each
while no trace is being taken.

Failure injection is deterministic: pass a
:class:`~repro.serve.faults.FaultSchedule` and every fault lands on a
fixed scheduling round — the chaos benchmark's metrics are exact-match
gated in CI.  Greedy outputs are bit-identical to the static
``generate`` path when the geometries match (see ``serve/README.md``);
MoE configs are served but excluded from the bit-identity guarantee
(expert capacity couples rows of a batch).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.models import lm
from repro.models.layers import token_tiles
from repro.serve.engine import (SamplingConfig, SparseLogitHead,
                                complete_static, jitted_decode_step,
                                jitted_prefill, named_jit, sample_token)
from repro.serve.faults import FaultSchedule, TransientStepError
from repro.serve.paged_cache import (DEAD_PAGE, PageAllocator,
                                     assert_paged_memory_bound, make_table,
                                     pages_for, reclaimable_pages,
                                     scatter_prefill_state)
from repro.serve.queue import (STATUS_DEADLINE, STATUS_ERROR,
                               STATUS_REJECTED, Completion, Request,
                               RequestQueue)


@dataclasses.dataclass(frozen=True)
class BatcherConfig:
    max_slots: int = 8           # fused-step batch width (compiled once)
    page_size: int = 8           # tokens per KV page
    n_pages: int = 64            # physical pool size (incl. dead page 0)
    max_seq: int = 128           # per-request prompt + new-token cap
    max_retries: int = 2         # fused-step replays before degrading
    preempt: bool = True         # evict lowest-progress slot when pages
    #                              run short (False = head-of-line block)

    @property
    def max_pages(self) -> int:  # block-table width per slot
        return -(-self.max_seq // self.page_size)


@dataclasses.dataclass
class _Slot:
    req: Request
    pages: List[int]
    pos: int                     # next write position (tokens so far)
    pending: int                 # last sampled token, not yet fed
    out: List[int]
    key: jax.Array
    t_admit: float
    t_first: float
    steps: int = 0


class ContinuousBatcher:
    """The serving engine.  See module docstring for the step anatomy."""

    def __init__(self, params, cfg: ModelConfig, queue: RequestQueue,
                 bcfg: BatcherConfig = BatcherConfig(),
                 sampling: SamplingConfig = SamplingConfig(),
                 head: Optional[SparseLogitHead] = None,
                 key: Optional[jax.Array] = None,
                 faults: Optional[FaultSchedule] = None):
        if queue.max_seq is None:
            queue.max_seq = bcfg.max_seq
        self.params = params
        self.cfg = cfg
        self.queue = queue
        self.bcfg = bcfg
        self.sampling = sampling
        self.head = head
        self.key = key if key is not None else jax.random.PRNGKey(0)
        self.faults = faults

        self.needs_kv = lm.needs_kv_pages(cfg)
        self.horizon = lm.history_horizon(cfg)
        self.allocator = PageAllocator(bcfg.n_pages, bcfg.page_size)
        # the pool holds K/V in the parameters' dtype — the dtype prefill
        # emits them in — so a bf16 model keeps a bf16 pool
        self.state = lm.init_paged_state(
            cfg, bcfg.max_slots, bcfg.n_pages, bcfg.page_size,
            bcfg.max_pages, dtype=params["embed_tokens"].dtype)
        self.slots: List[Optional[_Slot]] = [None] * bcfg.max_slots
        self._step_fn = jitted_decode_step(cfg, paged=True,
                                           return_hidden=head is not None)
        if head is not None:
            # the prebuilt plan is frozen into the callable (nothing a later
            # admission could replan); the weight is an argument, because a
            # closed-over array is baked into each executable as a constant
            # — another copy of the head in device memory per compile.
            # Two programs over one callable, so a trace tells the head
            # at decode from the head on a prefill's output.
            def score(w, h):
                return SparseLogitHead(w, head.plan)(h)
            self._head_decode = named_jit(score, "head_decode")
            self._head_prefill = named_jit(score, "head_prefill")
        self.completions: List[Completion] = []
        self.steps = 0
        self.rounds = 0              # step() calls — the fault-clock key
        self.occupancy_sum = 0       # Σ live slots per fused step
        self.pages_in_use_sum = 0    # Σ allocator.in_use per fused step
        self.admitted = 0            # admissions incl. preemption resumes
        self.pages_reclaimed = 0     # freed behind the window horizon
        # --- failure-semantics counters (all deterministic) ---
        self.preemptions = 0         # slots evicted for page pressure
        self.sheds = 0               # queued requests shed past deadline
        self.expired = 0             # in-flight deadline retirements
        self.quarantined = 0         # malformed prompts rejected at door
        self.errors = 0              # non-finite-logits retirements
        self.retries = 0             # fused-step replays that happened
        self.fallbacks = 0           # rounds degraded to the static path
        self._alloc_denied = False   # fault-injected exhaustion, per round

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------

    def free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def _prompt_pages(self, req: Request) -> int:
        """Pages a (re-)prefill must *allocate*.  Fresh requests cover
        the prompt; resumed requests cover prompt + generated minus the
        leading pages already behind the attention horizon (those map to
        the dead page — their KV can never be read again)."""
        if not self.needs_kv:
            return 0
        n_logical = pages_for(req.total_len, self.bcfg.page_size)
        if not req.generated:
            return n_logical
        dead = min(reclaimable_pages(req.total_len, self.horizon,
                                     self.bcfg.page_size), n_logical)
        return n_logical - dead

    def _validate_tokens(self, req: Request) -> bool:
        toks = req.tokens
        return bool(((toks >= 0) & (toks < self.cfg.vocab_size)).all())

    def try_admit(self, now: float) -> int:
        """Admit every ready request a slot + pages can take.  Returns
        how many were admitted this round.  Sheds expired queue entries
        first, quarantines malformed prompts, and preempts for pages."""
        for req in self.queue.shed_expired(now):
            self.sheds += 1
            self._complete_unstarted(req, STATUS_DEADLINE, now)
        n = 0
        while True:
            req = self.queue.peek_ready(now)
            if req is None:
                break
            if not self._validate_tokens(req):
                # poison-request quarantine: out-of-range token ids never
                # reach prefill (where they would index the embedding
                # table out of bounds — silent garbage under XLA)
                self.queue.pop()
                self.quarantined += 1
                self._complete_unstarted(req, STATUS_REJECTED, now)
                continue
            slot_id = self.free_slot()
            if slot_id is None:
                break
            n_pp = self._prompt_pages(req)
            # reserve one decode page beyond the prompt so the first
            # fused step can never die on an empty pool mid-flight
            if self.needs_kv and not (not self._alloc_denied
                                      and self.allocator.can_alloc(n_pp + 1)):
                if self._alloc_denied:
                    break        # freeing pages cannot satisfy a denial
                if not self._try_preempt(n_pp + 1, now):
                    break        # nothing evictable would make it fit
                slot_id = self.free_slot()
            self.queue.pop()
            self._admit(req, slot_id, n_pp, now)
            n += 1
        return n

    def _try_preempt(self, need: int, now: float) -> bool:
        """Evict the lowest-progress slot to free pages for an admission
        that does not fit.  Progress is tokens generated (ties: the
        youngest request — largest rid — yields first).  Only preempts
        when the victim's pages actually make the admission fit; returns
        whether a preemption happened."""
        if not self.bcfg.preempt:
            return False
        victims = [(len(s.out), -s.req.rid, i)
                   for i, s in enumerate(self.slots) if s is not None]
        if not victims:
            return False
        _, _, vid = min(victims)
        victim = self.slots[vid]
        freeable = sum(1 for p in victim.pages if p != DEAD_PAGE)
        if self.allocator.free_pages() + freeable < need:
            return False
        self._preempt(vid, now)
        return True

    def _preempt(self, slot_id: int, now: float) -> None:
        """Evict a slot: free its pages, push its request back into the
        queue carrying everything resume needs (generated tokens, key
        chain, original timestamps).  Resume is a re-prefill over
        prompt + generated — greedy output is bit-identical to an
        uninterrupted run because prefill and decode agree bitwise."""
        slot = self.slots[slot_id]
        req = slot.req
        live = [p for p in slot.pages if p != DEAD_PAGE]
        if live:
            self.allocator.free(live)
        req.generated = list(slot.out)
        req.resume_key = slot.key
        req.preemptions += 1
        req.t_admit0 = slot.t_admit
        req.t_first0 = slot.t_first
        req.steps0 = slot.steps
        self.slots[slot_id] = None
        self.queue.requeue(req)
        self.preemptions += 1

    def _admit(self, req: Request, slot_id: int, n_pp: int,
               now: float) -> None:
        resumed = bool(req.generated)
        with TraceAnnotation("serve.admit", rid=req.rid,
                             prompt_len=req.prompt_len, resumed=resumed):
            ctx = (np.concatenate([req.tokens,
                                   np.asarray(req.generated, np.int32)])
                   if resumed else req.tokens)
            total = int(ctx.size)
            pages = self.allocator.alloc(n_pp) if n_pp else []
            if resumed and self.needs_kv:
                # leading logical pages already behind the horizon were
                # not allocated (_prompt_pages): map them to the dead
                # page — their prefill KV writes land there, never read
                dead = pages_for(total, self.bcfg.page_size) - n_pp
                pages = [DEAD_PAGE] * dead + pages
            padded_len = max(len(pages) * self.bcfg.page_size, total)
            prefill = jitted_prefill(self.cfg, padded_len,
                                     return_hidden=self.head is not None)
            with TraceAnnotation("serve.prefill", rid=req.rid,
                                 padded_len=padded_len):
                out, pstate = prefill(self.params,
                                      batch={"tokens": jnp.asarray(
                                          ctx, jnp.int32)[None]})
            logits = (self._head_fn(out, prefill=True)
                      if self.head is not None else out)

            self.state = scatter_prefill_state(
                self.state, pstate, slot_id, pages, self.bcfg.page_size)

            key = (req.resume_key if req.resume_key is not None
                   else jax.random.fold_in(self.key, req.rid))
            slot = _Slot(req=req, pages=pages, pos=total,
                         pending=0, out=list(req.generated), key=key,
                         t_admit=(req.t_admit0 if resumed else now),
                         t_first=(req.t_first0 if resumed else now),
                         steps=req.steps0)
            with TraceAnnotation("serve.fetch"):
                row = np.asarray(logits[:, -1])
            reason = self._sample(slot, row, now)
            self.slots[slot_id] = slot
            self.admitted += 1
            if reason is not None:   # eos/length/error on the first token
                if reason == STATUS_ERROR:
                    self.errors += 1
                self._retire(slot_id, reason, now)

    # ------------------------------------------------------------------
    # sampling / retirement
    # ------------------------------------------------------------------

    def _sample(self, slot: _Slot, logits_row, now: float):
        """Sample one token for a slot; returns a finish reason or None.

        ``logits_row``: (1, V_padded) on the host.  Every slot draws from
        its own fold_in key chain, so a request's sampled tokens do not
        depend on which other requests share the batch.  A non-finite
        logits row (over the REAL vocabulary — padded slots carry garbage
        by design) is the quarantine signal: no token is sampled and the
        slot retires with ``status="error"``.
        """
        with TraceAnnotation("serve.sample", rid=slot.req.rid):
            row = np.asarray(logits_row)
            with TraceAnnotation("serve.sample.check"):
                finite = np.isfinite(row[0, :self.cfg.vocab_size]).all()
            if not finite:
                return STATUS_ERROR
            with TraceAnnotation("serve.sample.draw"):
                slot.key, sub = jax.random.split(slot.key)
                tok = int(sample_token(jnp.asarray(row), sub, self.sampling,
                                       self.cfg.vocab_size)[0])
        slot.out.append(tok)
        slot.pending = tok
        req = slot.req
        if req.eos_id >= 0 and tok == req.eos_id:
            return "eos"
        if len(slot.out) >= req.max_new_tokens:
            return "length"
        return None

    def _retire(self, slot_id: int, reason: str, now: float) -> None:
        slot = self.slots[slot_id]
        self.completions.append(Completion(
            rid=slot.req.rid, prompt_len=slot.req.prompt_len,
            tokens=list(slot.out), finished_by=reason,
            arrival=slot.req.arrival, t_admit=slot.t_admit,
            t_first_token=slot.t_first, t_done=now, steps=slot.steps,
            status=reason, preemptions=slot.req.preemptions))
        live = [p for p in slot.pages if p != DEAD_PAGE]
        if live:
            self.allocator.free(live)
        self.slots[slot_id] = None

    def _complete_unstarted(self, req: Request, status: str,
                            now: float) -> None:
        """Completion for a request that never (re)gained a slot: shed
        past deadline or quarantined at the door.  A preempted request
        shed while waiting keeps the tokens it had already generated."""
        t_admit = req.t_admit0 if req.t_admit0 is not None else now
        t_first = req.t_first0 if req.t_first0 is not None else now
        self.completions.append(Completion(
            rid=req.rid, prompt_len=req.prompt_len,
            tokens=list(req.generated), finished_by=status,
            arrival=req.arrival, t_admit=t_admit, t_first_token=t_first,
            t_done=now, steps=req.steps0, status=status,
            preemptions=req.preemptions))

    def _reclaim_window_pages(self, slot: _Slot) -> None:
        """Free pages every layer's read horizon has moved past (local
        window / pure-recurrent configs); their table entries fall back
        to the dead page.  Unbounded-horizon configs never reclaim."""
        r = reclaimable_pages(slot.pos, self.horizon, self.bcfg.page_size)
        for j in range(min(r, len(slot.pages))):
            if slot.pages[j] != DEAD_PAGE:
                self.allocator.free([slot.pages[j]])
                slot.pages[j] = DEAD_PAGE
                self.pages_reclaimed += 1

    # ------------------------------------------------------------------
    # the fused step
    # ------------------------------------------------------------------

    def live(self) -> int:
        return sum(s is not None for s in self.slots)

    def _ensure_decode_page(self, slot_id: int, now: float) -> None:
        """The token written this step lands at logical page pos // P —
        allocate it if the slot hasn't grown there yet.  When the pool is
        dry, lower-progress *other* slots are preempted to free pages
        (same victim policy as admission); with no evictable victim the
        allocator raises — a pool genuinely too small for one sequence is
        a capacity bug, not a schedulable condition."""
        slot = self.slots[slot_id]
        if not self.needs_kv:
            return
        need = slot.pos // self.bcfg.page_size + 1
        while len(slot.pages) < need:
            if not self.allocator.can_alloc(1) and self.bcfg.preempt:
                others = [(len(s.out), -s.req.rid, i)
                          for i, s in enumerate(self.slots)
                          if s is not None and i != slot_id
                          and any(p != DEAD_PAGE for p in s.pages)]
                if others:
                    self._preempt(min(others)[2], now)
            slot.pages.extend(self.allocator.alloc(1))

    def _retire_expired(self, now: float) -> None:
        for i, slot in enumerate(self.slots):
            if slot is not None and slot.req.expired(now):
                self.expired += 1
                self._retire(i, STATUS_DEADLINE, now)

    def _fallback_drain(self, now: float) -> None:
        """Graceful degradation after the fused step's retry budget is
        gone: every live slot finishes its remaining tokens on the
        static per-request path (``engine.complete_static`` — prefill
        over prompt + generated, per-token decode, same head, same key
        chain).  Pages are freed as slots retire; the engine keeps
        admitting and decoding normally from the next round."""
        self.fallbacks += 1
        for i, slot in enumerate(self.slots):
            if slot is None:
                continue
            req = slot.req
            ctx = (np.concatenate([req.tokens,
                                   np.asarray(slot.out, np.int32)])
                   if slot.out else req.tokens)
            new_toks, reason, slot.key = complete_static(
                self.params, self.cfg, ctx,
                req.max_new_tokens - len(slot.out),
                sampling=self.sampling, key=slot.key, eos_id=req.eos_id,
                head=self.head)
            slot.out.extend(new_toks)
            if reason == STATUS_ERROR:
                self.errors += 1
            self._retire(i, reason, now)

    def _head_fn(self, hidden, prefill: bool = False):
        """Logits of ``hidden`` through the sparse head: ``jit_head_decode``
        in the round, ``jit_head_prefill`` on a prefill's output.  The
        span carries the tokens scored and the token tiles the kernel
        runs for them (the round's slots fold into one)."""
        b, s, _ = hidden.shape
        _, tiles = token_tiles(hidden.shape)
        with TraceAnnotation("serve.head", tokens=b * s, tiles=tiles):
            fn = self._head_prefill if prefill else self._head_decode
            return fn(self.head.weight, hidden)

    def step(self, now: float = 0.0) -> List[Completion]:
        """One scheduling round: expire, admit, fused-decode (with
        bounded retry), sample, retire.  Returns the requests that
        completed during this round."""
        with TraceAnnotation("serve.round", round=self.rounds):
            return self._round(now)

    def _round(self, now: float) -> List[Completion]:
        before = len(self.completions)
        rnd = self.rounds
        self.rounds += 1
        self._alloc_denied = (self.faults.alloc_denied(rnd)
                              if self.faults is not None else False)
        self._retire_expired(now)
        self.try_admit(now)
        if self.live() == 0:
            return self.completions[before:]

        with TraceAnnotation("serve.prepare"):
            # grow write pages BEFORE assembling the batch: growth may
            # evict a co-resident slot, and a victim already baked into
            # the batch arrays would decode as a ghost into freed pages
            for i in range(self.bcfg.max_slots):
                if self.slots[i] is not None:
                    self._ensure_decode_page(i, now)
            if self.live() == 0:
                return self.completions[before:]

            tokens = np.zeros((self.bcfg.max_slots, 1), np.int32)
            pos = np.zeros((self.bcfg.max_slots,), np.int32)
            pages: List[List[int]] = [[] for _ in range(self.bcfg.max_slots)]
            for i, slot in enumerate(self.slots):
                if slot is None:
                    continue
                tokens[i, 0] = slot.pending
                pos[i] = slot.pos
                pages[i] = slot.pages
            table = make_table(pages, self.bcfg.max_pages)

            state = dict(self.state)
            state["table"] = jnp.asarray(table)
            state["pos"] = jnp.asarray(pos)

        # bounded retry: every input (params, state dict, host arrays)
        # is immutable until the step succeeds, so a replay is exact.
        # Only the injected TransientStepError is retried — real bugs
        # must not be silently replayed into a different failure mode.
        inject = (self.faults.transient_failures(rnd)
                  if self.faults is not None else 0)
        attempts = 0
        with TraceAnnotation("serve.decode", live=self.live(),
                             pages_in_use=self.allocator.in_use,
                             pages_free=self.allocator.free_pages()):
            while True:
                try:
                    if attempts < inject:
                        raise TransientStepError(
                            f"injected transient failure (round {rnd}, "
                            f"attempt {attempts})")
                    out, new_state = self._step_fn(
                        self.params, state=state, tokens=jnp.asarray(tokens))
                    break
                except TransientStepError:
                    attempts += 1
                    if attempts > self.bcfg.max_retries:
                        break
                    self.retries += 1
        if attempts > self.bcfg.max_retries:
            self._fallback_drain(now)
            return self.completions[before:]

        logits = (self._head_fn(out) if self.head is not None else out)
        self.state = new_state
        self.steps += 1
        self.occupancy_sum += self.live()
        self.pages_in_use_sum += self.allocator.in_use

        with TraceAnnotation("serve.fetch"):
            logits_host = np.asarray(logits[:, -1]).copy()
        psn = (self.faults.poison_slot(rnd)
               if self.faults is not None else None)
        if psn is not None and 0 <= psn < self.bcfg.max_slots \
                and self.slots[psn] is not None:
            logits_host[psn, :] = np.nan
        for i, slot in enumerate(self.slots):
            if slot is None:
                continue
            slot.pos += 1
            slot.steps += 1
            reason = self._sample(slot, logits_host[i][None], now)
            if reason is not None:
                if reason == STATUS_ERROR:
                    self.errors += 1
                self._retire(i, reason, now)
            else:
                self._reclaim_window_pages(slot)
        return self.completions[before:]

    # ------------------------------------------------------------------
    # driving
    # ------------------------------------------------------------------

    def idle(self) -> bool:
        return self.live() == 0 and self.queue.pending() == 0

    def run(self, max_steps: int = 100_000,
            clock=None) -> List[Completion]:
        """Drive until queue + slots drain.  ``clock`` maps the step
        index to 'now' (default: the step index itself — the
        deterministic replay clock)."""
        for t in range(max_steps):
            now = float(clock()) if clock is not None else float(t)
            if self.idle():
                break
            self.step(now)
        else:
            raise RuntimeError(f"engine did not drain in {max_steps} steps")
        return self.completions

    def memory_stats(self) -> Dict[str, Any]:
        stats = assert_paged_memory_bound(
            self.allocator, self.bcfg.max_slots, self.bcfg.max_pages)
        stats["page_size"] = self.bcfg.page_size
        stats["reclaimed"] = self.pages_reclaimed
        stats["pages_in_use_sum"] = self.pages_in_use_sum
        return stats

    def fault_stats(self) -> Dict[str, int]:
        """The deterministic failure-semantics counters, in the order the
        bench records and CI gates them."""
        return {"preemptions": self.preemptions,
                "sheds": self.sheds,
                "expired": self.expired,
                "quarantined": self.quarantined,
                "errors": self.errors,
                "retries": self.retries,
                "fallbacks": self.fallbacks}
