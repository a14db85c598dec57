"""Deterministic fault injection for the serving engine.

The failure-semantics layer (deadlines, preemption, quarantine, retry)
must be testable the same way the scheduler is: as *arithmetic on the
virtual step clock*, reproducible bit-for-bit in CI.  Wall-clock chaos
(kill -9 at a random time) cannot be gated exactly; a
:class:`FaultSchedule` can — it is a pure function from the engine's
scheduling-round index to "what breaks this round", fixed at
construction and hashable into test expectations.

Fault kinds (each keyed by the round counter the engine increments at
the top of every :meth:`~repro.serve.batcher.ContinuousBatcher.step`):

* **transient step failures** — ``transient[round] = k`` makes the first
  ``k`` attempts of that round's fused decode step raise
  :class:`TransientStepError`.  The engine's bounded-retry wrapper
  replays the step from host-tracked state (pages, block table, token
  buffers are only committed on success); ``k`` ≤ ``max_retries`` is
  absorbed invisibly, ``k`` > ``max_retries`` degrades that round to
  the static per-request path.
* **NaN-logit poisoning** — ``poison[round] = slot`` makes the
  round's sampler count that slot's logits row as non-finite (the row
  index is an argument of the same compiled program), simulating a
  device-side numeric fault confined to one sequence.  The engine's
  non-finite guard retires the slot with ``status="error"``; every
  co-resident slot must be unaffected (the bit-identity pin).
* **allocator denial** — rounds in ``deny_alloc`` refuse *admission*
  allocations (the pool claims exhaustion).  Unlike real exhaustion,
  freeing pages cannot satisfy a denial, so the engine blocks admission
  instead of preempting — backpressure that drives deadline sheds.
* **malformed requests** — ``malformed`` holds workload request
  *indices* whose prompts :func:`apply_malformed` corrupts with
  out-of-range token ids; admission must quarantine them
  (``status="rejected"``) without touching co-resident slots.

Schedules are built either explicitly (tests pin exact rounds) or by
:meth:`FaultSchedule.sample` from a seed (the chaos benchmark) — both
are plain data, so two engines fed equal schedules see identical faults.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, FrozenSet, List, Optional, Sequence

import numpy as np


class TransientStepError(RuntimeError):
    """A decode step failed in a way worth retrying (injected).

    The engine's retry wrapper catches exactly this type: real bugs
    (shape errors, OOM, ...) still propagate instead of being silently
    retried into a different failure mode.
    """


@dataclasses.dataclass(frozen=True)
class FaultSchedule:
    """A deterministic map from scheduling round → injected faults.

    All fields are optional; the default schedule injects nothing.
    Equality is field-wise (dataclass), so two schedules built from the
    same seed compare equal — the property the determinism tests gate.
    """

    transient: Dict[int, int] = dataclasses.field(default_factory=dict)
    poison: Dict[int, int] = dataclasses.field(default_factory=dict)
    deny_alloc: FrozenSet[int] = frozenset()
    malformed: FrozenSet[int] = frozenset()
    seed: Optional[int] = None     # provenance only (sample() stamps it)

    def transient_failures(self, rnd: int) -> int:
        """How many consecutive attempts of round ``rnd``'s fused step
        must fail before one succeeds."""
        return int(self.transient.get(rnd, 0))

    def poison_slot(self, rnd: int) -> Optional[int]:
        """Slot whose logits count as non-finite in round ``rnd``'s
        sampler (None = no poisoning this round)."""
        return self.poison.get(rnd)

    def alloc_denied(self, rnd: int) -> bool:
        """Does the allocator refuse admission allocations this round?"""
        return rnd in self.deny_alloc

    def is_empty(self) -> bool:
        return not (self.transient or self.poison or self.deny_alloc
                    or self.malformed)

    @classmethod
    def sample(cls, seed: int, n_rounds: int, *,
               p_transient: float = 0.0, max_burst: int = 1,
               p_poison: float = 0.0, max_slot: int = 0,
               p_deny: float = 0.0,
               n_requests: int = 0, p_malformed: float = 0.0
               ) -> "FaultSchedule":
        """Draw a schedule from a seed — same seed, same schedule.

        ``p_*`` are per-round (per-request for ``p_malformed``)
        probabilities; ``max_burst`` bounds the consecutive-failure
        count of a transient fault; ``max_slot`` is the exclusive upper
        bound of poisoned slot ids (the engine ignores a poison aimed at
        a free slot, so over-range ids are harmless but wasteful).
        """
        rng = np.random.default_rng(seed)
        transient: Dict[int, int] = {}
        poison: Dict[int, int] = {}
        deny: List[int] = []
        # one draw stream, consumed in a fixed field order → determinism
        # does not depend on which probabilities are zero
        for rnd in range(n_rounds):
            if rng.random() < p_transient:
                transient[rnd] = int(rng.integers(1, max_burst + 1))
            if rng.random() < p_poison and max_slot > 0:
                poison[rnd] = int(rng.integers(0, max_slot))
            if rng.random() < p_deny:
                deny.append(rnd)
        malformed = [i for i in range(n_requests)
                     if rng.random() < p_malformed]
        return cls(transient=transient, poison=poison,
                   deny_alloc=frozenset(deny),
                   malformed=frozenset(malformed), seed=seed)


def corrupt_tokens(tokens: np.ndarray, vocab_size: int,
                   rng: np.random.Generator) -> np.ndarray:
    """Return a copy of ``tokens`` with one deterministic out-of-range
    id — the canonical poison prompt (admission must reject it)."""
    out = np.array(tokens, np.int32, copy=True)
    pos = int(rng.integers(0, out.size))
    out[pos] = np.int32(vocab_size + int(rng.integers(1, 7)))
    return out


def apply_malformed(reqs: Sequence, schedule: FaultSchedule,
                    vocab_size: int, seed: int = 0) -> int:
    """Corrupt the prompts of ``reqs`` at ``schedule.malformed`` indices
    (in place); returns how many were corrupted.  Seeded so the corrupt
    positions/values are as reproducible as the schedule itself."""
    rng = np.random.default_rng(seed)
    n = 0
    for i in sorted(schedule.malformed):
        if i < len(reqs):
            reqs[i].tokens = corrupt_tokens(reqs[i].tokens, vocab_size,
                                            rng)
            n += 1
    return n
