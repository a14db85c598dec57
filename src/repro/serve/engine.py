"""Batched serving engine: prefill + sampling decode loop.

`generate` is the reference path used by the examples and tests; the
`serve_step` it jits per step is the same function the decode dry-run
shapes lower (one new token against the KV cache/state).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.csr import BlockCSR
from repro.kernels.partition import (PartitionedSpmmPlan,
                                     plan_partitioned_spmm)
from repro.kernels.schedule import (SpmmPlan, SpmmTrainPlan, plan_spmm,
                                    plan_spmm_vjp)
from repro.models import lm
from repro.models.layers import sparse_linear


@dataclasses.dataclass(frozen=True)
class SparseLogitHead:
    """Serving-side block-sparse unembedding.

    Scoring a batch of hidden states ``(B, S, D)`` against a block-sparse
    ``(V, D)`` head used to mean a host-side loop of one kernel call per
    sequence — the seed ``maple_spmm`` took a single unbatched RHS.  With
    the batched planned grid the whole batch is one ``pallas_call``, and
    the load-balanced execution plan is built **once** here from the
    weight's (static) sparsity pattern and reused on every step.

    ``build(trainable=True)`` caches the transpose-side plan alongside
    the forward one (``plan_spmm_vjp``), so the same head object serves
    *and* backpropagates under jit — e.g. logit-distillation fine-tuning
    against the serving head without replanning.

    ``build(n_shards=D)`` partitions the head's block-rows across ``D``
    devices (``kernels.partition``): each device scores its vocabulary
    slice with a shard-local plan under ``shard_map``, and the row-offset
    epilogue reassembles the logits — the §V PE-array scaling story
    applied to the widest matmul serving runs.  Pass
    ``len(jax.local_devices())`` to use every local device; the same
    head still works on a 1-device box (stacked loop, identical result).
    ``n_col_shards=C`` adds the second mesh axis: the hidden-state
    activations — long-sequence serving's memory wall — are panel-split
    along their token dimension instead of replicated on every shard,
    cutting per-device dense-operand bytes ~``C``× (the logits panels
    reassemble by placement, no collective).
    """

    weight: BlockCSR         # (vocab, d_model) block-sparse
    plan: SpmmPlan | SpmmTrainPlan | PartitionedSpmmPlan

    @classmethod
    def build(cls, weight: BlockCSR, *, n_lanes: int = 8,
              chunk: int | None = None, n_shards: int | None = None,
              n_col_shards: int | None = None,
              trainable: bool = False,
              plan: str | None = None) -> "SparseLogitHead":
        """``plan="auto"`` replaces the hand-tuned knobs with a budgeted
        ``kernels.autotune`` search over the head's sparsity pattern
        (memoized — rebuilding a head for a seen pattern never replans);
        ``n_shards`` then bounds the searched device axis,
        ``n_col_shards`` pins the column split (a memory layout, never
        searched), and ``n_lanes``/``chunk`` are ignored (the search
        owns them)."""
        if plan is not None:
            if plan != "auto":
                raise ValueError(f"unknown plan {plan!r}; only 'auto' "
                                 f"(or drop it for the hand-tuned knobs)")
            from repro.kernels.autotune import auto_plan
            return cls(weight=weight,
                       plan=auto_plan(weight, trainable=trainable,
                                      n_shards=n_shards,
                                      n_col_shards=n_col_shards))
        col = n_col_shards if n_col_shards is not None else 1
        if trainable:
            plan = plan_spmm_vjp(weight, n_lanes=n_lanes, chunk=chunk,
                                 n_shards=n_shards, n_col_shards=n_col_shards)
        elif (n_shards is not None and n_shards > 1) or col > 1:
            plan = plan_partitioned_spmm(
                weight, n_shards=n_shards if n_shards is not None else 1,
                n_lanes=n_lanes, chunk=chunk, n_col_shards=col)
        else:
            plan = plan_spmm(weight, n_lanes=n_lanes, chunk=chunk)
        return cls(weight=weight, plan=plan)

    @property
    def _fwd_plan(self) -> SpmmPlan | PartitionedSpmmPlan:
        return (self.plan.fwd if isinstance(self.plan, SpmmTrainPlan)
                else self.plan)

    @property
    def predicted_cycles(self):
        """Planner/analytical cycle estimates (see SpmmPlan; train plans
        add the A^T-pass breakdown)."""
        return self.plan.predicted_cycles()

    def __call__(self, hidden: jax.Array) -> jax.Array:
        """hidden: (B, S, D) → logits (B, S, V) in one batched launch.
        Decode's one token per sequence is scored as one ``B``-token
        right-hand side, one token tile for all slots
        (``layers.token_tiles``).

        The fused planned kernels merge cross-lane partials without a
        per-lane buffer: the compact layout's flush tiles are bounded by
        the plan's ``written`` map rather than ``lanes × V`` — so the lane-buffer budget (and
        the reduced-lane replanning it forced on wide vocab × token
        shapes) is gone with the ``(G, lanes, V, N)`` buffer itself."""
        return sparse_linear(self.weight, hidden, plan=self.plan)


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    temperature: float = 0.0     # 0 → greedy
    top_k: int = 0               # 0 → no top-k filtering
    max_new_tokens: int = 32
    eos_id: int = -1             # -1 → never stop early


def sample_token(logits, key, cfg: SamplingConfig, vocab_size: int):
    """logits: (B, V_padded) → (B,) int32; padded vocab ids are masked."""
    logits = logits.astype(jnp.float32)
    mask = jnp.arange(logits.shape[-1]) < vocab_size
    logits = jnp.where(mask, logits, -jnp.inf)
    if cfg.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / cfg.temperature
    if cfg.top_k > 0:
        kth = jax.lax.top_k(logits, cfg.top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def sample_tokens(logits, keys, poison, cfg: SamplingConfig,
                  vocab_size: int):
    """Every row's token in one program: ``sample_token`` over rows.

    logits: (B, V_padded); keys: (B, 2), one key chain per row; poison: a
    row index whose logits count as non-finite (-1: none).  Row ``b``
    splits its key and draws with the sub key, the same chain a per-row
    loop of ``split`` + ``sample_token`` walks.  Returns ``(tokens (B,)
    int32, finite (B,) bool, keys (B, 2))``: ``finite`` is over the REAL
    vocabulary only (padded ids carry garbage by design).
    """
    def row(lg, key):
        key, sub = jax.random.split(key)
        tok = sample_token(lg[None], sub, cfg, vocab_size)[0]
        return tok, jnp.isfinite(lg[:vocab_size]).all(), key
    tokens, finite, keys = jax.vmap(row)(logits, keys)
    finite = finite & (jnp.arange(logits.shape[0]) != poison)
    return tokens, finite, keys


def token_entropy(logits, vocab_size: int):
    """Per-row softmax entropy over the REAL vocabulary.

    logits: (B, V_padded) → (B,) f32.  Padded vocab slots hold garbage
    scores (``cfg.vocab_padded`` rounds the head up for sharding), so the
    distribution is taken over ``logits[:, :vocab_size]`` — the same ids
    ``sample_token`` can actually emit.
    """
    lg = logits[..., :vocab_size].astype(jnp.float32)
    probs = jax.nn.softmax(lg, axis=-1)
    return -jnp.sum(probs * jnp.log(probs + 1e-9), axis=-1)


# --------------------------------------------------------------------------
# jitted-callable cache: back-to-back generate()/engine calls must not
# recompile.  jax.jit caches traces per *callable*, and a fresh
# functools.partial is a fresh callable — so the callables are built once
# here, keyed on the (hashable, frozen) ModelConfig.
# --------------------------------------------------------------------------

_PREFILL_JIT: Dict[tuple, Any] = {}
_DECODE_JIT: Dict[tuple, Any] = {}
_SAMPLE_JIT: Dict[tuple, Any] = {}


def named_jit(fn, name: str):
    """``jax.jit(fn)`` whose compiled program is named ``jit_<name>`` in
    device traces (a jitted ``functools.partial`` or ``lambda`` comes out
    as ``jit__unknown`` or ``jit__lambda``)."""
    def call(*args, **kwargs):
        return fn(*args, **kwargs)
    call.__name__ = call.__qualname__ = name
    return jax.jit(call)


def jitted_prefill(cfg: ModelConfig, max_seq: int, *,
                   return_hidden: bool = False):
    """Cached ``jax.jit(lm.prefill)`` for (cfg, max_seq): ``jit_prefill``."""
    key = (cfg, int(max_seq), bool(return_hidden))
    fn = _PREFILL_JIT.get(key)
    if fn is None:
        fn = named_jit(functools.partial(lm.prefill, cfg=cfg,
                                         max_seq=int(max_seq),
                                         return_hidden=return_hidden),
                       "prefill")
        _PREFILL_JIT[key] = fn
    return fn


def jitted_decode_step(cfg: ModelConfig, *, paged: bool = False,
                       return_hidden: bool = False):
    """Cached ``jax.jit(lm.decode_step)`` per cfg (``jit_decode_static``),
    or the paged variant the batcher fuses over its slots
    (``jit_decode_step``)."""
    key = (cfg, bool(paged), bool(return_hidden))
    fn = _DECODE_JIT.get(key)
    if fn is None:
        if paged:
            fn = named_jit(functools.partial(lm.decode_step_paged, cfg=cfg,
                                             return_hidden=return_hidden),
                           "decode_step")
        else:
            fn = named_jit(functools.partial(lm.decode_step, cfg=cfg,
                                             return_hidden=return_hidden),
                           "decode_static")
        _DECODE_JIT[key] = fn
    return fn


def jitted_sampler(cfg: SamplingConfig, vocab_size: int):
    """Cached ``jit_sample``: ``sample_tokens`` on the last position of
    a model output ``(B, S, V_padded)``, so the slice rides in the same
    program.  One compile per batch width."""
    key = (cfg, int(vocab_size))
    fn = _SAMPLE_JIT.get(key)
    if fn is None:
        fn = named_jit(lambda logits, keys, poison: sample_tokens(
            logits[:, -1], keys, poison, cfg, int(vocab_size)), "sample")
        _SAMPLE_JIT[key] = fn
    return fn


def complete_static(params, cfg: ModelConfig, tokens, max_new: int, *,
                    sampling: SamplingConfig, key, eos_id: int = -1,
                    head: Optional["SparseLogitHead"] = None):
    """Finish ONE request on the static (non-paged) path.

    The continuous batcher's graceful-degradation target: when the fused
    paged step's retry budget is exhausted, each live slot's remaining
    tokens are produced here — batch-1 prefill over the full context
    (prompt + tokens generated so far), then per-token ``decode_step``.
    Greedy output is bit-identical to the paged path (the same
    bit-identity pin the engine already carries against ``generate``);
    sampled requests continue their own ``key`` chain, so the draw
    sequence matches the engine's per-slot chain too.

    Returns ``(new_tokens, reason, key)`` with ``reason`` in
    ``("eos", "length", "error")`` — the non-finite-logits guard applies
    here exactly as in the fused path.
    """
    tokens = np.asarray(tokens, np.int32).reshape(-1)
    if max_new <= 0:
        return [], "length", key
    use_head = head is not None
    prefill = jitted_prefill(cfg, tokens.size + max_new,
                             return_hidden=use_head)
    step_fn = jitted_decode_step(cfg, return_hidden=use_head)
    out, state = prefill(params, batch={"tokens": jnp.asarray(
        tokens, jnp.int32)[None]})
    logits = head(out) if use_head else out
    new_tokens: list = []
    while True:
        row = np.asarray(logits[:, -1])
        if not np.isfinite(row[:, :cfg.vocab_size]).all():
            return new_tokens, "error", key
        key, sub = jax.random.split(key)
        tok = int(sample_token(jnp.asarray(row), sub, sampling,
                               cfg.vocab_size)[0])
        new_tokens.append(tok)
        if eos_id >= 0 and tok == eos_id:
            return new_tokens, "eos", key
        if len(new_tokens) >= max_new:
            return new_tokens, "length", key
        out, state = step_fn(params, state=state,
                             tokens=jnp.full((1, 1), tok, jnp.int32))
        logits = head(out) if use_head else out


def generate(params, cfg: ModelConfig, batch: Dict[str, jax.Array],
             sampling: SamplingConfig = SamplingConfig(),
             key: Optional[jax.Array] = None,
             max_seq: Optional[int] = None):
    """Prefill on `batch` then decode `max_new_tokens` greedily/sampled.

    Returns (tokens (B, T), per-step entropy trace), T ≤ max_new_tokens.

    EOS is tracked *per sequence*: a row that samples ``eos_id`` stops —
    its later slots are filled with ``eos_id`` (never live samples) and it
    no longer contributes to the entropy trace — and the loop exits as
    soon as every row has finished.  Entropy is measured over the real
    vocabulary only (``token_entropy``): the padded head slots carry
    garbage logits that ``sample_token`` masks, so the trace must too.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    prompt_len = batch["tokens"].shape[1] + max(cfg.n_patches, 0)
    if max_seq is None:
        max_seq = prompt_len + sampling.max_new_tokens

    prefill = jitted_prefill(cfg, max_seq)
    step_fn = jitted_decode_step(cfg)

    logits, state = prefill(params, batch=batch)
    b = batch["tokens"].shape[0]
    done = jnp.zeros((b,), bool)
    outs = []
    entropies = []
    for t in range(sampling.max_new_tokens):
        key, sub = jax.random.split(key)
        tok = sample_token(logits[:, -1], sub, sampling, cfg.vocab_size)
        if sampling.eos_id >= 0:
            tok = jnp.where(done, sampling.eos_id, tok)
        outs.append(tok)
        ent = token_entropy(logits[:, -1], cfg.vocab_size)
        live = ~done
        entropies.append(float(jnp.where(live, ent, 0.0).sum()
                               / jnp.maximum(live.sum(), 1)))
        if sampling.eos_id >= 0:
            done = done | (tok == sampling.eos_id)
            if bool(done.all()):
                break
        logits, state = step_fn(params, state=state, tokens=tok[:, None])
    return jnp.stack(outs, axis=1), entropies
