"""Block-sparse flash attention — the Maple dataflow applied to attention.

A local/banded attention mask is exactly a banded BSR pattern over
(q-block × kv-block) tiles (DESIGN §5: recurrentgemma's window): the list
of admissible kv-blocks per q-block is CSR-style metadata, and tiles
outside the band are *never fetched* — the same zero-block skipping as
`maple_spmm`, with the PSB replaced by the flash (m, l, acc) online-softmax
accumulator in VMEM.

Metadata contract (built by ops.py from (seq, window) or any block mask):
  kv_map: (nq, max_blocks) int32 — kv-block ids per q-block, -1 padded.
The kernel runs grid (nq, max_blocks); padded steps contribute nothing
(@pl.when) and their BlockSpec index clamps to 0 — fetched but unused,
matching the BlockCSR padding protocol.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(
    kv_map,           # (nq*max_nb,) int32 scalar prefetch, -1 pads
    q_ref,            # (1, bq, H, hd) — current q block (heads folded in)
    k_ref,            # (1, bk, H, hd) — selected kv block
    v_ref,            # (1, bk, H, hd)
    out_ref,          # (1, bq, H, hd)
    m_ref, l_ref, acc_ref,   # VMEM scratch: the flash PSB
    *,
    max_nb: int,
    bq: int,
    bk: int,
    causal: bool,
    window: int,
):
    qi = pl.program_id(0)
    t = pl.program_id(1)
    slot = qi * max_nb + t
    kv_id = kv_map[slot]
    live = kv_id >= 0

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)          # (bq, H, hd)
        k = k_ref[0].astype(jnp.float32)          # (bk, H, hd)
        v = v_ref[0].astype(jnp.float32)
        hd = q.shape[-1]
        s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)

        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = kv_id * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window > 0:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask[None], s, -jnp.inf)

        m_prev = m_ref[...]                       # (H, bq)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[None], p, 0.0)
        corr = jnp.where(jnp.isfinite(m_prev),
                         jnp.exp(m_prev - m_safe), 0.0)
        m_ref[...] = m_new
        l_ref[...] = l_prev * corr + p.sum(axis=-1)
        acc_ref[...] = (acc_ref[...] * corr[..., None]
                        + jnp.einsum("hqk,khd->hqd", p, v))

    @pl.when(t == max_nb - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-20)[..., None]
        out = (acc_ref[...] / l).transpose(1, 0, 2)       # (bq, H, hd)
        out_ref[0] = out.astype(out_ref.dtype)


def block_attention_pallas(
    q: jax.Array,      # (S, H, hd)  — single example (vmap for batch)
    k: jax.Array,      # (S, H, hd)
    v: jax.Array,
    kv_map: jax.Array,  # (nq, max_nb) int32
    *,
    bq: int = 128,
    bk: int = 128,
    causal: bool = True,
    window: int = 0,
    interpret: bool,
) -> jax.Array:
    s, h, hd = q.shape
    if s % bq or s % bk:
        raise ValueError(f"S={s} vs blocks ({bq},{bk})")
    nq, max_nb = kv_map.shape
    flat_map = jnp.maximum(kv_map.reshape(-1), -1)

    kernel = functools.partial(_kernel, max_nb=max_nb, bq=bq, bk=bk,
                               causal=causal, window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nq, max_nb),
            in_specs=[
                pl.BlockSpec((1, bq, h, hd), lambda i, t, m: (i, 0, 0, 0)),
                pl.BlockSpec((1, bk, h, hd),
                             lambda i, t, m: (
                                 jnp.maximum(m[i * max_nb + t], 0), 0, 0, 0)),
                pl.BlockSpec((1, bk, h, hd),
                             lambda i, t, m: (
                                 jnp.maximum(m[i * max_nb + t], 0), 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, h, hd),
                                   lambda i, t, m: (i, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, bq), jnp.float32),
                pltpu.VMEM((h, bq), jnp.float32),
                pltpu.VMEM((h, bq, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((s // bq, bq, h, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(flat_map,
      q.reshape(s // bq, bq, h, hd),
      k.reshape(s // bk, bk, h, hd),
      v.reshape(s // bk, bk, h, hd)).reshape(s, h, hd)


def local_window_kv_map(seq: int, window: int, bq: int, bk: int) -> np.ndarray:
    """BSR metadata for a causal local window: the kv-blocks each q-block
    may touch (the banded pattern of DESIGN §5)."""
    nq = seq // bq
    rows = []
    for i in range(nq):
        q_lo, q_hi = i * bq, (i + 1) * bq - 1
        k_lo = max(0, (q_lo - window + 1) // bk)
        k_hi = q_hi // bk
        rows.append(list(range(k_lo, k_hi + 1)))
    max_nb = max(len(r) for r in rows)
    out = np.full((nq, max_nb), -1, np.int32)
    for i, r in enumerate(rows):
        out[i, :len(r)] = r
    return out
