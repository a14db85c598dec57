"""Shared transformer layers: norms, RoPE, GQA attention (causal / local /
cross / decode), SwiGLU-family MLPs.

Everything is functional (params are plain dict pytrees) and mesh-agnostic:
activation sharding hints go through :func:`repro.distributed.sharding.shard`
which is a no-op outside a mesh context.

Attention is *chunked* (flash-style): ``lax.scan`` over KV blocks with an
online max/denominator in f32 — scores for the full sequence are never
materialized, which is what makes the 32k-prefill shapes lowerable.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.csr import BlockCSR
from repro.distributed.sharding import shard


# --------------------------------------------------------------------------
# initializers / norms
# --------------------------------------------------------------------------

def dense_init(key, shape, in_axis_size, dtype=jnp.float32):
    scale = 1.0 / math.sqrt(max(in_axis_size, 1))
    return (jax.random.normal(key, shape) * scale).astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x, weight, eps: float = 1e-6):
    """RMSNorm with a hand-written backward that keeps the residual-stream
    cotangent in x.dtype.

    Autodiff of the f32 stats path makes dx f32, which doubles the bytes of
    every TP-boundary all-reduce in the backward pass (measured on the 72B
    train cell — EXPERIMENTS §Perf iteration 2).  Stats and dweight still
    reduce in f32; only the wide per-element math stays bf16.
    """
    y, _ = _rms_norm_fwd_math(x, weight, eps)
    return y


def _rms_norm_fwd_math(x, weight, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    inv = jax.lax.rsqrt(var + eps)                       # (..., 1) f32
    y = x * inv.astype(x.dtype) * (1.0 + weight).astype(x.dtype)
    return y, inv


def _rms_norm_fwd(x, weight, eps):
    y, inv = _rms_norm_fwd_math(x, weight, eps)
    return y, (x, weight, inv)


def _rms_norm_bwd(eps, res, dy):
    x, weight, inv = res
    d = x.shape[-1]
    w1 = (1.0 + weight).astype(x.dtype)
    dy_w = dy * w1                                        # x.dtype
    # m = E[dy_w · x] per row, reduced in f32
    m = jnp.mean((dy_w * x).astype(jnp.float32), axis=-1, keepdims=True)
    coeff = (inv ** 3) * m                                # (..., 1) f32
    dx = dy_w * inv.astype(x.dtype) - x * coeff.astype(x.dtype)
    dweight = jnp.sum(
        (dy * (x * inv.astype(x.dtype))).astype(jnp.float32),
        axis=tuple(range(x.ndim - 1)))
    return dx, dweight.astype(weight.dtype)


rms_norm.defvjp(_rms_norm_fwd, _rms_norm_bwd)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return ((x - mu.astype(x.dtype)) * inv * weight.astype(x.dtype)
            + bias.astype(x.dtype))


def apply_norm(x, p, kind: str):
    if kind == "rmsnorm":
        return rms_norm(x, p["scale"])
    return layer_norm(x, p["scale"], p["bias"])


def init_norm(key, d, kind: str):
    if kind == "rmsnorm":
        return {"scale": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: (..., S) int32."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    sin = jnp.sin(angles)[..., None, :]                        # (..., S, 1, hd/2)
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# GQA attention
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    causal: bool = True
    window: Optional[int] = None      # local attention window (tokens)
    norm: str = "rmsnorm"


def init_attention(key, cfg: AttnConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": dense_init(ks[0], (d, h, hd), d, dtype),
        "wk": dense_init(ks[1], (d, kvh, hd), d, dtype),
        "wv": dense_init(ks[2], (d, kvh, hd), d, dtype),
        "wo": dense_init(ks[3], (h, hd, d), h * hd, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), dtype)
        p["bk"] = jnp.zeros((kvh, hd), dtype)
        p["bv"] = jnp.zeros((kvh, hd), dtype)
    if cfg.qk_norm:
        p["q_norm"] = init_norm(ks[4], hd, "rmsnorm")
        p["k_norm"] = init_norm(ks[5], hd, "rmsnorm")
    return p


def _project_qkv(p, cfg: AttnConfig, x, positions, rope: bool = True):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"]["scale"])
        k = rms_norm(k, p["k_norm"]["scale"])
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _repeat_kv(k, n_heads: int):
    """(B, S, KVH, hd) → (B, S, H, hd) by head-group broadcast."""
    kvh = k.shape[2]
    if kvh == n_heads:
        return k
    rep = n_heads // kvh
    return jnp.repeat(k, rep, axis=2)


def _tile_mask(qpos, kpos, causal: bool, window: Optional[int]):
    mask = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window is not None:
        mask &= (qpos[:, None] - kpos[None, :]) < window
    return mask


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _flash_forward_impl(q, k, v, causal, window, q_chunk, kv_chunk,
                        q_offset):
    """Online-softmax forward.  Returns (out, L) with L = m + log(l),
    the per-row logsumexp needed by the flash backward.

    A *named jit region*: the roofline walker charges only its boundary
    I/O — this is the Pallas flash kernel's jnp twin (interior tiles live
    in VMEM on the TPU target)."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    nq, nk = sq // q_chunk, sk // kv_chunk
    qb = q.reshape(b, nq, q_chunk, h, hd)
    q_pos = (q_offset + jnp.arange(sq)).reshape(nq, q_chunk)

    def process_q_block(qi, q_blk):
        m0 = jnp.full((b, h, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        acc0 = jnp.zeros((b, h, q_chunk, hd), jnp.float32)
        qpos = q_pos[qi]

        def kv_step(carry, ki):
            m, l, acc = carry
            ks = jax.lax.dynamic_slice_in_dim(k, ki * kv_chunk, kv_chunk, 1)
            vs = jax.lax.dynamic_slice_in_dim(v, ki * kv_chunk, kv_chunk, 1)
            s = jnp.einsum("bqhk,bchk->bhqc",
                           q_blk.astype(jnp.float32) * scale,
                           ks.astype(jnp.float32))
            kpos = ki * kv_chunk + jnp.arange(kv_chunk)
            mask = _tile_mask(qpos, kpos, causal, window)
            s = jnp.where(mask[None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(mask[None, None], p, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqc,bchk->bhqk", p, vs.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, acc0),
                                      jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-20)[..., None]
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        lse = m_safe + jnp.log(jnp.maximum(l, 1e-20))       # (B,H,q_chunk)
        return out.transpose(0, 2, 1, 3), lse

    outs, lses = jax.vmap(process_q_block, in_axes=(0, 1),
                          out_axes=(1, 2))(jnp.arange(nq), qb)
    out = outs.reshape(b, sq, h, hd).astype(q.dtype)
    lse = lses.reshape(b, h, sq)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def chunked_attention(q, k, v, causal: bool = True,
                      window: Optional[int] = None,
                      q_chunk: int = 512, kv_chunk: int = 1024,
                      q_offset: int = 0):
    """Flash attention in jnp (custom VJP — the TPU-kernel twin).

    q: (B, Sq, H, hd); k/v: (B, Sk, H, hd), already head-repeated.  Scores
    exist only per (q_chunk × kv_chunk) tile in both passes; the backward
    recomputes p from the saved logsumexp instead of storing residuals —
    this is what bounds train/prefill activation memory at 32k (DESIGN §6).
    """
    out, _ = _flash_forward_impl(q, k, v, causal, window, q_chunk,
                                 kv_chunk, q_offset)
    return out


def _flash_fwd_rule(q, k, v, causal, window, q_chunk, kv_chunk, q_offset):
    out, lse = _flash_forward_impl(q, k, v, causal, window, q_chunk,
                                   kv_chunk, q_offset)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, window, q_chunk, kv_chunk, q_offset, res, dout):
    q, k, v, out, lse = res
    return _flash_backward_impl(q, k, v, out, lse, dout, causal, window,
                                q_chunk, kv_chunk, q_offset)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10))
def _flash_backward_impl(q, k, v, out, lse, dout, causal, window, q_chunk,
                         kv_chunk, q_offset):
    """Flash backward (named jit region — see _flash_forward_impl)."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    nq, nk = sq // q_chunk, sk // kv_chunk

    dout32 = dout.astype(jnp.float32)
    out32 = out.astype(jnp.float32)
    # D_i = rowsum(dout ⊙ out)
    delta = jnp.einsum("bshk,bshk->bhs", dout32, out32)     # (B,H,Sq)

    q_pos_all = q_offset + jnp.arange(sq)

    def kv_step(dq_acc, ki):
        ks = jax.lax.dynamic_slice_in_dim(k, ki * kv_chunk, kv_chunk, 1)
        vs = jax.lax.dynamic_slice_in_dim(v, ki * kv_chunk, kv_chunk, 1)
        ks32 = ks.astype(jnp.float32)
        vs32 = vs.astype(jnp.float32)
        kpos = ki * kv_chunk + jnp.arange(kv_chunk)

        def q_step(carry, qi):
            dq_acc, dkj, dvj = carry
            q0 = qi * q_chunk
            qb = jax.lax.dynamic_slice_in_dim(q, q0, q_chunk, 1)
            db = jax.lax.dynamic_slice_in_dim(dout32, q0, q_chunk, 1)
            lseb = jax.lax.dynamic_slice_in_dim(lse, q0, q_chunk, 2)
            deltab = jax.lax.dynamic_slice_in_dim(delta, q0, q_chunk, 2)
            qpos = jax.lax.dynamic_slice_in_dim(q_pos_all, q0, q_chunk, 0)

            s = jnp.einsum("bqhk,bchk->bhqc",
                           qb.astype(jnp.float32) * scale, ks32)
            mask = _tile_mask(qpos, kpos, causal, window)
            p = jnp.exp(s - lseb[..., None])
            p = jnp.where(mask[None, None], p, 0.0)         # (B,H,qc,kc)

            dvj = dvj + jnp.einsum("bhqc,bqhd->bchd", p, db)
            dp = jnp.einsum("bqhd,bchd->bhqc", db, vs32)
            ds = p * (dp - deltab[..., None])
            dqb = jnp.einsum("bhqc,bchd->bqhd", ds, ks32) * scale
            dkj = dkj + jnp.einsum("bhqc,bqhd->bchd", ds,
                                   qb.astype(jnp.float32)) * scale
            prev = jax.lax.dynamic_slice_in_dim(dq_acc, q0, q_chunk, 1)
            dq_acc = jax.lax.dynamic_update_slice_in_dim(
                dq_acc, prev + dqb, q0, 1)
            return (dq_acc, dkj, dvj), None

        zero_kc = jnp.zeros((b, kv_chunk, h, hd), jnp.float32)
        (dq_acc, dkj, dvj), _ = jax.lax.scan(
            q_step, (dq_acc, zero_kc, zero_kc), jnp.arange(nq))
        return dq_acc, (dkj, dvj)

    dq0 = jnp.zeros((b, sq, h, hd), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(kv_step, dq0, jnp.arange(nk))
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(b, sk, h, hd)
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(b, sk, h, hd)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


chunked_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _chunked_attention_call(q, k, v, *, causal: bool,
                            window: Optional[int], q_chunk: int = 512,
                            kv_chunk: int = 1024, q_offset: int = 0):
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    q_chunk = largest_divisor_leq(sq, q_chunk)
    kv_chunk = largest_divisor_leq(sk, kv_chunk)
    return chunked_attention(q, k, v, causal, window, q_chunk, kv_chunk,
                             q_offset)


def attention(p, cfg: AttnConfig, x, positions, *, q_chunk=512, kv_chunk=1024):
    """Full-sequence self-attention (train / prefill)."""
    q, k, v = _project_qkv(p, cfg, x, positions)
    q = shard(q, ("batch", "seq", "heads", None))
    k = _repeat_kv(k, cfg.n_heads)
    v = _repeat_kv(v, cfg.n_heads)
    out = _chunked_attention_call(q, k, v, causal=cfg.causal,
                                  window=cfg.window, q_chunk=q_chunk,
                                  kv_chunk=kv_chunk)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def attention_decode(p, cfg: AttnConfig, x, cache_k, cache_v, pos):
    """One-token decode step against a static KV cache.

    x: (B, 1, D); cache_k/v: (B, S_cache, KVH, hd); pos: scalar int32 —
    number of tokens generated so far (absolute).  For a *global* cache
    ``S_cache >= pos`` and the new K/V land at slot ``pos``; for a *rolling
    local-window* cache ``S_cache == window`` and slots wrap (RoPE is applied
    at the absolute position before the write, so wrapped slots stay
    correct).  Returns (out, new_k, new_v).

    The softmax runs over the (possibly seq-sharded) cache axis in plain
    jnp — GSPMD inserts the max/sum/weighted-sum collectives when the cache
    is sharded over `model` (DESIGN §6, flash-decode equivalent).
    """
    s_cache = cache_k.shape[1]
    rolling = cfg.window is not None and s_cache == cfg.window
    write_idx = jnp.mod(pos, s_cache) if rolling else pos

    positions = jnp.full((x.shape[0], 1), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(p, cfg, x, positions)
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k_new.astype(cache_k.dtype), write_idx, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v_new.astype(cache_v.dtype), write_idx, axis=1)

    # absolute position held by each slot
    slot = jnp.arange(s_cache)
    if rolling:
        abs_pos = pos - jnp.mod(write_idx - slot, s_cache)
    else:
        abs_pos = slot
    valid = (abs_pos >= 0) & (abs_pos <= pos)
    if cfg.window is not None:
        valid &= abs_pos > pos - cfg.window

    # grouped-query attention WITHOUT materializing head-repeated K/V
    # (the repeat costs 2×(B,S,H,hd) HBM on a 32k cache — §Perf memory fix)
    b = q.shape[0]
    kvh = cfg.n_kv_heads
    grp = cfg.n_heads // kvh
    scale = 1.0 / math.sqrt(cfg.head_dim)
    qg = q.reshape(b, 1, kvh, grp, cfg.head_dim)
    # flash-decode layout: the single query token is replicated over
    # `model`; the 32k cache stays sharded on its sequence axis, and the
    # softmax max/sum and the V contraction reduce over the sharded axis
    # (GSPMD inserts small psums).  Without these hints GSPMD may instead
    # all-gather the whole cache per layer (measured +8.6 GiB/layer).
    qg = shard(qg, ("batch", None, None, None, None))
    s = jnp.einsum("bqkgh,bskh->bkgqs",
                   qg.astype(jnp.float32) * scale,
                   cache_k.astype(jnp.float32))             # (B,KV,G,1,S)
    s = shard(s, ("batch", None, None, None, "kv_seq"))
    s = jnp.where(valid[None, None, None, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w,
                     cache_v.astype(jnp.float32))
    out = out.reshape(b, 1, cfg.n_heads, cfg.head_dim).astype(x.dtype)
    out = shard(out, ("batch", None, None, None))
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache_k, cache_v


def attention_decode_paged(p, cfg: AttnConfig, x, pool_k, pool_v, table,
                           pos):
    """One fused decode step against a *paged* KV pool (vLLM-style).

    x: (B, 1, D) — one new token per batch slot; B is the engine's slot
    count, not a request count.  pool_k/v: (n_pages, P, KVH, hd) — the
    physical page pool shared by every slot (page 0 is the sacrificial
    dead page free slots write into).  table: (B, max_pages) int32 —
    per-slot block table mapping logical page ``t // P`` to a physical
    page.  pos: (B,) int32 — per-slot absolute decode position (the slot
    this token is written to), so slots at *different* sequence depths
    share one fused step.

    Pages keep tokens in logical order (no rolling layout): local-window
    masking happens at read time, and the serving engine frees pages that
    fall entirely behind the window instead.  Reads gather the slot's
    pages back into a (B, max_pages·P, KVH, hd) view; entries past the
    slot's position (or outside its window) are masked to -inf exactly
    like the static cache path, so a gathered page holding a previous
    occupant's stale tokens can never contribute (softmax weight exactly
    0.0).  Returns (out, new_pool_k, new_pool_v).
    """
    n_pages, psize = pool_k.shape[0], pool_k.shape[1]
    positions = pos[:, None].astype(jnp.int32)              # (B, 1)
    q, k_new, v_new = _project_qkv(p, cfg, x, positions)

    page_idx = pos // psize
    off = pos % psize
    phys = jnp.take_along_axis(table, page_idx[:, None], axis=1)[:, 0]
    pool_k = pool_k.at[phys, off].set(k_new[:, 0].astype(pool_k.dtype))
    pool_v = pool_v.at[phys, off].set(v_new[:, 0].astype(pool_v.dtype))

    gk = pool_k[table]                  # (B, max_pages, P, KVH, hd)
    gv = pool_v[table]
    b = x.shape[0]
    s_len = gk.shape[1] * psize
    gk = gk.reshape(b, s_len, cfg.n_kv_heads, cfg.head_dim)
    gv = gv.reshape(b, s_len, cfg.n_kv_heads, cfg.head_dim)

    idx = jnp.arange(s_len)[None, :]                        # logical pos
    valid = idx <= pos[:, None]
    if cfg.window is not None:
        valid &= idx > (pos[:, None] - cfg.window)

    # grouped-query attention without materializing head-repeated K/V
    # (same dataflow as attention_decode; the mask is per-row here)
    kvh = cfg.n_kv_heads
    grp = cfg.n_heads // kvh
    scale = 1.0 / math.sqrt(cfg.head_dim)
    qg = q.reshape(b, 1, kvh, grp, cfg.head_dim)
    s = jnp.einsum("bqkgh,bskh->bkgqs",
                   qg.astype(jnp.float32) * scale,
                   gk.astype(jnp.float32))                  # (B,KV,G,1,S)
    s = jnp.where(valid[:, None, None, None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, gv.astype(jnp.float32))
    out = out.reshape(b, 1, cfg.n_heads, cfg.head_dim).astype(x.dtype)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), pool_k, pool_v


def attention_prefill(p, cfg: AttnConfig, x, positions, *,
                      cache_len: int, q_chunk=512, kv_chunk=1024):
    """Full-sequence attention that also returns the K/V cache.

    Returns (out, k_cache, v_cache) with caches of length ``cache_len``
    (pre-head-repeat, n_kv_heads) — for a local window, the *last* ``window``
    positions in rolling layout so that decode can continue seamlessly.
    """
    q, k, v = _project_qkv(p, cfg, x, positions)
    q = shard(q, ("batch", "seq", "heads", None))
    kr = _repeat_kv(k, cfg.n_heads)
    vr = _repeat_kv(v, cfg.n_heads)
    out = _chunked_attention_call(q, kr, vr, causal=cfg.causal,
                                  window=cfg.window, q_chunk=q_chunk,
                                  kv_chunk=kv_chunk)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])

    s = x.shape[1]
    if cache_len >= s:
        pad = cache_len - s
        k_cache = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        # rolling local-window layout: slot (pos % cache_len) holds pos
        tail_k = k[:, -cache_len:]
        tail_v = v[:, -cache_len:]
        shift = jnp.mod(s - cache_len, cache_len)
        k_cache = jnp.roll(tail_k, shift=shift, axis=1)
        v_cache = jnp.roll(tail_v, shift=shift, axis=1)
    return out, k_cache, v_cache


def largest_divisor_leq(n: int, target: int) -> int:
    """Largest divisor of n that is ≤ target (trace-time ints)."""
    c = min(n, target)
    while n % c:
        c -= 1
    return c


def cross_attention(p, cfg: AttnConfig, x, enc_k, enc_v):
    """Decoder cross-attention against precomputed encoder K/V (no RoPE)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    k = _repeat_kv(enc_k, cfg.n_heads)
    v = _repeat_kv(enc_v, cfg.n_heads)
    out = _chunked_attention_call(q, k, v, causal=False, window=None)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def encode_kv(p, cfg: AttnConfig, enc_out):
    """Project encoder output to cross-attention K/V once (cached)."""
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])
    if cfg.qkv_bias:
        k, v = k + p["bk"], v + p["bv"]
    return k, v


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, activation: str,
             dtype=jnp.float32, *, sparse_down: bool = False,
             sparse_block=(64, 64), sparse_density: float = 0.25,
             mask_key=None):
    """MLP params.  ``sparse_down=True`` replaces the down projection with
    a block-sparse :class:`~repro.core.csr.BlockCSR` weight (the Maple
    kernel as a trainable layer).  Pass the same ``mask_key`` for every
    layer of a scanned stack so all layers share one block pattern — the
    stacked pytree then has congruent leaf shapes and a single
    ``SpmmTrainPlan`` drives every layer's forward *and* backward.
    """
    ks = jax.random.split(key, 3)
    if activation in ("silu", "gelu_glu"):  # gated (SwiGLU / GeGLU)
        p = {
            "w_gate": dense_init(ks[0], (d_model, d_ff), d_model, dtype),
            "w_up": dense_init(ks[1], (d_model, d_ff), d_model, dtype),
        }
        if sparse_down:
            p["w_down"] = init_sparse_linear(
                ks[2], d_ff, d_model, block_shape=sparse_block,
                block_density=sparse_density, dtype=dtype,
                mask_key=mask_key)
        else:
            p["w_down"] = dense_init(ks[2], (d_ff, d_model), d_ff, dtype)
        return p
    if sparse_down:
        raise ValueError("sparse_down supports the gated (silu/gelu_glu) "
                         f"MLP only, got activation={activation!r}")
    return {  # plain 2-layer (whisper-style GELU)
        "w_in": dense_init(ks[0], (d_model, d_ff), d_model, dtype),
        "b_in": jnp.zeros((d_ff,), dtype),
        "w_out": dense_init(ks[1], (d_ff, d_model), d_ff, dtype),
        "b_out": jnp.zeros((d_model,), dtype),
    }


def mlp(p, x, activation: str, *, sparse_plan=None):
    """MLP apply.  A ``BlockCSR`` down projection routes through
    ``sparse_linear`` (one batched Maple kernel launch, differentiable);
    ``sparse_plan`` is the prebuilt ``SpmmTrainPlan`` jitted train steps
    close over (without it the wrapper re-plans eagerly, or — with traced
    metadata, e.g. the decode path — falls back to the naive schedule).
    """
    if activation in ("silu", "gelu_glu"):
        act = jax.nn.silu if activation == "silu" else jax.nn.gelu
        h = act(jnp.einsum("bsd,df->bsf", x, p["w_gate"]))
        h = h * jnp.einsum("bsd,df->bsf", x, p["w_up"])
        h = shard(h, ("batch", "seq", "mlp"))
        if isinstance(p["w_down"], BlockCSR):
            return sparse_linear(p["w_down"], h, plan=sparse_plan)
        return jnp.einsum("bsf,fd->bsd", h, p["w_down"])
    h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["w_in"]) + p["b_in"])
    h = shard(h, ("batch", "seq", "mlp"))
    return jnp.einsum("bsf,fd->bsd", h, p["w_out"]) + p["b_out"]


# --------------------------------------------------------------------------
# block-sparse projections (the Maple kernel as a model layer)
# --------------------------------------------------------------------------

def init_sparse_linear(key, d_in: int, d_out: int, *,
                       block_shape=(64, 64), block_density: float = 0.25,
                       dtype=jnp.float32, mask_key=None) -> BlockCSR:
    """Block-sparse ``(d_out, d_in)`` projection weight as BlockCSR.

    Sparsity is sampled at block granularity — the unit the Maple kernels
    skip — and every block-row keeps at least one block so no output
    channel goes structurally dead.  BlockCSR is a pytree, so the weight
    drops into a params dict like any dense array, and ``maple_spmm``'s
    custom VJP makes it *trainable*: the payload gets gradients (sampled
    at the fixed pattern), the metadata gets float0.

    ``mask_key`` decouples the pattern from the value init: layers that
    share a ``mask_key`` share a block pattern (and therefore one
    ``SpmmTrainPlan``) while drawing independent values — what a scanned
    stack of sparse layers needs.
    """
    bm, bk = block_shape
    if d_out % bm or d_in % bk:
        raise ValueError(f"({d_out},{d_in}) not divisible by {block_shape}")
    gm, gk = d_out // bm, d_in // bk
    k_mask, k_val = jax.random.split(key)
    if mask_key is not None:
        k_mask = mask_key
    mask = jax.random.uniform(k_mask, (gm, gk)) < block_density
    fallback = jnp.zeros((gm, gk), bool).at[
        jnp.arange(gm), jnp.arange(gm) % gk].set(True)
    mask = jnp.where(mask.any(axis=1, keepdims=True), mask, fallback)
    fan_in = max(d_in * block_density, float(bk))   # expected live fan-in
    w = jax.random.normal(k_val, (d_out, d_in)) / math.sqrt(fan_in)
    dense = w * jnp.repeat(jnp.repeat(mask, bm, axis=0), bk, axis=1)
    return BlockCSR.from_dense(np.asarray(dense.astype(dtype)), block_shape)


def token_tiles(shape, bn: int = 128) -> tuple[bool, int]:
    """How :func:`sparse_linear` tiles the tokens of an input of ``shape``:
    ``(fold, tiles)``, the ``bn``-token tiles the kernel runs per weight
    block.

    A ``(B, S, d_in)`` input folds its batch into the token axis when that
    runs fewer tiles, ``B·⌈S/bn⌉ > ⌈B·S/bn⌉``: decode's ``(B, 1, d_in)``
    is then one tile, not ``B``.  Where both tile the same (``B = 1``, or
    ``S`` a multiple of ``bn``) each batch element stays its own
    right-hand side.  ``(T, d_in)`` and ``(d_in,)`` inputs are one
    right-hand side already and never fold.
    """
    lead = tuple(shape[:-1])
    tiles = -(-math.prod(lead) // bn)         # all tokens on one axis
    fold = len(lead) == 2 and lead[0] * -(-lead[1] // bn) > tiles
    return fold, tiles


def sparse_linear(w: BlockCSR, x, *, plan=None, bn: int = 128,
                  schedule: str = "balanced", interpret=None):
    """``y = x @ Wᵀ`` for block-sparse ``W`` in ONE batched kernel launch.

    ``x`` may be ``(d_in,)``, ``(T, d_in)`` or ``(B, S, d_in)``.  Tokens
    are moved token-minor so they become the PSB columns of the kernel: a
    3D ``x`` maps each batch element to one dense right-hand side of the
    batched grid — the host never loops over ``B`` (the seed kernels
    forced exactly that loop) — unless folding the batch into one
    ``(B·S)``-token right-hand side runs fewer ``bn``-token tiles
    (:func:`token_tiles`; decode's one token per sequence).  Each output
    column is the same block × column product either way.  Ragged token
    counts are fine; the wrapper pads to the ``bn`` tile and slices back.

    Pass ``plan`` (from ``repro.kernels.plan_spmm``, or ``plan_spmm_vjp``
    when gradients must flow under jit) to amortize schedule construction
    across calls — layers build it once per weight.  ``plan="auto"``
    autotunes eagerly instead (``kernels.autotune.plan_search``, memoized
    per sparsity pattern — repeat calls on a seen weight pattern reuse
    the cached winner; under jit prebuild with ``auto_plan`` and close
    over the result).  The call is
    differentiable w.r.t. both ``w``'s payload and ``x`` through
    ``maple_spmm``'s custom VJP (A^T pass + block SDDMM; see
    ``kernels/README.md``).

    Multi-device: a ``PartitionedSpmmPlan`` (``plan_partitioned_spmm``,
    or ``plan_spmm_vjp(..., n_shards=D)`` for training) runs the layer
    sharded over ``D`` devices — each device owns a slice of ``W``'s
    block-rows (= output features) under ``shard_map``.  Activations are
    replicated on the 1-D mesh; a plan built with ``n_col_shards=C > 1``
    instead panel-splits them along the token axis over a second
    ``"col"`` mesh axis (per-device activation bytes shrink ~``C``×, the
    output panels reassemble by placement, and the dA SDDMM backward
    partitions over the same 2-D mesh).  ``schedule="partitioned"`` does
    the same eagerly.
    """
    from repro.kernels import maple_spmm  # local: keep layers importable
    # without pulling pallas in for dense-only models
    d_out = w.shape[0]
    if x.ndim == 3 and not token_tiles(x.shape, bn)[0]:
        bt = jnp.swapaxes(x, 1, 2)                      # (B, d_in, S)
        y = maple_spmm(w, bt, bn=bn, plan=plan, schedule=schedule,
                       interpret=interpret)             # (B, d_out, S)
        return jnp.swapaxes(y, 1, 2)
    flat = x.reshape(-1, x.shape[-1])                   # (T, d_in)
    y = maple_spmm(w, flat.T, bn=bn, plan=plan, schedule=schedule,
                   interpret=interpret)                 # (d_out, T)
    return y.T.reshape(*x.shape[:-1], d_out)
