"""Unified model zoo: one functional LM covering all ten assigned archs.

A model is a stack of *blocks*; each block is a temporal mixer (global GQA
attention, local-window attention, RG-LRU, or Mamba-2 SSD) plus an optional
cross-attention (enc-dec) and an optional FFN (dense SwiGLU/GELU or MoE).
The per-layer kind sequence comes from ``cfg.pattern_unit`` repeated
``n_groups`` times plus a homogeneous ``tail`` — both executed with
``lax.scan`` over stacked parameters so the HLO is O(one group), which is
what keeps 80-94-layer configs lowerable in the 512-device dry-run.

Entry points:
  init_params / forward / loss_fn                  (training)
  init_decode_state / prefill / decode_step        (serving)
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from repro.models import layers as L
from repro.models import moe as M
from repro.models import rglru as R
from repro.models import ssm as S


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def _pick_chunk(n: int, target: int) -> int:
    """Largest divisor of n that is ≤ target (trace-time ints)."""
    c = min(n, target)
    while n % c:
        c -= 1
    return c


def _attn_cfg(cfg: ModelConfig, kind: str) -> L.AttnConfig:
    return L.AttnConfig(
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, qk_norm=cfg.qk_norm, qkv_bias=cfg.qkv_bias,
        rope_theta=cfg.rope_theta,
        causal=kind != "enc_attn",
        window=cfg.window if kind == "local_attn" else None,
        norm=cfg.norm,
    )


def _moe_cfg(cfg: ModelConfig) -> M.MoEConfig:
    return M.MoEConfig(
        d_model=cfg.d_model, n_experts=cfg.n_experts,
        n_experts_padded=cfg.n_experts_padded, top_k=cfg.top_k,
        d_expert=cfg.d_expert, capacity_factor=cfg.moe_capacity_factor,
        impl=cfg.moe_impl)


def _ssm_cfg(cfg: ModelConfig) -> S.SSMConfig:
    return S.SSMConfig(d_model=cfg.d_model, d_state=cfg.ssm_d_state,
                       headdim=cfg.ssm_headdim, chunk=cfg.ssm_chunk)


def _rglru_cfg(cfg: ModelConfig) -> R.RGLRUConfig:
    return R.RGLRUConfig(d_model=cfg.d_model, lru_width=cfg.lru_width)


def sinusoidal_positions(seq: int, dim: int) -> jax.Array:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, dim, 2, dtype=jnp.float32)
                  * (-math.log(10000.0) / dim))
    pe = jnp.zeros((seq, dim), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


# --------------------------------------------------------------------------
# block init
# --------------------------------------------------------------------------

def _init_block(key, cfg: ModelConfig, kind: str, *, cross: bool,
                dtype) -> Dict[str, Any]:
    ks = jax.random.split(key, 8)
    p: Dict[str, Any] = {"norm1": L.init_norm(ks[0], cfg.d_model, cfg.norm)}
    if kind in ("attn", "local_attn", "enc_attn"):
        p["attn"] = L.init_attention(ks[1], _attn_cfg(cfg, kind), dtype)
    elif kind == "rglru":
        p["rglru"] = R.init_rglru(ks[1], _rglru_cfg(cfg), dtype)
    elif kind == "ssm":
        p["ssm"] = S.init_ssm(ks[1], _ssm_cfg(cfg), dtype)
    else:
        raise ValueError(kind)
    if cross:
        p["cross_norm"] = L.init_norm(ks[2], cfg.d_model, cfg.norm)
        p["cross"] = L.init_attention(ks[3], _attn_cfg(cfg, "enc_attn"),
                                      dtype)
    if cfg.ffn_kind != "none" and kind != "ssm":
        p["norm2"] = L.init_norm(ks[4], cfg.d_model, cfg.norm)
        if cfg.ffn_kind == "moe":
            p["moe"] = M.init_moe(ks[5], _moe_cfg(cfg), dtype)
        else:
            # sparse_mlp: the block mask comes from the *config* seed, not
            # the per-layer key, so every layer of the scanned stack shares
            # one pattern (congruent stacked leaves, one SpmmTrainPlan)
            mask_key = (jax.random.PRNGKey(cfg.sparse_mask_seed)
                        if cfg.sparse_mlp else None)
            p["mlp"] = L.init_mlp(ks[5], cfg.d_model, cfg.d_ff,
                                  cfg.activation, dtype,
                                  sparse_down=cfg.sparse_mlp,
                                  sparse_block=cfg.sparse_block,
                                  sparse_density=cfg.sparse_density,
                                  mask_key=mask_key)
    return p


# --------------------------------------------------------------------------
# block apply (full sequence)
# --------------------------------------------------------------------------

def _apply_block(p, cfg: ModelConfig, kind: str, x, positions,
                 enc_kv=None, mlp_plan=None):
    h = L.apply_norm(x, p["norm1"], cfg.norm)
    if kind in ("attn", "local_attn", "enc_attn"):
        acfg = _attn_cfg(cfg, kind)
        sq = h.shape[1]
        h = L.attention(p["attn"], acfg, h, positions,
                        q_chunk=_pick_chunk(sq, 512),
                        kv_chunk=_pick_chunk(sq, 1024))
        h = _name_tp(h)
    elif kind == "rglru":
        h = R.rglru_block(p["rglru"], _rglru_cfg(cfg), h)
    elif kind == "ssm":
        h = S.ssm_block(p["ssm"], _ssm_cfg(cfg), h)
    x = x + h
    x = shard(x, ("batch", "seq", None))

    if "cross" in p and enc_kv is not None:
        h = L.apply_norm(x, p["cross_norm"], cfg.norm)
        h = L.cross_attention(p["cross"], _attn_cfg(cfg, "enc_attn"),
                              h, *enc_kv)
        x = x + h

    if "mlp" in p:
        h = L.apply_norm(x, p["norm2"], cfg.norm)
        x = x + _name_tp(L.mlp(p["mlp"], h, cfg.activation,
                               sparse_plan=mlp_plan))
    elif "moe" in p:
        h = L.apply_norm(x, p["norm2"], cfg.norm)
        x = x + _name_tp(M.moe_layer(p["moe"], _moe_cfg(cfg), h))
    return shard(x, ("batch", "seq", None))


def _name_tp(h):
    """Tag TP-projection outputs (post all-reduce) for the chunked-remat
    save policy: the inner recompute keeps them, so the backward does not
    re-run the forward all-reduces a third time (§Perf iteration 3)."""
    from jax.ad_checkpoint import checkpoint_name
    return checkpoint_name(h, "tp_proj_out")


# --------------------------------------------------------------------------
# parameter init
# --------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key, dtype=jnp.float32):
    unit, n_groups, tail = cfg.layer_plan()
    keys = jax.random.split(key, 8)

    def stack_blocks(key, kinds, count, cross):
        """init `count` copies of the kinds-unit, stacked on axis 0."""
        def one(k):
            sub = jax.random.split(k, len(kinds))
            return {f"b{i}": _init_block(sub[i], cfg, kind, cross=cross,
                                         dtype=dtype)
                    for i, kind in enumerate(kinds)}
        ks = jax.random.split(key, count)
        if cfg.sparse_mlp:
            # BlockCSR weights are assembled from host metadata, which a
            # vmapped trace cannot provide
            per = [one(k) for k in ks]
            return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per)
        # one traced layer instead of `count` unrolled copies: the stack is
        # built once, not held twice, and init compiles in O(1) layers
        return jax.vmap(one)(ks)

    cross = cfg.n_enc_layers > 0
    params: Dict[str, Any] = {
        "embed_tokens": L.dense_init(keys[0],
                                     (cfg.vocab_padded, cfg.d_model),
                                     cfg.d_model, dtype),
        "groups": stack_blocks(keys[1], unit, n_groups, cross),
        "final_norm": L.init_norm(keys[2], cfg.d_model, cfg.norm),
        "lm_head": L.dense_init(keys[3], (cfg.vocab_padded, cfg.d_model),
                                cfg.d_model, dtype),
    }
    if tail:
        # tail is a homogeneous run: stack `len(tail)` single-kind blocks
        params["tail"] = stack_blocks(keys[4], (tail[0],), len(tail), cross)
    if cfg.n_enc_layers > 0:
        params["encoder"] = {
            "groups": stack_blocks(keys[5], ("enc_attn",), cfg.n_enc_layers,
                                   False),
            "final_norm": L.init_norm(keys[6], cfg.d_model, cfg.norm),
        }
    if cfg.n_patches > 0:
        params["vis_proj"] = L.dense_init(
            keys[7], (cfg.d_model, cfg.d_model), cfg.d_model, dtype)
    return params


def sparse_mlp_plan(params, *, n_lanes: int = 8, chunk=None,
                    n_shards=None, n_col_shards=None,
                    autotune: bool = False):
    """Build the shared ``SpmmTrainPlan`` for a sparse-MLP model.

    Every sparse layer shares the mask (``cfg.sparse_mask_seed``), so one
    plan — built from layer 0 of the first stacked BlockCSR found in the
    param tree — schedules forward *and* backward for all of them.  Host
    metadata walk: call it once on concrete params (outside jit) and close
    the jitted train step over the result.  Returns ``None`` when the tree
    holds no sparse weight (dense configs pass through).

    ``n_shards > 1`` makes both sides mesh-partitioned (one shard of
    block-rows per device; the backward re-partitions on the transposed
    pattern) so the train step runs the sparse layers multi-device —
    pass ``len(jax.local_devices())`` to use every local device.
    ``n_col_shards > 1`` adds the second mesh axis: activations are
    panel-split along their N (token) dimension instead of replicated on
    every shard, and the dA SDDMM backward partitions over the same 2-D
    mesh (see ``kernels.partition``).

    ``autotune=True`` replaces the hand-tuned ``n_lanes``/``chunk`` with
    a budgeted ``kernels.autotune`` search over the mask's pattern
    (memoized per pattern, so re-deriving the plan for the same mask
    seed never re-searches); ``n_shards`` then bounds the searched
    device axis instead of pinning it.
    """
    from repro.core.csr import BlockCSR
    from repro.kernels.schedule import plan_spmm_vjp

    is_bcsr = lambda v: isinstance(v, BlockCSR)
    weights = [w for w in jax.tree_util.tree_leaves(params, is_leaf=is_bcsr)
               if is_bcsr(w)]
    if not weights:
        return None
    w = weights[0]
    if w.blocks.ndim == 4:          # stacked over layers: take layer 0
        w = jax.tree_util.tree_map(lambda a: a[0], w)
    if autotune:
        from repro.kernels.autotune import auto_plan
        return auto_plan(w, trainable=True, n_shards=n_shards,
                         n_col_shards=n_col_shards)
    return plan_spmm_vjp(w, n_lanes=n_lanes, chunk=chunk,
                         n_shards=n_shards, n_col_shards=n_col_shards)


# --------------------------------------------------------------------------
# forward (training / full-sequence)
# --------------------------------------------------------------------------

def _scan_stack(stack_params, kinds, cfg, x, positions, enc_kv, remat: bool,
                mlp_plan=None):
    def body(x, layer_p):
        for i, kind in enumerate(kinds):
            x = _apply_block(layer_p[f"b{i}"], cfg, kind, x, positions,
                             enc_kv, mlp_plan)
        return x, None

    if remat:
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, stack_params)
    return x


def _encode(params, cfg: ModelConfig, enc_frames, remat, mlp_plan=None):
    """Whisper-style encoder over precomputed (stub) frame embeddings."""
    x = enc_frames + sinusoidal_positions(
        enc_frames.shape[1], cfg.d_model).astype(enc_frames.dtype)
    positions = jnp.broadcast_to(
        jnp.arange(x.shape[1], dtype=jnp.int32), x.shape[:2])
    x = _scan_stack(params["encoder"]["groups"], ("enc_attn",), cfg, x,
                    positions, None, remat, mlp_plan)
    return L.apply_norm(x, params["encoder"]["final_norm"], cfg.norm)


def _embed_inputs(params, cfg: ModelConfig, batch):
    """tokens (+ optional vision prefix) → (B, S, D) and positions."""
    tok = batch["tokens"]
    x = params["embed_tokens"][tok]                        # (B, S_text, D)
    if cfg.n_patches > 0:
        vis = batch["vision_embeds"].astype(x.dtype)       # (B, P, D)
        vis = jnp.einsum("bpd,de->bpe", vis, params["vis_proj"])
        x = jnp.concatenate([vis, x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    return shard(x, ("batch", "seq", None)), positions


def forward(params, cfg: ModelConfig, batch, *, remat: bool = True,
            mlp_plan=None):
    """Full-sequence forward → logits (B, S, vocab_padded).

    ``mlp_plan`` — prebuilt ``SpmmTrainPlan`` for the shared sparse-MLP
    pattern (``sparse_mlp_plan``); a host object the scan bodies close
    over, required for the planned kernel path under jit (without it the
    sparse layers fall back to the naive traced schedule).
    """
    unit, n_groups, tail = cfg.layer_plan()
    x, positions = _embed_inputs(params, cfg, batch)

    enc_kv = None
    if cfg.n_enc_layers > 0:
        enc_out = _encode(params, cfg, batch["enc_frames"], remat, mlp_plan)
        # cross K/V are shared across decoder layers per-layer; each block
        # projects its own K/V from enc_out inside the scan (stacked wk/wv),
        # so pass enc_out and let blocks project.  To keep the scan carry
        # simple we precompute nothing here.
        enc_kv = enc_out

    def block_enc_kv(layer_p):
        if enc_kv is None:
            return None
        acfg = _attn_cfg(cfg, "enc_attn")
        return L.encode_kv(layer_p["cross"], acfg, enc_kv)

    def scan_with_cross(stack_params, kinds, x):
        def body(x, layer_p):
            for i, kind in enumerate(kinds):
                bp = layer_p[f"b{i}"]
                kv = block_enc_kv(bp) if "cross" in bp else None
                x = _apply_block(bp, cfg, kind, x, positions, kv, mlp_plan)
            return x, None

        n_groups_here = jax.tree_util.tree_leaves(stack_params)[0].shape[0]
        chunk = cfg.scan_remat_chunk
        if remat and chunk > 1 and n_groups_here % chunk == 0:
            # two-level (sqrt) remat: the outer scan saves only
            # n_groups/chunk carries; the inner chunk is recomputed inside
            # each outer backward step (DESIGN §6, activation-memory knob).
            # The inner recompute SAVES the TP projection outputs so the
            # forward all-reduces run 2×, not 3× (§Perf iteration 3).
            inner = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.save_only_these_names(
                    "tp_proj_out"))

            def outer(x, chunk_params):
                x, _ = jax.lax.scan(inner, x, chunk_params)
                return x, None

            outer = jax.checkpoint(
                outer, policy=jax.checkpoint_policies.nothing_saveable)
            reshaped = jax.tree_util.tree_map(
                lambda a: a.reshape(n_groups_here // chunk, chunk,
                                    *a.shape[1:]), stack_params)
            x, _ = jax.lax.scan(outer, x, reshaped)
            return x

        if remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
        x, _ = jax.lax.scan(body, x, stack_params)
        return x

    x = scan_with_cross(params["groups"], unit, x)
    if tail:
        x = scan_with_cross(params["tail"], (tail[0],), x)

    x = L.apply_norm(x, params["final_norm"], cfg.norm)
    logits = jnp.einsum("bsd,vd->bsv", x, params["lm_head"])
    return shard(logits, ("batch", "seq", "vocab"))


def loss_fn(params, cfg: ModelConfig, batch, *, remat: bool = True,
            mlp_plan=None):
    """Next-token cross-entropy (+z-loss), masked on labels < 0."""
    logits = forward(params, cfg, batch, remat=remat,
                     mlp_plan=mlp_plan).astype(jnp.float32)
    labels = batch["labels"]
    if cfg.n_patches > 0:  # vision prefix produces no loss positions
        pad = jnp.full((labels.shape[0], cfg.n_patches), -1, labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    mask = labels >= 0
    safe = jnp.where(mask, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * mask
    z_loss = 1e-4 * jnp.square(lse) * mask
    denom = jnp.maximum(mask.sum(), 1)
    loss = (nll + z_loss).sum() / denom
    return loss, {"loss": nll.sum() / denom,
                  "z_loss": z_loss.sum() / denom,
                  "tokens": mask.sum()}


# --------------------------------------------------------------------------
# serving: decode state, prefill, decode step
# --------------------------------------------------------------------------

def _init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_seq: int,
                      dtype, cross: bool):
    cache: Dict[str, Any] = {}
    if kind in ("attn", "local_attn"):
        window = cfg.window if kind == "local_attn" else None
        s = min(max_seq, window) if window else max_seq
        # local windows keep a rolling cache of `window`; global keeps all.
        cache["k"] = jnp.zeros((batch, s, cfg.n_kv_heads, cfg.head_dim),
                               dtype)
        cache["v"] = jnp.zeros((batch, s, cfg.n_kv_heads, cfg.head_dim),
                               dtype)
    elif kind == "rglru":
        conv, h = R.init_rglru_state(_rglru_cfg(cfg), batch, dtype)
        cache["conv"], cache["h"] = conv, h
    elif kind == "ssm":
        conv, st = S.init_ssm_state(_ssm_cfg(cfg), batch, dtype)
        cache["conv"], cache["state"] = conv, st
    if cross:
        cache["cross_k"] = jnp.zeros(
            (batch, cfg.enc_seq, cfg.n_kv_heads, cfg.head_dim), dtype)
        cache["cross_v"] = jnp.zeros_like(cache["cross_k"])
    return cache


def init_decode_state(cfg: ModelConfig, batch: int, max_seq: int,
                      dtype=jnp.float32):
    unit, n_groups, tail = cfg.layer_plan()
    cross = cfg.n_enc_layers > 0

    def stacked(kinds, count):
        def one():
            return {f"b{i}": _init_block_cache(cfg, k, batch, max_seq,
                                               dtype, cross)
                    for i, k in enumerate(kinds)}
        per = [one() for _ in range(count)]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per)

    state = {"groups": stacked(unit, n_groups), "pos": jnp.int32(0)}
    if tail:
        state["tail"] = stacked((tail[0],), len(tail))
    return state


def _apply_block_decode(p, cfg: ModelConfig, kind: str, x, cache, pos):
    new_cache = dict(cache)
    h = L.apply_norm(x, p["norm1"], cfg.norm)
    if kind in ("attn", "local_attn"):
        acfg = _attn_cfg(cfg, kind)
        # attention_decode handles both the global cache and the rolling
        # local-window cache (slots wrap when S_cache == window).
        h, nk, nv = L.attention_decode(p["attn"], acfg, h,
                                       cache["k"], cache["v"], pos)
        new_cache["k"], new_cache["v"] = nk, nv
    elif kind == "rglru":
        h, conv, hidden = R.rglru_decode_step(
            p["rglru"], _rglru_cfg(cfg), h, cache["conv"], cache["h"])
        new_cache["conv"], new_cache["h"] = conv, hidden
    elif kind == "ssm":
        h, conv, st = S.ssm_decode_step(
            p["ssm"], _ssm_cfg(cfg), h, cache["conv"], cache["state"])
        new_cache["conv"], new_cache["state"] = conv, st
    x = x + h

    if "cross" in p:
        h = L.apply_norm(x, p["cross_norm"], cfg.norm)
        h = L.cross_attention(p["cross"], _attn_cfg(cfg, "enc_attn"), h,
                              cache["cross_k"], cache["cross_v"])
        x = x + h

    if "mlp" in p:
        h = L.apply_norm(x, p["norm2"], cfg.norm)
        x = x + L.mlp(p["mlp"], h, cfg.activation)
    elif "moe" in p:
        h = L.apply_norm(x, p["norm2"], cfg.norm)
        x = x + M.moe_layer(p["moe"], _moe_cfg(cfg), h)
    return x, new_cache


def decode_step(params, cfg: ModelConfig, state, tokens, *,
                return_hidden: bool = False):
    """One decode step.  tokens: (B, 1) int32 → (logits, new_state).

    The stacked per-layer caches ride the scan CARRY with dynamic
    index/update (not xs/ys): XLA keeps carry DUS in place inside the
    while body, so the multi-GB KV cache is single-buffered (xs/ys would
    double-buffer it — measured ~2×5.4 GiB on qwen2-72b decode_32k).

    ``return_hidden=True`` returns the final-norm hidden state instead
    of logits (mirrors ``decode_step_paged`` — the serving engine's
    static fallback path scores it with an external ``SparseLogitHead``).
    """
    unit, n_groups, tail = cfg.layer_plan()
    pos = state["pos"]
    x = params["embed_tokens"][tokens]

    def scan_decode(stack_params, stack_cache, kinds, x):
        def body(carry, layer_p):
            x, cache_all, li = carry
            layer_c = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, li, 0,
                                                       keepdims=False),
                cache_all)
            new_c = {}
            for i, kind in enumerate(kinds):
                x, nc = _apply_block_decode(layer_p[f"b{i}"], cfg, kind, x,
                                            layer_c[f"b{i}"], pos)
                new_c[f"b{i}"] = nc
            cache_all = jax.tree_util.tree_map(
                lambda a, nc: jax.lax.dynamic_update_index_in_dim(
                    a, nc.astype(a.dtype), li, 0),
                cache_all, new_c)
            return (x, cache_all, li + 1), None
        (x, new_cache, _), _ = jax.lax.scan(
            body, (x, stack_cache, jnp.int32(0)), stack_params)
        return x, new_cache

    x, g_cache = scan_decode(params["groups"], state["groups"], unit, x)
    new_state = {"groups": g_cache, "pos": pos + 1}
    if tail:
        x, t_cache = scan_decode(params["tail"], state["tail"],
                                 (tail[0],), x)
        new_state["tail"] = t_cache

    x = L.apply_norm(x, params["final_norm"], cfg.norm)
    if return_hidden:
        return x, new_state
    logits = jnp.einsum("bsd,vd->bsv", x, params["lm_head"])
    return shard(logits, ("batch", None, "vocab")), new_state


# --------------------------------------------------------------------------
# serving: paged decode (continuous batching)
# --------------------------------------------------------------------------

def needs_kv_pages(cfg: ModelConfig) -> bool:
    """Does any layer keep a token-indexed KV history?  Pure-recurrent
    stacks (SSM / RG-LRU only) carry fixed-size state and need no pages."""
    return any(k in ("attn", "local_attn") for k in cfg.block_kinds())


def history_horizon(cfg: ModelConfig) -> Optional[int]:
    """How many past tokens any layer can still read.

    ``None`` → unbounded (some global-attention layer); otherwise the
    largest local window (0 for pure-recurrent stacks).  The serving
    engine frees KV pages that fall entirely behind this horizon, which
    is what bounds a local/recurrent config's per-slot memory by its
    window rather than its sequence length.
    """
    horizon = 0
    for k in cfg.block_kinds():
        if k == "attn":
            return None
        if k == "local_attn":
            horizon = max(horizon, cfg.window or 0)
    return horizon


def init_paged_state(cfg: ModelConfig, n_slots: int, n_pages: int,
                     page_size: int, max_pages: int, dtype=jnp.float32):
    """Decode state for the continuous-batching engine.

    Unlike ``init_decode_state`` — whose attention caches pin
    ``batch × max_seq`` memory per layer — the attention K/V here live in
    a *physical page pool* ``(n_pages, page_size, KVH, hd)`` shared by all
    ``n_slots`` batch slots through a per-slot block table
    ``(n_slots, max_pages)``; a slot's memory is the pages actually
    allocated to it.  Page 0 is the sacrificial dead page: free slots
    (table all-zero, pos 0) write their garbage token there, and reads of
    unallocated logical pages land there too (masked at -inf by position).
    Recurrent layers (RG-LRU / SSM conv+hidden) keep fixed-size per-slot
    state indexed by slot id — no paging, but they ride the same pytree
    and are reset by the engine's prefill-on-admit.  ``pos`` is per-slot
    (slots decode at different depths in one fused step).
    """
    if cfg.n_enc_layers > 0 or cfg.n_patches > 0:
        raise NotImplementedError(
            "paged decode supports decoder-only token models (enc-dec "
            "cross caches / vision prefixes still use the static path)")
    unit, n_groups, tail = cfg.layer_plan()

    def block_cache(kind: str) -> Dict[str, Any]:
        cache: Dict[str, Any] = {}
        if kind in ("attn", "local_attn"):
            cache["k"] = jnp.zeros(
                (n_pages, page_size, cfg.n_kv_heads, cfg.head_dim), dtype)
            cache["v"] = jnp.zeros_like(cache["k"])
        elif kind == "rglru":
            conv, h = R.init_rglru_state(_rglru_cfg(cfg), n_slots, dtype)
            cache["conv"], cache["h"] = conv, h
        elif kind == "ssm":
            conv, st = S.init_ssm_state(_ssm_cfg(cfg), n_slots, dtype)
            cache["conv"], cache["state"] = conv, st
        else:
            raise ValueError(kind)
        return cache

    def stacked(kinds, count):
        per = [{f"b{i}": block_cache(k) for i, k in enumerate(kinds)}
               for _ in range(count)]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per)

    state = {"groups": stacked(unit, n_groups),
             "table": jnp.zeros((n_slots, max_pages), jnp.int32),
             "pos": jnp.zeros((n_slots,), jnp.int32)}
    if tail:
        state["tail"] = stacked((tail[0],), len(tail))
    return state


def _apply_block_decode_paged(p, cfg: ModelConfig, kind: str, x, cache,
                              table, pos):
    new_cache = dict(cache)
    h = L.apply_norm(x, p["norm1"], cfg.norm)
    if kind in ("attn", "local_attn"):
        acfg = _attn_cfg(cfg, kind)
        h, nk, nv = L.attention_decode_paged(p["attn"], acfg, h,
                                             cache["k"], cache["v"],
                                             table, pos)
        new_cache["k"], new_cache["v"] = nk, nv
    elif kind == "rglru":
        h, conv, hidden = R.rglru_decode_step(
            p["rglru"], _rglru_cfg(cfg), h, cache["conv"], cache["h"])
        new_cache["conv"], new_cache["h"] = conv, hidden
    elif kind == "ssm":
        h, conv, st = S.ssm_decode_step(
            p["ssm"], _ssm_cfg(cfg), h, cache["conv"], cache["state"])
        new_cache["conv"], new_cache["state"] = conv, st
    x = x + h

    if "mlp" in p:
        h = L.apply_norm(x, p["norm2"], cfg.norm)
        x = x + L.mlp(p["mlp"], h, cfg.activation)
    elif "moe" in p:
        h = L.apply_norm(x, p["norm2"], cfg.norm)
        x = x + M.moe_layer(p["moe"], _moe_cfg(cfg), h)
    return x, new_cache


def decode_step_paged(params, cfg: ModelConfig, state, tokens, *,
                      return_hidden: bool = False):
    """One fused decode step over every engine slot, paged KV.

    tokens: (n_slots, 1) int32 — the pending token of each slot (free
    slots carry 0 and write into the dead page).  Mirrors ``decode_step``
    (same carry-DUS scan over the stacked layer caches) with two
    differences: positions are per-slot (``state["pos"]``), and attention
    layers read/write the shared page pool through ``state["table"]``.
    Returns ``(logits | hidden, new_state)``; ``return_hidden=True``
    skips the dense ``lm_head`` so a serving-side ``SparseLogitHead`` can
    score the hidden states instead (its execution plan depends only on
    the weight pattern, never on how many slots are live).
    """
    unit, n_groups, tail = cfg.layer_plan()
    table, pos = state["table"], state["pos"]
    x = params["embed_tokens"][tokens]

    def scan_decode(stack_params, stack_cache, kinds, x):
        def body(carry, layer_p):
            x, cache_all, li = carry
            layer_c = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, li, 0,
                                                       keepdims=False),
                cache_all)
            new_c = {}
            for i, kind in enumerate(kinds):
                x, nc = _apply_block_decode_paged(
                    layer_p[f"b{i}"], cfg, kind, x, layer_c[f"b{i}"],
                    table, pos)
                new_c[f"b{i}"] = nc
            cache_all = jax.tree_util.tree_map(
                lambda a, nc: jax.lax.dynamic_update_index_in_dim(
                    a, nc.astype(a.dtype), li, 0),
                cache_all, new_c)
            return (x, cache_all, li + 1), None
        (x, new_cache, _), _ = jax.lax.scan(
            body, (x, stack_cache, jnp.int32(0)), stack_params)
        return x, new_cache

    x, g_cache = scan_decode(params["groups"], state["groups"], unit, x)
    new_state = {"groups": g_cache, "table": table, "pos": pos + 1}
    if tail:
        x, t_cache = scan_decode(params["tail"], state["tail"],
                                 (tail[0],), x)
        new_state["tail"] = t_cache

    x = L.apply_norm(x, params["final_norm"], cfg.norm)
    if return_hidden:
        return x, new_state
    logits = jnp.einsum("bsd,vd->bsv", x, params["lm_head"])
    return shard(logits, ("batch", None, "vocab")), new_state


def _apply_block_prefill(p, cfg: ModelConfig, kind: str, x, positions,
                         enc_kv, max_seq: int, cache_dtype):
    """Full-sequence block that also emits its decode cache."""
    cache: Dict[str, Any] = {}
    h = L.apply_norm(x, p["norm1"], cfg.norm)
    if kind in ("attn", "local_attn"):
        acfg = _attn_cfg(cfg, kind)
        cache_len = (min(max_seq, cfg.window) if kind == "local_attn"
                     else max_seq)
        sq = h.shape[1]
        h, kc, vc = L.attention_prefill(
            p["attn"], acfg, h, positions, cache_len=cache_len,
            q_chunk=_pick_chunk(sq, 512), kv_chunk=_pick_chunk(sq, 1024))
        cache["k"] = kc.astype(cache_dtype)
        cache["v"] = vc.astype(cache_dtype)
    elif kind == "rglru":
        h, (conv, hid) = R.rglru_block(p["rglru"], _rglru_cfg(cfg), h,
                                       return_state=True)
        cache["conv"] = conv.astype(cache_dtype)
        cache["h"] = hid
    elif kind == "ssm":
        h, (conv, st) = S.ssm_block(p["ssm"], _ssm_cfg(cfg), h,
                                    return_state=True)
        cache["conv"] = conv.astype(cache_dtype)
        cache["state"] = st
    x = x + h

    if "cross" in p and enc_kv is not None:
        hh = L.apply_norm(x, p["cross_norm"], cfg.norm)
        acfg = _attn_cfg(cfg, "enc_attn")
        ck, cv = L.encode_kv(p["cross"], acfg, enc_kv)
        x = x + L.cross_attention(p["cross"], acfg, hh, ck, cv)
        cache["cross_k"] = ck.astype(cache_dtype)
        cache["cross_v"] = cv.astype(cache_dtype)

    if "mlp" in p:
        hh = L.apply_norm(x, p["norm2"], cfg.norm)
        x = x + L.mlp(p["mlp"], hh, cfg.activation)
    elif "moe" in p:
        hh = L.apply_norm(x, p["norm2"], cfg.norm)
        x = x + M.moe_layer(p["moe"], _moe_cfg(cfg), hh)
    return shard(x, ("batch", "seq", None)), cache


def prefill(params, cfg: ModelConfig, batch, *, max_seq: Optional[int] = None,
            cache_dtype=None, remat: bool = True,
            return_hidden: bool = False):
    """Process the prompt, return (last-token logits, decode state).

    The per-layer caches come out stacked (scan ys), matching
    ``init_decode_state`` layout, with ``pos`` set past the prompt.
    ``return_hidden=True`` returns the final-norm hidden state instead of
    logits (for serving with an external ``SparseLogitHead``).
    """
    unit, n_groups, tail = cfg.layer_plan()
    x, positions = _embed_inputs(params, cfg, batch)
    if max_seq is None:
        max_seq = x.shape[1]
    if cache_dtype is None:
        cache_dtype = x.dtype

    enc_kv = None
    if cfg.n_enc_layers > 0:
        enc_kv = _encode(params, cfg, batch["enc_frames"], remat)

    def scan_prefill(stack_params, kinds, x):
        def body(x, layer_p):
            caches = {}
            for i, kind in enumerate(kinds):
                x, c = _apply_block_prefill(
                    layer_p[f"b{i}"], cfg, kind, x, positions, enc_kv,
                    max_seq, cache_dtype)
                caches[f"b{i}"] = c
            return x, caches
        if remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
        return jax.lax.scan(body, x, stack_params)

    x, g_cache = scan_prefill(params["groups"], unit, x)
    state = {"groups": g_cache,
             "pos": jnp.asarray(x.shape[1], jnp.int32)}
    if tail:
        x, t_cache = scan_prefill(params["tail"], (tail[0],), x)
        state["tail"] = t_cache

    x = L.apply_norm(x[:, -1:], params["final_norm"], cfg.norm)
    if return_hidden:
        return x, state
    logits = jnp.einsum("bsd,vd->bsv", x, params["lm_head"])
    return shard(logits, ("batch", None, "vocab")), state


def prefill_cross_kv(params, cfg: ModelConfig, state, enc_frames,
                     remat: bool = False):
    """Run the encoder once and fill every decoder layer's cross K/V."""
    enc_out = _encode(params, cfg, enc_frames, remat)
    acfg = _attn_cfg(cfg, "enc_attn")

    def fill(stack_params, stack_cache):
        def body(_, inp):
            layer_p, layer_c = inp
            new_c = dict(layer_c)
            for key in layer_c:
                k, v = L.encode_kv(layer_p[key]["cross"], acfg, enc_out)
                blk = dict(layer_c[key])
                blk["cross_k"] = k.astype(blk["cross_k"].dtype)
                blk["cross_v"] = v.astype(blk["cross_v"].dtype)
                new_c[key] = blk
            return 0, new_c
        _, new_cache = jax.lax.scan(body, 0, (stack_params, stack_cache))
        return new_cache

    state = dict(state)
    state["groups"] = fill(params["groups"], state["groups"])
    if "tail" in state:
        state["tail"] = fill(params["tail"], state["tail"])
    return state
