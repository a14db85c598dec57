"""Mixture-of-Experts layer — the shardable jnp twin of the `moe_gemm`
Pallas engine (DESIGN §2-B/§5).

The dispatch is *sort-based* (no [T, E, C] one-hot einsums): top-k expert
assignments are flattened, stably sorted by expert, ranked within their
expert segment by position, capacity-clamped, scattered into per-expert
buffers, pushed through a batched expert GEMM (the row-panel multiply of the
Maple dataflow — expert id ≡ block col_id), and combined with a weighted
scatter-add (the PSB accumulate).  Every shape is static.

Sharding: expert buffers/weights carry the "experts" logical axis (→ mesh
`model`); token tensors carry "batch".  GSPMD turns the gather/scatter into
the EP all-to-all/all-gather pattern.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from repro.models.layers import dense_init


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    n_experts: int          # true expert count (router logits)
    n_experts_padded: int   # padded for EP divisibility (pads never routed)
    top_k: int
    d_expert: int           # per-expert FFN width
    capacity_factor: float = 1.25
    impl: str = "gspmd"     # "gspmd" | "ep_a2a" (shard_map all-to-all)


def init_moe(key, cfg: MoEConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    e, d, f = cfg.n_experts_padded, cfg.d_model, cfg.d_expert
    return {
        "router": dense_init(ks[0], (d, cfg.n_experts), d, jnp.float32),
        "experts_gate": dense_init(ks[1], (e, d, f), d, dtype),
        "experts_up": dense_init(ks[2], (e, d, f), d, dtype),
        "experts_down": dense_init(ks[3], (e, f, d), f, dtype),
    }


def _capacity(tokens: int, cfg: MoEConfig) -> int:
    cap = int(tokens * cfg.top_k * cfg.capacity_factor
              / cfg.n_experts_padded)
    return max(8, ((cap + 7) // 8) * 8)


def moe_layer(p, cfg: MoEConfig, x, *, return_aux: bool = False):
    """x: (B, S, D) → (B, S, D) (+ optional load-balancing aux loss).

    Dispatches to the shard_map expert-parallel path (explicit all-to-all,
    DESIGN §6 / EXPERIMENTS §Perf iteration 1) when configured and the mesh
    allows it; otherwise runs the GSPMD sort-based path below.
    """
    if cfg.impl == "ep_a2a" and not return_aux and _ep_applicable(cfg):
        return moe_layer_ep(p, cfg, x)
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    k = cfg.top_k
    e = cfg.n_experts_padded
    cap = _capacity(t, cfg)

    # ---- router (f32 for stable softmax) ----------------------------------
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                  # (T, E_true)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)          # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)              # renormalize

    # ---- sort-based dispatch ----------------------------------------------
    flat_e = expert_idx.reshape(-1).astype(jnp.int32)        # (T*k,)
    order = jnp.argsort(flat_e, stable=True)                 # (T*k,)
    sorted_e = shard(flat_e[order], ("batch",))
    # rank within expert segment = index - first index of that expert
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    rank = (jnp.arange(t * k, dtype=jnp.int32) - first.astype(jnp.int32))
    keep = rank < cap
    token_of_slot = shard((order // k).astype(jnp.int32), ("batch",))

    safe_e = shard(jnp.where(keep, sorted_e, 0), ("batch",))
    safe_r = shard(jnp.where(keep, rank, cap - 1), ("batch",))

    x_slot = jnp.where(keep[:, None], xt[token_of_slot], 0)  # (T*k, D)
    x_slot = shard(x_slot, ("batch", None))
    buf = jnp.zeros((e, cap, d), x.dtype).at[safe_e, safe_r].add(x_slot)
    buf = shard(buf, ("experts", None, None))

    # ---- expert compute (batched row-panel GEMM — the Maple multiply) -----
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["experts_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["experts_up"])
    h = shard(h, ("experts", None, None))
    y_e = jnp.einsum("ecf,efd->ecd", h, p["experts_down"])   # (E, C, D)

    # ---- combine (weighted scatter-add — the PSB accumulate) --------------
    y_slot = shard(y_e[safe_e, safe_r], ("batch", None))     # (T*k, D)
    gates_sorted = gate_vals.reshape(-1)[order]
    w = jnp.where(keep, gates_sorted, 0.0).astype(x.dtype)
    y = jnp.zeros((t, d), x.dtype).at[token_of_slot].add(y_slot * w[:, None])
    y = y.reshape(b, s, d)

    if not return_aux:
        return y
    # Switch-style load-balance loss over true experts
    me = probs.mean(axis=0)                                  # (E_true,)
    ce = jnp.zeros((cfg.n_experts,), jnp.float32).at[flat_e].add(
        1.0 / (t * k))
    aux = cfg.n_experts * jnp.sum(me * ce)
    return y, aux


# --------------------------------------------------------------------------
# expert-parallel path: shard_map + explicit all-to-all (the perf iteration)
# --------------------------------------------------------------------------
#
# Why: under pure GSPMD the sort-based dispatch's data-dependent gathers and
# scatters lower to full-buffer all-gathers + all-reduces (measured in the
# baseline dry-run: ~21 TB/device collective bytes for qwen3-moe train_4k).
# The fix is the classic EP schedule made explicit with shard_map:
#
#   tokens stay sharded over (pod, data); each `model`-column owns E/16
#   experts; per-destination capacity buffers ride ONE all_to_all over
#   `model` each way (bytes/device ≈ 2·T_loc·k·cf·D — orders of magnitude
#   below the GSPMD fallback), and every gather/scatter in between is local.
#
# The Maple mapping is unchanged — this is the same CSR-metadata walk, with
# the NoC hop made explicit (DESIGN §3.3: Extensor's multicast ≈ all_to_all).

from jax import shard_map  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.distributed.sharding import active_mesh  # noqa: E402


def _ep_applicable(cfg: MoEConfig) -> bool:
    mesh = active_mesh()
    if mesh is None or "model" not in mesh.shape:
        return False
    msize = mesh.shape["model"]
    return (cfg.n_experts_padded % msize == 0
            and cfg.d_model % mesh.shape.get("data", 1) == 0)


def _round8(n: int) -> int:
    return max(8, ((n + 7) // 8) * 8)


def moe_layer_ep(p, cfg: MoEConfig, x):
    """Expert-parallel MoE with explicit all-to-all dispatch/combine."""
    mesh = active_mesh()
    msize = mesh.shape["model"]
    batch_axes = tuple(ax for ax in ("pod", "data") if ax in mesh.shape)
    e_loc = cfg.n_experts_padded // msize
    b, s, d = x.shape
    k = cfg.top_k

    # greedily pick the largest batch-axis subset that divides b (e.g. a
    # 16-row microbatch on the 2×16×16 mesh shards over `data` only and
    # replicates over `pod` — matching DP semantics; full replication was
    # measured at 137 GiB/chip on qwen3-moe multi-pod train)
    candidates = [batch_axes]
    if len(batch_axes) > 1:
        candidates += [batch_axes[1:], batch_axes[:1]]
    candidates.append(())
    for cand in candidates:
        batch_div = 1
        for ax in cand:
            batch_div *= mesh.shape[ax]
        if b % batch_div == 0:
            batch_axes = cand
            break
    t_loc = (b // batch_div) * s
    cap_send = _round8(int(t_loc * k * cfg.capacity_factor / msize))
    cap_exp = _round8(int(msize * cap_send * 1.25 / e_loc))

    def inner(x_loc, router, wg, wu, wd):
        # FSDP: un-shard the expert weights' d_model dim over `data`
        if "data" in mesh.shape and wg.shape[1] != d:
            wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, "data", axis=1, tiled=True)
        if "data" in mesh.shape and wd.shape[2] != d:
            wd = jax.lax.all_gather(wd, "data", axis=2, tiled=True)

        bl = x_loc.shape[0]
        xt = x_loc.reshape(t_loc, d)

        # ---- local routing (replicated across the model axis) -------------
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

        flat_e = expert_idx.reshape(-1).astype(jnp.int32)      # (T_loc·k,)
        dest = flat_e // e_loc                                  # model peer
        order = jnp.argsort(dest, stable=True)
        sorted_dest = dest[order]
        first = jnp.searchsorted(sorted_dest, sorted_dest, side="left")
        rank = (jnp.arange(t_loc * k, dtype=jnp.int32)
                - first.astype(jnp.int32))
        keep = rank < cap_send
        tok = (order // k).astype(jnp.int32)
        safe_d = jnp.where(keep, sorted_dest, 0)
        safe_r = jnp.where(keep, rank, cap_send - 1)

        x_slot = jnp.where(keep[:, None], xt[tok], 0)
        x_send = jnp.zeros((msize, cap_send, d), x_loc.dtype
                           ).at[safe_d, safe_r].add(x_slot)
        eid_send = jnp.full((msize, cap_send), -1, jnp.int32
                            ).at[safe_d, safe_r].set(
            jnp.where(keep, flat_e[order] % e_loc, -1))

        # ---- ONE all_to_all each way over `model` --------------------------
        x_recv = jax.lax.all_to_all(x_send, "model", 0, 0, tiled=False)
        eid_recv = jax.lax.all_to_all(eid_send, "model", 0, 0, tiled=False)

        # ---- local grouped expert compute ----------------------------------
        xr = x_recv.reshape(msize * cap_send, d)
        er = eid_recv.reshape(msize * cap_send)
        valid = er >= 0
        er_sortkey = jnp.where(valid, er, e_loc)      # invalid sorts last
        order2 = jnp.argsort(er_sortkey, stable=True)
        se = er_sortkey[order2]
        first2 = jnp.searchsorted(se, se, side="left")
        rank2 = (jnp.arange(se.shape[0], dtype=jnp.int32)
                 - first2.astype(jnp.int32))
        keep2 = (se < e_loc) & (rank2 < cap_exp)
        safe_e2 = jnp.where(keep2, se, 0)
        safe_r2 = jnp.where(keep2, rank2, cap_exp - 1)

        x2 = jnp.where(keep2[:, None], xr[order2], 0)
        buf = jnp.zeros((e_loc, cap_exp, d), x_loc.dtype
                        ).at[safe_e2, safe_r2].add(x2)

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
        h = h * jnp.einsum("ecd,edf->ecf", buf, wu)
        y_buf = jnp.einsum("ecf,efd->ecd", h, wd)

        # undo the local grouping: slot i ← y_buf[e2(i), r2(i)]
        y_sorted = jnp.where(keep2[:, None], y_buf[safe_e2, safe_r2], 0)
        y_flat = jnp.zeros_like(y_sorted).at[order2].set(y_sorted)
        y_back = y_flat.reshape(msize, cap_send, d)

        y_recv = jax.lax.all_to_all(y_back, "model", 0, 0, tiled=False)

        # ---- combine (slots return to their (dest, rank) coordinates) -----
        y_slot = jnp.where(keep[:, None], y_recv[safe_d, safe_r], 0)
        gates = gate_vals.reshape(-1)[order].astype(x_loc.dtype)
        w = jnp.where(keep, gates, 0)
        y = jnp.zeros((t_loc, d), x_loc.dtype
                      ).at[tok].add(y_slot * w[:, None])
        return y.reshape(bl, s, d)

    bspec = (batch_axes if len(batch_axes) > 1
             else (batch_axes[0] if batch_axes else None))
    wg_spec = P("model", "data" if "data" in mesh.shape else None, None)
    wd_spec = P("model", None, "data" if "data" in mesh.shape else None)
    return shard_map(
        inner, mesh=mesh,
        in_specs=(P(bspec, None, None), P(), wg_spec, wg_spec, wd_spec),
        out_specs=P(bspec, None, None),
        check_vma=False,
    )(x, p["router"], p["experts_gate"], p["experts_up"],
      p["experts_down"])
