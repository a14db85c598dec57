"""qwen3-4b: the benchmark's weights and its plain float32 reference.

Imports nothing of the program.  ``init_weights`` makes the weights from a
key, on the device, in the layout the program takes (a stacked layer
group ``groups.b0``); the reference reads the same arrays in float32.

The reference follows the Qwen3 decoder: RMSNorm before attention and MLP
(scale stored as ``1 + w``), GQA with RMSNorm on q and k over the head
dimension, rotate-half RoPE, causal softmax attention, SwiGLU MLP, a final
RMSNorm.  Departures from the published model, as run here: the output
head is a separate block-sparse matrix (the Maple SpMM) and not the tied
embedding.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def vocab_padded(spec) -> int:
    m = int(spec["vocab_pad_multiple"])
    return -(-int(spec["vocab_size"]) // m) * m


def model_config_kwargs(spec) -> dict:
    """The program's configuration fields for this file's sizes."""
    return dict(name=spec["name"], family="dense",
                n_layers=spec["num_hidden_layers"],
                d_model=spec["hidden_size"],
                n_heads=spec["num_attention_heads"],
                n_kv_heads=spec["num_key_value_heads"],
                head_dim=spec["head_dim"], d_ff=spec["intermediate_size"],
                vocab_size=spec["vocab_size"], qk_norm=True,
                rope_theta=float(spec["rope_theta"]),
                vocab_pad_multiple=spec["vocab_pad_multiple"])


def init_weights(spec, key, dtype=jnp.bfloat16):
    """Matrices ~ N(0, 1/fan_in) in ``dtype``; norm scales ``w`` ~
    N(0, 0.1^2) in float32, applied as ``1 + w``."""
    L, D = spec["num_hidden_layers"], spec["hidden_size"]
    H, KV = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd, F = spec["head_dim"], spec["intermediate_size"]
    ks = iter(jax.random.split(key, 16))

    def mat(shape, fan_in):
        return (jax.random.normal(next(ks), shape, F32)
                / math.sqrt(fan_in)).astype(dtype)

    def scale(shape):
        return 0.1 * jax.random.normal(next(ks), shape, F32)

    return {
        "embed_tokens": mat((vocab_padded(spec), D), D),
        "groups": {"b0": {
            "norm1": {"scale": scale((L, D))},
            "attn": {"wq": mat((L, D, H, hd), D),
                     "wk": mat((L, D, KV, hd), D),
                     "wv": mat((L, D, KV, hd), D),
                     "wo": mat((L, H, hd, D), H * hd),
                     "q_norm": {"scale": scale((L, hd))},
                     "k_norm": {"scale": scale((L, hd))}},
            "norm2": {"scale": scale((L, D))},
            "mlp": {"w_gate": mat((L, D, F), D), "w_up": mat((L, D, F), D),
                    "w_down": mat((L, F, D), F)}}},
        "final_norm": {"scale": scale((D,))},
    }


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, theta):
    """x: (B, S, heads, hd) at positions 0..S-1, rotate-half layout."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs          # (S, hd/2)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _upcast(cast):
    """float32 per array; with ``cast`` (the control) each matrix is then
    passed through it, one layer's matrices at a time."""
    def up(a):
        a = a.astype(F32)
        return cast(a) if cast is not None and a.ndim >= 2 else a
    return up


def hidden_states(weights, spec, tokens, cast=None):
    """Final-normed hidden states (B, S, D) in float32 for token ids
    (B, S), causal over each row; layer by layer, so one layer's float32
    weights are live at a time."""
    eps = float(spec["rms_norm_eps"])
    theta = float(spec["rope_theta"])
    H, KV = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd = spec["head_dim"]
    with jax.default_matmul_precision("highest"):
        up = _upcast(cast)
        x = up(weights["embed_tokens"][tokens])
        s = tokens.shape[1]
        causal = jnp.tril(jnp.ones((s, s), bool))

        def layer(x, p):
            p = jax.tree_util.tree_map(up, p["b0"])
            a = p["attn"]
            h = _rms(x, p["norm1"]["scale"], eps)
            q = jnp.einsum("bsd,dhk->bshk", h, a["wq"])
            k = jnp.einsum("bsd,dhk->bshk", h, a["wk"])
            v = jnp.einsum("bsd,dhk->bshk", h, a["wv"])
            q = _rope(_rms(q, a["q_norm"]["scale"], eps), theta)
            k = _rope(_rms(k, a["k_norm"]["scale"], eps), theta)
            k = jnp.repeat(k, H // KV, axis=2)
            v = jnp.repeat(v, H // KV, axis=2)
            sc = jnp.einsum("bqhk,bthk->bhqt", q, k) / math.sqrt(hd)
            sc = jnp.where(causal, sc, -jnp.inf)
            o = jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(sc, -1), v)
            x = x + jnp.einsum("bshk,hkd->bsd", o, a["wo"])
            h = _rms(x, p["norm2"]["scale"], eps)
            m = p["mlp"]
            g = jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])
            return x + g @ m["w_down"], None

        x, _ = jax.lax.scan(layer, x, weights["groups"])
        return _rms(x, weights["final_norm"]["scale"].astype(F32), eps)

