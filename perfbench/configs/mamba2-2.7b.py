"""mamba2-2.7b: the benchmark's weights and its plain float32 reference.

Imports nothing of the program.  ``init_weights`` makes the weights from a
key, on the device, in the layout the program takes (a stacked layer
group ``groups.b0.ssm``); the reference reads the same arrays in float32.

The reference is the Mamba-2 block as a sequential recurrence, one token
at a time (not the chunked SSD algorithm): RMSNorm, in_proj to
``[z, x, B, C, dt]``, a depthwise causal conv of width ``d_conv`` over
``[x, B, C]``, SiLU, ``dt = softplus(dt + dt_bias)``,
``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``,
``y * silu(z)``, a gated RMSNorm over ``d_inner`` (one group), out_proj,
residual.  Departures from the published model, as run here: no conv
bias, the residual stream in bfloat16 (``residual_in_fp32`` false), RMSNorm
epsilon 1e-6, and a separate block-sparse output head (not tied).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def vocab_padded(spec) -> int:
    m = int(spec["vocab_pad_multiple"])
    return -(-int(spec["vocab_size"]) // m) * m


def _sizes(spec):
    d = spec["d_model"]
    di = spec["expand"] * d
    n = spec["d_state"]
    return d, di, n, di // spec["headdim"], spec["headdim"]


def model_config_kwargs(spec) -> dict:
    return dict(name=spec["name"], family="ssm", n_layers=spec["n_layer"],
                d_model=spec["d_model"], n_heads=0, n_kv_heads=0,
                head_dim=0, d_ff=0, vocab_size=spec["vocab_size"],
                pattern_unit=("ssm",), ssm_d_state=spec["d_state"],
                ssm_headdim=spec["headdim"], ssm_chunk=spec["chunk_size"],
                vocab_pad_multiple=spec["vocab_pad_multiple"])


def init_weights(spec, key, dtype=jnp.bfloat16):
    """Matrices ~ N(0, 1/fan_in) in ``dtype``; Mamba-2's own init for the
    recurrence: ``A = -U(1, 16)``, ``dt`` ~ log-uniform on [1e-3, 1e-1]
    through ``dt_bias``, ``D`` ~ U(0.5, 1.5); norm scales ``w`` ~
    N(0, 0.1^2), applied as ``1 + w``."""
    L = spec["n_layer"]
    d, di, n, h, _ = _sizes(spec)
    w = spec["d_conv"]
    ks = iter(jax.random.split(key, 16))

    def mat(shape, fan_in):
        return (jax.random.normal(next(ks), shape, F32)
                / math.sqrt(fan_in)).astype(dtype)

    def scale(shape):
        return 0.1 * jax.random.normal(next(ks), shape, F32)

    dt = jnp.exp(jax.random.uniform(next(ks), (L, h), F32,
                                    math.log(1e-3), math.log(1e-1)))
    return {
        "embed_tokens": mat((vocab_padded(spec), d), d),
        "groups": {"b0": {
            "norm1": {"scale": scale((L, d))},
            "ssm": {
                "in_proj": mat((L, d, 2 * di + 2 * n + h), d),
                "conv": mat((L, w, di + 2 * n), w),
                "a_log": jnp.log(jax.random.uniform(next(ks), (L, h), F32,
                                                    1.0, 16.0)),
                "d_skip": jax.random.uniform(next(ks), (L, h), F32, 0.5,
                                             1.5),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1
                "norm": {"scale": scale((L, di))},
                "out_proj": mat((L, di, d), di)}}},
        "final_norm": {"scale": scale((d,))},
    }


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _upcast(cast):
    """float32 per array; with ``cast`` (the control) each matrix is then
    passed through it, one layer's matrices at a time."""
    def up(a):
        a = a.astype(F32)
        return cast(a) if cast is not None and a.ndim >= 2 else a
    return up


def hidden_states(weights, spec, tokens, cast=None):
    """Final-normed hidden states (B, S, D) in float32 for token ids
    (B, S); layer by layer, each layer a scan over the tokens."""
    eps = float(spec["norm_epsilon"])
    d, di, n, h, pd = _sizes(spec)
    width = spec["d_conv"]
    with jax.default_matmul_precision("highest"):
        up = _upcast(cast)
        x = up(weights["embed_tokens"][tokens])
        b, s, _ = x.shape

        def layer(x, p):
            p = jax.tree_util.tree_map(up, p["b0"])
            m = p["ssm"]
            zxbcdt = _rms(x, p["norm1"]["scale"], eps) @ m["in_proj"]
            z = zxbcdt[..., :di]
            xbc = zxbcdt[..., di:2 * di + 2 * n]
            dt = zxbcdt[..., 2 * di + 2 * n:]
            xp = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
            xbc = sum(xp[:, i:i + s] * m["conv"][i] for i in range(width))
            xbc = jax.nn.silu(xbc)
            xs = xbc[..., :di].reshape(b, s, h, pd)
            bm, cm = xbc[..., di:di + n], xbc[..., di + n:]
            dt = jax.nn.softplus(dt + m["dt_bias"])            # (B, S, H)
            a = -jnp.exp(m["a_log"])                           # (H,)

            def tok(state, inp):
                x_t, dt_t, b_t, c_t = inp
                state = (state * jnp.exp(dt_t * a)[..., None, None]
                         + jnp.einsum("bh,bhp,bn->bhpn", dt_t, x_t, b_t))
                y = jnp.einsum("bhpn,bn->bhp", state, c_t) \
                    + m["d_skip"][:, None] * x_t
                return state, y

            seq = tuple(jnp.moveaxis(v, 1, 0) for v in (xs, dt, bm, cm))
            _, y = jax.lax.scan(tok, jnp.zeros((b, h, pd, n), F32), seq)
            y = jnp.moveaxis(y, 0, 1).reshape(b, s, di) * jax.nn.silu(z)
            y = _rms(y, m["norm"]["scale"], eps)
            return x + y @ m["out_proj"], None

        x, _ = jax.lax.scan(layer, x, weights["groups"])
        return _rms(x, weights["final_norm"]["scale"].astype(F32), eps)

