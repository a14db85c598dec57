"""MoE dispatch invariants (hypothesis): token conservation, capacity
discipline, gate normalization — on the GSPMD path (meshless)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.models import moe as M


def _cfg(e=8, k=2, cap=8.0):
    return M.MoEConfig(d_model=32, n_experts=e, n_experts_padded=e,
                       top_k=k, d_expert=16, capacity_factor=cap)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16), b=st.integers(1, 4), s=st.integers(2, 8))
def test_moe_linear_in_expert_outputs(seed, b, s):
    """Scaling all expert weights scales the output (router fixed)."""
    cfg = _cfg()
    key = jax.random.PRNGKey(seed)
    p = M.init_moe(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1), (b, s, 32))
    y1 = M.moe_layer(p, cfg, x)
    p2 = dict(p)
    p2["experts_down"] = p["experts_down"] * 2.0
    y2 = M.moe_layer(p2, cfg, x)
    np.testing.assert_allclose(np.asarray(y2), 2 * np.asarray(y1),
                               rtol=1e-5, atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_moe_zero_capacity_drops_everything(seed):
    """With capacity forced to the floor, outputs shrink (drops), never NaN."""
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 64, 32))
    big = _cfg(cap=16.0)
    tiny = dataclasses.replace(big, capacity_factor=0.01)
    p = M.init_moe(key, big)
    y_big = np.asarray(M.moe_layer(p, big, x))
    y_tiny = np.asarray(M.moe_layer(p, tiny, x))
    assert np.isfinite(y_big).all() and np.isfinite(y_tiny).all()
    assert np.linalg.norm(y_tiny) <= np.linalg.norm(y_big) + 1e-5


def test_moe_aux_loss_bounds():
    """Load-balance aux ≥ 1 with equality only at perfect balance."""
    cfg = _cfg(e=4, k=1)
    key = jax.random.PRNGKey(0)
    p = M.init_moe(key, cfg)
    x = jax.random.normal(key, (4, 64, 32))
    _, aux = M.moe_layer(p, cfg, x, return_aux=True)
    assert float(aux) >= 0.9  # ≈1 at near-uniform routing, larger if skewed


def test_padded_experts_never_routed():
    """Router logits exist only for true experts; pads get zero tokens."""
    cfg = M.MoEConfig(d_model=32, n_experts=5, n_experts_padded=8,
                      top_k=2, d_expert=16, capacity_factor=8.0)
    key = jax.random.PRNGKey(0)
    p = M.init_moe(key, cfg)
    # poison the padded experts: if anything routes there, outputs blow up
    poison = p["experts_down"].at[5:].set(1e6)
    p2 = dict(p, experts_down=poison)
    x = jax.random.normal(key, (2, 32, 32))
    y = np.asarray(M.moe_layer(p2, cfg, x))
    assert np.isfinite(y).all()
    assert np.abs(y).max() < 1e4
