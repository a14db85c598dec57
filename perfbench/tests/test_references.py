"""Both plain references against the program's own forward at smoke size,
in float32: the reference imports nothing of the program, so agreement
here is two independent readings of one architecture."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import lm

import smoke


@pytest.mark.parametrize("workload", ["qwen3-4b.chat",
                                      "mamba2-2.7b.chat-burst"])
def test_reference_matches_program_forward(workload):
    cell = smoke.cell(workload)
    spec, ref = cell.config, cell.reference
    cfg = ModelConfig(**ref.model_config_kwargs(spec))
    k_w, k_h, k_t = jax.random.split(jax.random.PRNGKey(0), 3)
    weights = ref.init_weights(spec, k_w, jnp.float32)
    head = jax.random.normal(k_h, (cfg.vocab_padded, cfg.d_model)) * 0.1
    tokens = jax.random.randint(k_t, (2, 64), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got = lm.forward(dict(weights, lm_head=head), cfg,
                         {"tokens": tokens}, remat=False)
        hid = ref.hidden_states(weights, spec, tokens)
        want = jnp.einsum("bsd,vd->bsv", hid, head)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err < 2e-4, err


def test_reference_is_causal():
    cell = smoke.cell("qwen3-4b.chat")
    spec, ref = cell.config, cell.reference
    w = ref.init_weights(spec, jax.random.PRNGKey(1), jnp.float32)
    t = np.random.default_rng(0).integers(0, 900, (1, 48))
    t2 = t.copy()
    t2[0, 40:] = 7
    a = ref.hidden_states(w, spec, jnp.asarray(t))
    b = ref.hidden_states(w, spec, jnp.asarray(t2))
    np.testing.assert_allclose(a[0, :40], b[0, :40], rtol=1e-5, atol=1e-5)
