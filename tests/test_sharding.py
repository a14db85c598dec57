"""Logical-axis sharding rules: divisibility fallback, param/state specs."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.distributed import sharding as sh
from repro.launch.mesh import make_debug_mesh

pytestmark = pytest.mark.tier1


@pytest.fixture(scope="module")
def mesh():
    # 1 real device: use a (1, 1) mesh — rule *selection* logic is
    # device-count independent (divisibility uses axis sizes).
    return make_debug_mesh((1, 1), ("data", "model"))


def mesh16():
    """Abstract 16×16 mesh for rule checks (no devices needed)."""
    return AbstractMesh((16, 16), ("data", "model"))


def test_divisibility_fallback():
    m = mesh16()
    # 28 heads do NOT divide 16 → replicated
    spec = sh.logical_spec(("embed", "heads", None), (3584, 28, 128), m)
    assert spec == P("data", None, None)
    # 32 heads divide 16 → sharded
    spec = sh.logical_spec(("embed", "heads", None), (4096, 32, 128), m)
    assert spec == P("data", "model", None)


def test_axis_used_once():
    m = mesh16()
    # both dims want "model": only the first gets it
    spec = sh.logical_spec(("heads", "mlp"), (32, 1024), m)
    assert spec == P("model", None)


def test_param_patterns():
    m = mesh16()
    assert sh.spec_for_param("groups/b0/attn/wq", (2, 4096, 32, 128), m) \
        == P(None, "data", "model", None)
    assert sh.spec_for_param("embed_tokens", (151936, 4096), m) \
        == P("model", "data")
    assert sh.spec_for_param("groups/b0/moe/experts_gate",
                             (2, 128, 4096, 1536), m) \
        == P(None, "model", "data", None)
    # norms replicated
    assert sh.spec_for_param("groups/b0/norm1/scale", (4096,), m) == P()
    # scalars replicated
    assert sh.spec_for_param("error/anything", (), m) == P()


def test_state_patterns():
    m = mesh16()
    assert sh.spec_for_state("groups/b0/k", (2, 128, 32768, 8, 128), m) \
        == P(None, "data", "model", None, None)
    assert sh.spec_for_state("groups/b0/state", (2, 128, 80, 64, 128), m) \
        == P(None, "data", "model", None, None)
    assert sh.spec_for_state("pos", (), m) == P()


def test_shard_noop_outside_context():
    x = jnp.ones((4, 4))
    assert sh.shard(x, ("batch", None)) is x


def test_shard_applies_constraint(mesh):
    with sh.use_mesh_rules(mesh):
        y = jax.jit(lambda x: sh.shard(x, ("batch", None)))(jnp.ones((4, 4)))
    assert y.shape == (4, 4)


def test_rank_mismatch_raises(mesh):
    with sh.use_mesh_rules(mesh):
        with pytest.raises(ValueError):
            sh.shard(jnp.ones((4, 4)), ("batch",))
