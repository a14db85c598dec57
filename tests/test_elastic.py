"""Elastic restart: checkpoint written under one mesh restores onto a
different mesh size (reshard-on-load), continuing training losslessly.

Runs in a subprocess with 8 forced host devices: trains 2 steps on a
(4,2) mesh, checkpoints, restores onto (2,2) and (8,1) meshes, and checks the
continued training matches the uninterrupted run (tight tolerance — a
different mesh shape reorders the floating-point reductions, so exact
bit-equality only holds for same-shape restarts, covered in
test_checkpoint.py)."""

import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import tempfile
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_smoke_config
    from repro.data import DataConfig, synth_batch
    from repro.distributed.sharding import param_shardings, use_mesh_rules
    from repro.ft import checkpoint as ckpt
    from repro.launch.mesh import make_debug_mesh
    from repro.models import lm
    from repro.train import OptimizerConfig, init_opt_state, make_train_step

    cfg = get_smoke_config("qwen3-4b")
    ocfg = OptimizerConfig(peak_lr=1e-3, warmup_steps=1, total_steps=10)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=8)
    step_raw = make_train_step(cfg, ocfg, micro_batches=1)

    def run_steps(mesh, params, opt, steps, start):
        p_sh = param_shardings(params, mesh)
        with use_mesh_rules(mesh):
            fn = jax.jit(step_raw)
            params = jax.device_put(params, p_sh)
            opt_sh = param_shardings(opt, mesh)
            opt = jax.device_put(opt, opt_sh)
            for s in range(start, start + steps):
                params, opt, _ = fn(params, opt, synth_batch(dcfg, s))
        return params, opt

    mesh_a = make_debug_mesh((4, 2), ("data", "model"))
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(ocfg, params)

    # uninterrupted: 4 steps on mesh A
    p_ref, _ = run_steps(mesh_a, params, opt, 4, 0)
    ref = jax.device_get(p_ref)

    # interrupted: 2 steps on A -> checkpoint -> restore on B -> 2 more
    p2, o2 = run_steps(mesh_a, params, opt, 2, 0)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 2, {"params": jax.device_get(p2),
                         "opt": jax.device_get(o2)})
        for shape in ((2, 2), (8, 1)):
            mesh_b = make_debug_mesh(shape, ("data", "model"))
            like = {"params": params, "opt": opt}
            sh = {"params": param_shardings(params, mesh_b),
                  "opt": param_shardings(opt, mesh_b)}
            _, restored = ckpt.load(d, like, shardings=sh)
            p3, _ = run_steps(mesh_b, restored["params"],
                              restored["opt"], 2, 2)
            jax.tree_util.tree_map(
                lambda a, b: np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    rtol=2e-3, atol=1e-5),
                jax.device_get(p3), ref)
            print(f"elastic restart onto {shape}: equivalent")
""")


@pytest.mark.timeout(900)
def test_elastic_restart_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (proc.stderr[-3000:], proc.stdout[-500:])
    assert "elastic restart onto (2, 2): equivalent" in proc.stdout
    assert "elastic restart onto (8, 1): equivalent" in proc.stdout
