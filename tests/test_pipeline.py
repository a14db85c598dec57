"""GPipe pipeline over the `pod` axis: forward equivalence vs sequential
execution and gradient flow.  Needs >1 device, so it runs in a subprocess
with a forced host-device count (the same mechanism as the dry-run)."""

import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.pipeline import pipeline_apply, stage_group_count
    from repro.launch.mesh import make_debug_mesh

    mesh = make_debug_mesh((4,), ("pod",))
    G, B, D = 8, 8, 16
    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (G, D, D)) * 0.1
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, D))

    def stage_fn(stage_ws, x):
        def body(h, w):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, stage_ws)
        return h

    # sequential reference: all G layers in order
    ref = stage_fn(ws, x)

    out = pipeline_apply(stage_fn, mesh, n_microbatches=4,
                         params_stacked=ws, x=x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    print("forward OK")

    # gradients flow through the schedule and match the sequential grads
    def loss_pipe(ws):
        return (pipeline_apply(stage_fn, mesh, 4, ws, x) ** 2).sum()
    def loss_seq(ws):
        return (stage_fn(ws, x) ** 2).sum()
    g1 = jax.grad(loss_pipe)(ws)
    g2 = jax.grad(loss_seq)(ws)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-5)
    print("backward OK")

    assert stage_group_count(8, 4) == 2
""")


def test_gpipe_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "forward OK" in proc.stdout
    assert "backward OK" in proc.stdout
