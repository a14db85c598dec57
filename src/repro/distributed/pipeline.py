"""GPipe pipeline parallelism over the `pod` mesh axis (DESIGN §6).

Rationale: inter-pod ICI is the slow tier.  Data parallelism over `pod`
moves O(bytes(grads)) per step across pods; a pipeline moves
O(bytes(activations) × microbatches) — for large models (grads ≫
activations) the pipeline wins, and its sends overlap with compute.

Implementation: `shard_map` over `pod`; each stage owns `n_groups / P`
layer groups (the leading scan axis of the stacked params is split across
pods).  The GPipe schedule runs `M + P - 1` ticks of `lax.scan`; each tick
computes one microbatch on each busy stage and `ppermute`s the activation
ring forward.  The whole schedule is differentiable (scan + ppermute
transpose = reverse ring), so `jax.grad` through `pipeline_apply` yields
1F1B-equivalent math with GPipe scheduling.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, mesh: Mesh, n_microbatches: int,
                   params_stacked, x, *, pod_axis: str = "pod"):
    """Run x through all pipeline stages.

    stage_fn(stage_params, x_mb) → y_mb : applies this stage's layer groups
      (stage_params leaves have leading dim n_groups/P).
    params_stacked: leaves (n_groups, ...) — sharded over `pod` on axis 0.
    x: (batch, ...) with batch divisible by n_microbatches.

    Returns y with the same shape as x (pipeline output, from the last
    stage, re-broadcast over the pod axis so downstream DP code is
    unchanged).
    """
    n_pods = mesh.shape[pod_axis]
    m = n_microbatches
    if x.shape[0] % m:
        raise ValueError(f"batch {x.shape[0]} vs microbatches {m}")

    mb_shape = (m, x.shape[0] // m) + x.shape[1:]

    def inner(params_local, x_local):
        # x_local: full batch (replicated over pod); reshape to microbatches
        xs = x_local.reshape(mb_shape)
        p = jax.lax.axis_index(pod_axis)
        ticks = m + n_pods - 1

        buf = jnp.zeros_like(xs[0])          # activation entering this stage
        outs = jnp.zeros_like(xs)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (if still in range)
            inject = xs[jnp.clip(t, 0, m - 1)]
            x_in = jnp.where(p == 0, inject, buf)
            y = stage_fn(params_local, x_in)
            # last stage retires microbatch t-(P-1)
            out_idx = jnp.clip(t - (n_pods - 1), 0, m - 1)
            live = (t - (n_pods - 1) >= 0) & (p == n_pods - 1)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(live, y, outs[out_idx]), out_idx, axis=0)
            # ring forward p → p+1 (last stage's send is ignored)
            buf_next = jax.lax.ppermute(
                y, pod_axis,
                [(i, (i + 1) % n_pods) for i in range(n_pods)])
            return (buf_next, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # broadcast the last stage's outputs to every pod so the result is
        # replicated over `pod` (psum of one-hot contribution)
        contribution = jnp.where(p == n_pods - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(contribution, pod_axis)
        return outs.reshape(x_local.shape)

    other_axes = tuple(ax for ax in mesh.axis_names if ax != pod_axis)
    del other_axes  # x and params are replicated over non-pod axes here
    return shard_map(
        inner, mesh=mesh,
        in_specs=(P(pod_axis), P()),
        out_specs=P(),
        check_vma=False,
    )(params_stacked, x)


def stage_group_count(n_groups: int, n_pods: int) -> int:
    if n_groups % n_pods:
        raise ValueError(f"{n_groups} layer groups not divisible over "
                         f"{n_pods} pods")
    return n_groups // n_pods
