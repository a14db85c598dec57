"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run overrides the
platform device count before first jax init and smoke tests must keep
seeing 1 device.

Topology: TPU v5e pods of 16×16 = 256 chips.  Single-pod mesh is
(data=16, model=16); multi-pod adds the leading `pod` axis (2 pods = 512
chips).  `pod` composes with `data` for gradient reduction by default, or
carries GPipe stages when pipeline mode is selected (DESIGN §6).
"""

from __future__ import annotations

import math

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devices)} — "
            "the dry-run entrypoint must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "any jax import")
    return _auto_mesh(shape, axes, devices[:need])


def make_debug_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh over however many devices exist (tests)."""
    need = math.prod(shape)
    return _auto_mesh(shape, axes, jax.devices()[:need])


def _auto_mesh(shape, axes, devices):
    # Auto axes: the model code places activations with
    # with_sharding_constraint hints, which Explicit axes (jax.make_mesh's
    # default) reject
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
