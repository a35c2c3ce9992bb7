"""Production meshes. Functions, not constants — importing this module
never touches jax device state (device count is locked at first use)."""
from __future__ import annotations

import jax



def make_production_mesh(*, multi_pod: bool = False):
    """16×16 v5e pod (data, model); 2 pods add a leading "pod" axis (DP
    across the DCI — gradients cross pods once per step)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(n: int | None = None, name: str = "data"):
    """Small mesh over the actually-present devices (tests, examples)."""
    n = n or len(jax.devices())
    return jax.make_mesh((n,), (name,),
                         axis_types=(jax.sharding.AxisType.Auto,))
