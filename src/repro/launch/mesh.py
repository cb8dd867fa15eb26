"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first init,
while smoke tests and benches see the real single device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """`jax.make_mesh` with Auto axes.  The sharding layer steers layouts
    with `with_sharding_constraint` and bare `PartitionSpec`s, which
    Explicit axes (`jax.make_mesh`'s default) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 (one v5e pod's worth of chips) or 2×16×16 (two pods).

    Axes: 'pod' is the DCN-connected outer data axis; 'data' hosts
    FSDP/EP/DP; 'model' hosts tensor parallelism over ICI.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_for_devices(n_devices: int, model_parallel: int = 1,
                          axes: tuple[str, str] = ("data", "model")):
    """Largest (data, model) grid for an elastic restart (repro.ft)."""
    model = min(model_parallel, n_devices)
    while n_devices % model:
        model -= 1
    return make_mesh((n_devices // model, model), axes)
