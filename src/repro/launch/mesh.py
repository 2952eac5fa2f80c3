"""Production mesh construction.

Defined as FUNCTIONS (not module constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, and smoke tests must keep seeing 1 device.

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
"pod" axis is pure DP over DCN; "data"/"model" stay intra-pod on ICI.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def make_mesh(shape, axes):
    """Mesh over the first prod(shape) devices, ordered for the physical
    topology by ``jax.make_mesh``.  Axes are Auto: GSPMD places what the
    programs leave unconstrained (U-mode), and shard_map bodies see
    local views (D-mode)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
