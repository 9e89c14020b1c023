"""Production mesh definitions (TPU v5e target).

Defined as FUNCTIONS so importing this module never touches jax device
state — the dry-run must set XLA_FLAGS before any jax initialisation.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# Hardware constants (TPU v5e), used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12       # per chip [FLOP/s]
HBM_BW = 819e9                 # per chip [B/s]
ICI_BW = 50e9                  # per link [B/s]
HBM_BYTES = 16 * 1024**3       # per chip
CHIPS_PER_POD = 256


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple, axes: tuple) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axes: the sharding rules place
    arrays with NamedShardings and constrain activations with bare
    PartitionSpecs, which Explicit axes (the jax default) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(n_data: int = 2, n_model: int = 2) -> jax.sharding.Mesh:
    """Small host-device mesh for sharding unit tests (needs
    XLA_FLAGS=--xla_force_host_platform_device_count >= n_data*n_model)."""
    return make_mesh((n_data, n_model), ("data", "model"))


def chips(mesh: jax.sharding.Mesh) -> int:
    return mesh.devices.size
