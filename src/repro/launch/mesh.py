"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state.  The dry-run entrypoint
(repro.launch.dryrun) sets XLA_FLAGS for 512 host devices BEFORE any jax
import; everything else sees the real device count.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 (256-chip pod) or 2x16x16 (2 pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(n_data: int = 2, n_model: int = 2) -> Mesh:
    """Small mesh over whatever devices exist (CPU tests)."""
    n = len(jax.devices())
    n_data = min(n_data, n)
    n_model = max(1, min(n_model, n // n_data))
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def dp_axes(mesh: Mesh):
    """The data-parallel axes of a mesh (includes 'pod' when present)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def fsdp_axes(mesh: Mesh, *, params_bytes: float = 0.0):
    """FSDP sharding axes: fold the pod axis in for very large models
    (>= 40 GB of parameters) so optimizer state fits per-device HBM."""
    if "pod" in mesh.axis_names and params_bytes >= 40e9:
        return ("pod", "data")
    return ("data",)
