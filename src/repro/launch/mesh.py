"""Production mesh construction.

Kept as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* any jax
import to build these meshes on a CPU-only host.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_custom_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with ``Auto`` axes.  jax >= 0.7 defaults to
    ``Explicit`` axes, which ``with_sharding_constraint`` and the gathers
    of the sharding rules refuse; every mesh of this repo is built here.
    Also the elastic-scaling entry point: the runtime remesh planner
    (repro/runtime/health.py) picks a new factorization after failures and
    rebuilds the mesh here."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256-chip pod (data, model); 2x16x16 = 512-chip two-pod mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_custom_mesh(shape, axes)


def make_smoke_mesh():
    """1x1 mesh with the production axis names — lets every pjit code path
    run unchanged in single-device tests."""
    return make_custom_mesh((1, 1), ("data", "model"))
