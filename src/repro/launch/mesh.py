"""Mesh construction for the production pods.

Everything is a FUNCTION — importing this module never touches jax device
state, so tests/benches that want a single CPU device can import it safely.

Production target: TPU v5e pods, 256 chips each, mesh (16 data, 16 model);
multi-pod doubles up with a leading "pod" axis used as a second data-
parallel axis (DP across DCN, TP kept inside the pod ICI domain).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

SINGLE_POD = (16, 16)
MULTI_POD = (2, 16, 16)


def make_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with Auto axes: ``shard_map`` and the sharding
    rules here place arrays themselves (``make_mesh`` defaults to
    Explicit axes)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests, examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, n // data)
    return make_mesh((data, model), ("data", "model"))


def solver_mesh(workers: int, model: int = 1) -> Mesh:
    """Mesh for the solver backend: 'data' = workers, 'model' = col shards."""
    return make_mesh((workers, model), ("data", "model"))


def solver_mesh_for(workers: int, model: int = 1) -> Mesh:
    """Largest solver mesh the available devices support.

    The 'data' axis is the largest divisor of ``workers`` that fits the
    device count (the backend shards the m worker blocks over it, so it
    must divide m) — on a single-device host this degrades to a (1, 1)
    mesh and the backend still runs, just unsharded.
    """
    budget = max(1, len(jax.devices()) // max(1, model))
    data = max(d for d in range(1, workers + 1)
               if workers % d == 0 and d <= budget)
    return make_mesh((data, model), ("data", "model"))
