"""Production mesh construction (DESIGN §5, assignment spec).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run sets the 512-device XLA flag before
any jax import; everything else sees the real topology).
"""
from __future__ import annotations


import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) = 256 chips/pod ("data", "model"); multi-pod adds the
    leading ("pod",) axis: (2, 16, 16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_elastic_mesh(data: int, model: int = 16, pod: int = 1):
    """Degraded-operation meshes after failures: whole TP groups only
    (shrink 'data'; 'model' stays intact — see ft/elastic.py)."""
    shape = (pod, data, model) if pod > 1 else (data, model)
    axes = (("pod", "data", "model") if pod > 1 else ("data", "model"))
    return _make_mesh(shape, axes)


def mesh_context(mesh):
    """Context manager that makes ``mesh`` the current mesh."""
    return jax.set_mesh(mesh)


def make_host_mesh(model: int = 1):
    """Mesh over whatever devices exist (tests / CPU smoke)."""
    n = len(jax.devices())
    data = max(1, n // model)
    return _make_mesh((data, model), ("data", "model"))
