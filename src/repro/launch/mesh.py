"""Production + serving meshes.

Defined as FUNCTIONS (not module constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before the first jax initialization.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np

__all__ = ["make_production_mesh", "make_local_mesh", "make_serve_mesh",
           "parse_mesh_spec", "mesh_axes", "chips"]


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds the 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh():
    """Single-process mesh over whatever devices exist (tests, examples)."""
    return _make_mesh((len(jax.devices()), 1), ("data", "model"))


def parse_mesh_spec(spec: str) -> Tuple[int, int]:
    """'8x1' -> (data=8, model=1) (the serve-CLI ``--mesh`` format)."""
    try:
        d, m = (int(p) for p in spec.lower().split("x"))
    except ValueError:
        raise ValueError(f"mesh spec must be DATAxMODEL (e.g. '8x1'), "
                         f"got {spec!r}") from None
    if d < 1 or m < 1:
        raise ValueError(f"mesh axes must be >= 1, got {spec!r}")
    return d, m


def make_serve_mesh(data: Optional[int] = None, model: int = 1):
    """(data, model) serving mesh over the first ``data * model`` local
    devices (default: all of them data-parallel).

    This is the multi-device serving topology: batch shards over
    'data', packed inner weights optionally tensor-shard over 'model'
    (SERVE_RULES), and with ``--xla_force_host_platform_device_count=N``
    the same mesh drives N placeholder CPU devices for tests/benches.
    """
    n_avail = len(jax.devices())
    if data is None:
        data = n_avail // model
    if data < 1:
        raise ValueError(
            f"model axis {model} exceeds the {n_avail} available devices "
            f"(a {0}x{model} mesh has no data shards)")
    need = data * model
    if need > n_avail:
        raise ValueError(
            f"serve mesh {data}x{model} needs {need} devices, "
            f"have {n_avail} (force more with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    devices = np.asarray(jax.devices()[:need]).reshape(data, model)
    return jax.sharding.Mesh(devices, ("data", "model"))


def mesh_axes(mesh) -> tuple:
    return tuple((name, size) for name, size in
                 zip(mesh.axis_names, mesh.devices.shape))


def chips(mesh) -> int:
    return mesh.devices.size
