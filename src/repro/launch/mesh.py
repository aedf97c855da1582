"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before any jax
initialization. Every axis is explicitly ``AxisType.Auto``; activate a mesh
for jitted computations with ``jax.set_mesh``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _auto_kwargs(n_axes: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_auto_kwargs(len(axes)))


def make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(tuple(shape), tuple(axes),
                         **_auto_kwargs(len(axes)))


def mesh_from_devices(devices, axes=("seq",)) -> Mesh:
    """Mesh over an EXPLICIT 1-D device list (``jax.make_mesh`` always
    starts from device 0 — the serving engine's main mesh must instead
    claim specific devices so offload shards can round-robin over the
    rest)."""
    import numpy as np

    return Mesh(np.array(devices), tuple(axes), **_auto_kwargs(len(axes)))


def split_mesh_roles(mesh: Mesh, prefill_fraction: float = 0.5):
    """Prefill/decode disaggregation (paper Fig. 6b): partition the data axis
    into a prefill submesh and a decode submesh. Returns (prefill, decode)."""
    devices = mesh.devices  # [..., data, model]
    n_data = mesh.shape["data"]
    cut = max(1, int(n_data * prefill_fraction))
    axes = mesh.axis_names
    d_idx = axes.index("data")
    sl_pre = [slice(None)] * devices.ndim
    sl_dec = [slice(None)] * devices.ndim
    sl_pre[d_idx] = slice(0, cut)
    sl_dec[d_idx] = slice(cut, n_data)
    pre = Mesh(devices[tuple(sl_pre)], axes, **_auto_kwargs(len(axes)))
    dec = Mesh(devices[tuple(sl_dec)], axes, **_auto_kwargs(len(axes)))
    return pre, dec
