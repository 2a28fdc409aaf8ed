"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then calls ``make_production_mesh``.

Axes:
  pod    — data parallelism across pods (pure DP; also hosts the optional
           pipeline driver in dist/pipeline.py)
  data   — data parallelism within a pod (+ FSDP param sharding)
  model  — tensor/sequence/expert parallelism within a pod row
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def mesh_shape_dict(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def make_mesh_info(mesh, *, fsdp: bool = False, attn_impl: str = "xla",
                   fsdp_resident: bool = False):
    from ..models.layers import MeshInfo
    d = mesh_shape_dict(mesh)
    return MeshInfo(tp=d.get("model", 1), dp=d.get("data", 1),
                    pods=d.get("pod", 1), fsdp=fsdp,
                    fsdp_resident=fsdp_resident, attn_impl=attn_impl)
