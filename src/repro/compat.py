"""The few JAX mesh and ``shard_map`` calls this repo makes, in one place.

The repo targets the installed JAX (0.9): ``jax.shard_map`` with
``check_vma``, ``jax.make_mesh`` with explicit ``Auto`` axis types, and the
positional ``AbstractMesh(axis_sizes, axis_names)``.  Callers go through
these helpers so that a later API move touches one file.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True when the default JAX backend is TPU.

    The single home of the kernel packages' auto-dispatch check
    (``backend="auto"`` -> Pallas on TPU, jnp reference elsewhere).
    """
    return jax.default_backend() == "tpu"


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False,
              axis_names=None):
    """``jax.shard_map``; ``axis_names`` (the manual axes) defaults to all
    of the mesh's axes."""
    kwargs = {}
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma, **kwargs,
    )


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis of type ``Auto``.

    ``devices`` restricts the mesh to a subset (e.g. the first N of
    ``jax.devices()`` when a plan has fewer Legions than the host has
    devices).
    """
    kwargs = {}
    if devices is not None:
        kwargs["devices"] = tuple(devices)
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names), **kwargs,
    )


def abstract_mesh(axis_shapes, axis_names):
    """Device-free mesh for sharding-rule logic and tests."""
    return jax.sharding.AbstractMesh(tuple(axis_shapes), tuple(axis_names))
