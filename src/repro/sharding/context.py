"""Ambient mesh context for model-internal sharding decisions.

Model code (shard_map EP-MoE, activation sharding constraints) needs the
mesh at trace time, but model functions are pure and config-driven. The
launcher / dry-run sets the ambient mesh here before tracing; model code
reads it. `None` (default, e.g. in CPU smoke tests) disables all
mesh-dependent paths.

`make_mesh` is the one place meshes are built: every axis is
`AxisType.Auto`, so sharding stays a compiler decision (GSPMD) rather
than part of every array's type — `jax.make_mesh` defaults to
`Explicit` axes, under which ops such as a dynamic-update-slice or an
embedding gather over a sharded operand raise `ShardingTypeError`.
"""
from __future__ import annotations

import contextlib

import jax
from jax.sharding import AxisType

_MESH = None


def make_mesh(shape, axes, *, devices=None):
    """`jax.make_mesh` with Auto axes over `devices` (default: all)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def set_mesh(mesh) -> None:
    global _MESH
    _MESH = mesh


def get_mesh():
    return _MESH


@contextlib.contextmanager
def use_mesh(mesh):
    global _MESH
    prev = _MESH
    _MESH = mesh
    try:
        yield
    finally:
        _MESH = prev
