"""Mesh construction for the launchers.

Functions, not module-level constants — importing this module never
touches jax device state (required so smoke tests see 1 device while the
dry-run sees its 512 placeholder host devices).
"""
from __future__ import annotations

import jax

from repro.sharding.context import make_mesh

# the production pod slice's data axis: train shapes (configs/shapes.py)
# are sized for it, so one chip of the data axis owns 1/16 of a batch
PRODUCTION_DATA = 16


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (TPU v5e pod slice); 2 pods = 512 chips."""
    shape = (2, PRODUCTION_DATA, 16) if multi_pod else (PRODUCTION_DATA, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_device_mesh(devices=None):
    """(data, model) = (n, 1) over the chips present (`jax.devices()`
    by default): what the entry points run on outside the dry-run."""
    devices = list(devices if devices is not None else jax.devices())
    return make_mesh((len(devices), 1), ("data", "model"), devices=devices)
