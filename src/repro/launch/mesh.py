"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Single-pod: 16×16 = 256 chips, axes
("data", "model").  Multi-pod: 2×16×16 = 512 chips, axes
("pod", "data", "model") — the leading "pod" axis crosses the DCN.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices):
    """``jax.make_mesh`` with Auto axes.  The model code places activations
    with ``with_sharding_constraint`` and lets XLA propagate the rest, which
    only Auto axes accept; ``jax.make_mesh`` defaults to Explicit axes."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devices)} — "
            "run under launch/dryrun.py (it forces 512 host devices) or on "
            "real hardware")
    return _auto_mesh(shape, axes, devices[:need])


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Tiny mesh for unit tests (requires forced host device count)."""
    need = math.prod(shape)
    return _auto_mesh(shape, axes, jax.devices()[:need])
