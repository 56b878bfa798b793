"""Mesh construction.

Functions (not module-level constants) so importing this module never
touches jax device state.  Production target: TPU v5e pods — a 16x16
(256-chip) pod with axes (data, model), or 2 pods = 512 chips with a
leading `pod` axis that composes with `data` for cross-pod data parallelism
(gradient all-reduce crosses the pod axis; model parallelism never does).

Every mesh is built by ``make_mesh`` with ``Auto`` axes: the model code
places activations with ``with_sharding_constraint`` (``sharding.rules``),
which only accepts ``Auto`` mesh axes.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """A mesh of ``shape`` over ``devices`` (default: all) whose axes are
    all ``AxisType.Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist, as a 1D (data,) mesh — CPU smoke runs."""
    return make_mesh((len(jax.devices()),), ("data",))


def make_tp_mesh(tp: int):
    """First ``tp`` local devices as a 1D (tp,) tensor-parallel mesh.

    The serving engine shards heads/FFN over this axis (rules.TP_SERVE_RULES)
    while slot state stays replicated.  On CPU, force a multi-device host
    with XLA_FLAGS=--xla_force_host_platform_device_count=N before importing
    jax.
    """
    devices = jax.devices()
    if tp < 1:
        raise ValueError(f"--tp must be >= 1, got {tp}")
    if tp > len(devices):
        raise ValueError(
            f"--tp {tp} exceeds the {len(devices)} visible device(s); on CPU "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=N")
    return make_mesh((tp,), ("tp",), devices=devices[:tp])
