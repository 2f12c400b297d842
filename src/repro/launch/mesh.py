"""Production meshes.

Defined as a FUNCTION so importing this module never touches jax device
state (jax locks the device count on first backend init — the dry-run must
set XLA_FLAGS before anything calls this).

  single-pod:  (16, 16)      axes ("data", "model")          — 256 chips
  multi-pod:   (2, 16, 16)   axes ("pod", "data", "model")   — 512 chips

The ``pod`` axis is pure data parallelism: the only cross-pod collective is
the per-step gradient all-reduce, which is what survives a DCN hop at
1000+ node scale.  ``model`` carries TP / EP / vocab / embedding-row
parallelism and stays inside the pod's ICI domain.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding-in-types
    default (``Explicit``) rejects the reshapes of shard_map outputs that
    the serving and training code performs."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model: int | None = None, devices=None):
    """Host-sized mesh with the production axis names.  ``model`` widens
    the model axis over ``devices`` (default: the first ``model`` of
    ``jax.devices()``) — e.g. the corpus shards four ways on a four-chip
    host, or on the CPU under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``; the default
    is the 1-device (1, 1) mesh."""
    model = model or 1
    if devices is None:
        devices = jax.devices()[:model]
    if len(devices) != model:
        raise ValueError(f"a {model}-wide model axis needs {model} devices, "
                         f"got {len(devices)}")
    return _mesh((1, model), ("data", "model"), devices)
