"""Mesh construction for single-pod / multi-pod deployments.

``make_production_mesh`` is the contract required by the dry-run: a
function (never a module-level constant — importing this module must not
touch jax device state).

Production target: TPU v5e pods, 256 chips each (16×16), ICI ~50 GB/s/link,
197 bf16 TFLOP/s + 16 GB HBM @ 819 GB/s per chip.  The ``pod`` axis of the
multi-pod mesh is pure data parallelism over DCN (gradient all-reduce
crosses pods once per step); ``data`` is in-pod data parallel; ``model`` is
the tensor/expert-parallel axis kept inside an ICI-adjacent 16-chip ring.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis of type Auto (GSPMD sharding)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_elastic_mesh(n_devices: int, *, model: int = 16):
    """Best mesh for an arbitrary surviving-device count (elastic restart).

    Keeps the model axis at the largest power-of-two divisor ≤ ``model`` so
    TP weight shards stay ICI-local; the rest becomes data parallelism.
    """
    m = model
    while m > 1 and n_devices % m:
        m //= 2
    return make_mesh((n_devices // m, m), ("data", "model"))


def make_host_mesh():
    """Whatever this process actually has (tests / examples)."""
    n = len(jax.devices())
    return make_elastic_mesh(n, model=min(4, n))
