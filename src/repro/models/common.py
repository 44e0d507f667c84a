"""Shared model utilities: sharding hints, init, dtype handling."""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------- batch axes
#
# Which mesh axes the *batch* dimension shards over is a run-level choice:
#   megatron (default): ("pod", "data") — 'model' carries TP/EP.
#   fsdp:               ("pod", "data", "model") — every axis is data
#                       parallel; weights stream via per-layer all-gathers
#                       (ZeRO-3).  Selected by TrainHParams.parallelism.
# Model code marks batch dims with the BATCH sentinel; shd() resolves it
# against this context at trace time.

BATCH = "batch"
_BATCH_AXES = ("pod", "data")
_TP_MODE = "explicit"   # 'explicit' (shard_map TP blocks) | 'auto' (GSPMD)


def set_batch_axes(axes: Sequence[str]) -> None:
    global _BATCH_AXES
    _BATCH_AXES = tuple(axes)


def batch_axes() -> tuple:
    return _BATCH_AXES


def set_tp_mode(mode: str) -> None:
    global _TP_MODE
    _TP_MODE = mode


def tp_mode() -> str:
    return _TP_MODE


_SERVING = False


def set_serving_mode(on: bool) -> None:
    """Serving layouts differ from training (resident bf16 TP weights;
    2D expert-parallel MoE storage) — see launch/specs.py + models/moe.py."""
    global _SERVING
    _SERVING = bool(on)


def serving_mode() -> bool:
    return _SERVING


def shd(x: jax.Array, *spec) -> jax.Array:
    """Apply a sharding constraint if a mesh is ambient; no-op otherwise.

    Axis names that are absent from the ambient mesh are dropped, so the
    same model code runs on a laptop (no mesh), a single pod
    ``(data, model)``, and a multi-pod ``(pod, data, model)`` mesh.
    Compound entries (tuples of names) are filtered element-wise.  The
    BATCH sentinel resolves to the current batch-axes context; an axis
    already consumed by an earlier entry is dropped (e.g. the 'model'
    head-sharding hint degrades to replicated under fsdp, where 'model'
    belongs to the batch).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    names = set(mesh.axis_names)
    used: set = set()

    def keep(e):
        if e == BATCH:
            e = _BATCH_AXES
        if e is None:
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(n for n in e if n in names and n not in used)
            used.update(kept)
            return kept if kept else None
        if e in names and e not in used:
            used.add(e)
            return e
        return None

    return jax.lax.with_sharding_constraint(x, P(*[keep(e) for e in spec]))


def psum_point(x: jax.Array) -> jax.Array:
    """Pin the tensor-parallel all-reduce at this tensor's dtype.

    Placed between a row-parallel matmul output (bf16) and the residual
    add / next norm (whose fp32 upcast XLA's convert-mover otherwise
    hoists *through* the all-reduce, doubling its wire bytes — measured
    2x on llama3 train_4k, EXPERIMENTS.md §Perf iteration 2).  The
    barrier is linear, so its transpose pins the backward all-reduce at
    the cotangent's dtype at the same point.
    """
    return jax.lax.optimization_barrier(x)


def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    """Truncated-normal fan-in init (LeCun-style), stored in fp32."""
    fan_in = shape[in_axis] if shape else 1
    scale = 1.0 / max(1.0, fan_in) ** 0.5
    return scale * jax.random.truncated_normal(key, -3, 3, shape, dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * 0.02


def split_keys(key, n: int):
    return list(jax.random.split(key, n))


def cast(tree, dtype):
    """Floating leaves to ``dtype``; leaves already in it are kept as is."""
    return jax.tree.map(
        lambda x: x.astype(dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != dtype else x,
        tree,
    )
