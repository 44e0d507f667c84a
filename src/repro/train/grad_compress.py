"""Int8 block-quantized ring all-reduce with error feedback.

Distributed-optimization trick for the multi-pod mesh: cross-pod (DCN)
gradient reduction is bandwidth-bound, so we reduce in int8 (+fp32
per-block scales, 1/256 overhead) instead of bf16 — ~2× wire bytes saved —
with per-step quantization error carried in an *error-feedback* buffer so
the optimizer sees an unbiased long-run gradient (Seide et al. 1-bit SGD /
EF-SGD line of work).

Implementation: shard_map over the reduction axes; a ring reduce-scatter of
quantized chunks via ``lax.ppermute`` (each hop dequantizes, accumulates in
fp32, requantizes), then a ring all-gather of the final quantized chunks.
On the wire every hop carries int8 payload + fp32 scales.

``compressed_psum_mean`` is a drop-in for ``psum/axis-mean`` on a pytree.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp


BLOCK = 256


def _quant(x32: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """fp32 [n] -> (int8 [n], scales fp32 [n/BLOCK])."""
    xb = x32.reshape(-1, BLOCK)
    scale = jnp.max(jnp.abs(xb), axis=1, keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(xb / scale), -127, 127).astype(jnp.int8)
    return q, scale[:, 0]


def _dequant(q: jax.Array, scale: jax.Array) -> jax.Array:
    return (q.astype(jnp.float32).reshape(-1, BLOCK)
            * scale[:, None]).reshape(-1)


def _ring_allreduce_q(x32: jax.Array, axis: str) -> jax.Array:
    """In-shard_map int8 ring all-reduce of a flat fp32 vector."""
    n_dev = jax.lax.axis_size(axis)
    if n_dev == 1:
        return x32
    me = jax.lax.axis_index(axis)
    n = x32.shape[0]
    chunk = n // n_dev
    xs = x32.reshape(n_dev, chunk)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    # Reduce-scatter: after D-1 hops, shard `me` holds the full sum of
    # chunk (me+1) % D.
    acc = xs
    send_idx = me

    def rs_hop(i, carry):
        acc, send_idx = carry
        payload = jax.lax.dynamic_index_in_dim(acc, send_idx, 0,
                                               keepdims=False)
        q, s = _quant(payload)
        q = jax.lax.ppermute(q, axis, perm)
        s = jax.lax.ppermute(s, axis, perm)
        recv_idx = (send_idx - 1) % n_dev
        inc = _dequant(q, s)
        acc = acc.at[recv_idx].add(inc)
        return acc, recv_idx

    acc, hold_idx = jax.lax.fori_loop(0, n_dev - 1, rs_hop, (acc, send_idx))

    # All-gather: circulate the reduced chunk D-1 hops, quantized.
    def ag_hop(i, carry):
        acc, send_idx = carry
        payload = jax.lax.dynamic_index_in_dim(acc, send_idx, 0,
                                               keepdims=False)
        q, s = _quant(payload)
        q = jax.lax.ppermute(q, axis, perm)
        s = jax.lax.ppermute(s, axis, perm)
        recv_idx = (send_idx - 1) % n_dev
        acc = acc.at[recv_idx].set(_dequant(q, s))
        return acc, recv_idx

    acc, _ = jax.lax.fori_loop(0, n_dev - 1, ag_hop, (acc, hold_idx))
    return acc.reshape(n)


def compressed_allreduce_flat(g32: jax.Array, err: jax.Array, axis: str):
    """One flat fp32 vector: returns (mean-reduced g, new error feedback).

    Error feedback: e' = (g + e) - Q(g + e) accumulated locally.
    """
    n_dev = jax.lax.axis_size(axis)
    x = g32 + err
    q, s = _quant(x)
    xq = _dequant(q, s)
    new_err = x - xq
    total = _ring_allreduce_q(xq, axis)
    return total / n_dev, new_err


def pad_to_block(x: jax.Array, block: int = BLOCK):
    n = x.size
    pad = (-n) % block
    return jnp.pad(x.reshape(-1), (0, pad)), n


def padded_size(n_elems: int, n_dev: int = 1) -> int:
    """Length after padding for an n_dev-ring of BLOCK-quantized chunks.

    Each ring chunk (1/n_dev of the vector) must itself be a whole number
    of quantization blocks, so the vector pads to BLOCK * n_dev.
    """
    block = BLOCK * max(1, n_dev)
    return n_elems + (-n_elems) % block
