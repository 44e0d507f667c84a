"""Pallas TPU kernel for the Mamba-2 SSD chunked scan (arXiv:2405.21060 §6).

Grid: (batch, heads, n_chunks) — batch/head blocks are parallel, the
chunk axis is sequential ("arbitrary") and carries the running
[head_dim, d_state] recurrent state in a VMEM scratch accumulator, so the
inter-chunk linear recurrence never round-trips HBM.  Per step the kernel
does three MXU matmuls on one chunk:

    scores = (C B^T) ⊙ exp(segsum)        [Q, Q]   (the "duality" term)
    y      = scores · (dt x) + (C h^T) ⊙ exp(cum)  [Q, hd]
    h'     = diag(exp(cum_last)) h + (dt x ⊙ decay)^T B   [hd, N]

Shapes are chosen MXU-friendly by the model (Q = chunk = 256, hd = 64,
N = 128).  ``repro.models.mamba2.ssd_chunked`` is the pure-JNP oracle;
``tests/test_kernels.py`` sweeps shapes/dtypes against it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref,
                y_ref, hout_ref, h_acc):
    c_idx = pl.program_id(2)
    Q = x_ref.shape[2]

    @pl.when(c_idx == 0)
    def _init():
        h_acc[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)              # [Q, hd]
    dt = dt_ref[0, 0].astype(jnp.float32)            # [Q, 1]
    A = a_ref[pl.program_id(1)].astype(jnp.float32)  # scalar (SMEM)
    Bm = b_ref[0, 0].astype(jnp.float32)             # [Q, N]
    Cm = c_ref[0, 0].astype(jnp.float32)             # [Q, N]

    xdt = x * dt
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    # Within-chunk cumsum of dt·A as masked reductions (no cumsum on the
    # TPU's vector unit): as a row, then moved to a column via the diagonal.
    cum_row = jnp.sum(jnp.where(ii <= jj, dt * A, 0.0), axis=0,
                      keepdims=True)                 # [1, Q]
    cum = jnp.sum(jnp.where(ii == jj, cum_row, 0.0), axis=1,
                  keepdims=True)                     # [Q, 1]
    cum_last = cum[Q - 1:, :]                        # [1, 1]
    Ldec = jnp.exp(jnp.where(ii >= jj, cum - cum_row, NEG_INF))
    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * Ldec   # [Q, Q]
    y = jax.lax.dot_general(scores, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # Off-diagonal (carried-state) term.
    h = h_acc[...]                                   # [hd, N]
    y = y + jax.lax.dot_general(
        Cm, h, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * jnp.exp(cum)
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # State update.
    decay = jnp.exp(cum_last - cum)                  # [Q, 1]
    h_new = h * jnp.exp(cum_last) + jax.lax.dot_general(
        xdt * decay, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    h_acc[...] = h_new
    hout_ref[0, 0] = h_new.astype(hout_ref.dtype)


def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int, h0=None,
             interpret: bool = False):
    """Chunked SSD scan. x [B,T,nh,hd]; dt [B,T,nh] (post-softplus);
    A [nh] (negative); Bm/Cm [B,T,nh,N]; h0 [B,nh,hd,N] or None.
    Returns (y [B,T,nh,hd] f32, h_final [B,nh,hd,N] f32).
    """
    Bsz, T, nh, hd = x.shape
    N = Bm.shape[-1]
    assert T % chunk == 0, (T, chunk)
    nc = T // chunk
    if h0 is None:
        h0 = jnp.zeros((Bsz, nh, hd, N), jnp.float32)

    # Heads lead so each block's minor dims are (chunk, hd|N|1) tiles the
    # TPU accepts; A is read from SMEM.
    def heads_first(t):
        return jnp.moveaxis(t, 2, 1)

    def chunk_blk(d):
        return pl.BlockSpec((1, 1, chunk, d), lambda b, h, c: (b, h, c, 0))

    state_blk = pl.BlockSpec((1, 1, hd, N), lambda b, h, c: (b, h, 0, 0))
    y, h_last = pl.pallas_call(
        _ssd_kernel,
        grid=(Bsz, nh, nc),
        in_specs=[
            chunk_blk(hd),
            chunk_blk(1),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            chunk_blk(N),
            chunk_blk(N),
            state_blk,
        ],
        out_specs=[chunk_blk(hd), state_blk],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, nh, T, hd), jnp.float32),
            jax.ShapeDtypeStruct((Bsz, nh, hd, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(heads_first(x), heads_first(dt)[..., None], A, heads_first(Bm),
      heads_first(Cm), h0)
    return heads_first(y), h_last
