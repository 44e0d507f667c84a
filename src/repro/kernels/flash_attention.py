"""Causal flash attention (train/prefill) — Pallas TPU kernel.

Standard two-level blocking: grid = (B·Hkv·g, Tq/bq, Tk/bk) with the KV
axis innermost ("arbitrary": sequential per core, accumulator in VMEM
scratch).  Causal blocks above the diagonal are skipped entirely via
``pl.when`` (the index map still loads, but no FLOPs are spent — on real
TPUs the Mosaic compiler elides the DMA for fully-masked blocks when the
bound is static; we keep the simple form).

Block sizes default to (bq, bk) = (128, 512): MXU-aligned, and the working
set per step — q 128×dh + k/v 2×512×dh + acc 128×dh fp32 — stays well under
VMEM for dh ≤ 256.

GQA is handled by flattening (B, Hkv) into the grid's batch axis and
carrying the g query heads of the group in the q block: q block is
[1, g·bq, dh] so group heads share the K/V DMA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
                  bq: int, bk: int, g: int, causal: bool, scale: float,
                  kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s[...], NEG_INF)
        l_s[...] = jnp.zeros_like(l_s[...])
        acc_s[...] = jnp.zeros_like(acc_s[...])

    q_start = qi * bq
    k_start = ki * bk
    run = (not causal) or (k_start <= q_start + bq - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale       # [g*bq, dh]
        k = k_ref[0].astype(jnp.float32)               # [bk, dh]
        v = v_ref[0].astype(jnp.float32)               # [bk, dh_v]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [g*bq, bk]
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask = kpos < kv_len
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (g * bq, 1), 0) % bq
            mask = mask & (kpos <= qpos)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_s[...], s.max(axis=-1))
        alpha = jnp.exp(m_s[...] - m_new)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask, p, 0.0)
        l_s[...] = l_s[...] * alpha + p.sum(axis=-1)
        acc_s[...] = acc_s[...] * alpha[..., None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    @pl.when(ki == nk - 1)
    def _flush():
        o_ref[0] = (acc_s[...] /
                    jnp.maximum(l_s[...], 1e-30)[..., None]
                    ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, scale=None,
                    bq: int = 128, bk: int = 512, interpret: bool = False):
    """q [B,T,H,dh]; k/v [B,T,Hkv,dh{,_v}] -> [B,T,H,dh_v].

    T must be a multiple of bq and bk (pad upstream; the model path pads).
    """
    B, T, H, dh = q.shape
    Hkv = k.shape[2]
    dh_v = v.shape[-1]
    g = H // Hkv
    bq = min(bq, T)
    bk = min(bk, T)
    assert T % bq == 0 and T % bk == 0, (T, bq, bk)
    scale = dh ** -0.5 if scale is None else scale

    # [B*Hkv, g*T, dh] layout: group heads ride along the q row-block.
    qr = (q.reshape(B, T, Hkv, g, dh).transpose(0, 2, 3, 1, 4)
          .reshape(B * Hkv, g * T, dh))
    kr = k.transpose(0, 2, 1, 3).reshape(B * Hkv, T, dh)
    vr = v.transpose(0, 2, 1, 3).reshape(B * Hkv, T, dh_v)

    def q_index(bh, qi, ki):
        return (bh, qi, 0)

    def kv_index(bh, qi, ki):
        return (bh, ki, 0)

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, g=g, causal=causal, scale=scale,
        kv_len=T)
    # q block carries the g heads of the group: rows [g, bq] flattened.
    # We lay q as [B*Hkv, g*T, dh] with head-major rows, so the q block for
    # (qi) must gather g strided row-slices — instead use block = g*bq rows
    # at stride T: reorder to [B*Hkv, T/bq, g*bq, dh] host-side.
    qb = (qr.reshape(B * Hkv, g, T // bq, bq, dh).transpose(0, 2, 1, 3, 4)
          .reshape(B * Hkv, T // bq * g * bq, dh))

    out = pl.pallas_call(
        kernel,
        grid=(B * Hkv, T // bq, T // bk),
        in_specs=[
            pl.BlockSpec((1, g * bq, dh), q_index),
            pl.BlockSpec((1, bk, dh), kv_index),
            pl.BlockSpec((1, bk, dh_v), kv_index),
        ],
        out_specs=pl.BlockSpec((1, g * bq, dh_v), q_index),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, T // bq * g * bq, dh_v),
                                       q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g * bq,), jnp.float32),
            pltpu.VMEM((g * bq,), jnp.float32),
            pltpu.VMEM((g * bq, dh_v), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qb, kr, vr)
    out = (out.reshape(B, Hkv, T // bq, g, bq, dh_v)
           .transpose(0, 2, 4, 1, 3, 5)
           .reshape(B, T, H, dh_v))
    return out
