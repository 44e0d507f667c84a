"""Dual-granularity paged decode attention — the Mosaic "hardware" half.

Two Pallas TPU kernels share one flash-accumulator structure; both emit
*unnormalized* (o, m, l) partials so their results can be flash-combined
with each other and across page shards:

  * ``frames`` kernel — the **coalesced fast path** (paper: the 2MB TLB
    entry).  A coalesced large frame is ``frame_pages`` physically
    contiguous, aligned base pages, so the whole frame streams HBM→VMEM as
    ONE BlockSpec block per grid step via ONE scalar-prefetched index
    (frame table).  16× fewer table lookups and long contiguous DMAs.

  * ``pages`` kernel — the **splintered path** (the 4KB base-page walk).
    One base page per grid step, one table lookup per page, short
    scattered DMAs.  This is what 100% of traffic pays under the
    GPU-MMU baseline; under Mosaic only the un-coalesced tail pays it.

Both use ``PrefetchScalarGridSpec`` so the page/frame table drives the
BlockSpec ``index_map`` — the TPU-native analogue of the paper's
hardware page-table walk: translation happens in the DMA descriptor
stream, and its *cost* is the number of descriptors (table entries)
consumed per KV byte.

Grid: (batch, n_blocks, slices) with the KV axes iterated sequentially
("arbitrary") per sequence; a frame too large for one step's VMEM is
read as consecutive slices of its slab.  The flash accumulator lives in
VMEM scratch and is flushed on the last step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_step(q, k, v, valid, m_s, l_s, o_s, *, first: bool):
    """One flash-accumulation step over a KV slab.

    q [kv, g, dh]; k [t, kv, dh]; v [t, kv, dh_v]; valid [t] bool.
    Scratch m_s/l_s [kv, g]; o_s [kv, g, dh_v]  (all fp32).
    """
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)            # [kv, g, t]
    s = jnp.where(valid[None, None, :], s, NEG_INF)
    m_blk = s.max(axis=-1)
    if first:
        m_new = m_blk
        alpha = jnp.zeros_like(m_blk)                  # kill stale scratch
    else:
        m_new = jnp.maximum(m_s[...], m_blk)
        alpha = jnp.exp(m_s[...] - m_new)
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(valid[None, None, :], p, 0.0)
    l_new = (0.0 if first else l_s[...] * alpha) + p.sum(axis=-1)
    pv = jax.lax.dot_general(
        p, v, (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)            # [kv, g, dh_v]
    o_new = (0.0 if first else o_s[...] * alpha[..., None]) + pv
    m_s[...] = m_new
    l_s[...] = l_new
    o_s[...] = o_new


def _paged_kernel(tables_ref, ntok_ref, q_ref, k_ref, v_ref,
                  o_ref, m_ref, l_ref, m_s, l_s, o_s, *,
                  tokens_per_step: int, scale: float):
    """Shared body for both granularities.

    Block shapes (leading batch block of 1 squeezed by indexing):
      q_ref [1, kv, g, dh]; k_ref [1, T, kv, dh]; v_ref [1, T, kv, dh_v]
      (T = tokens_per_step: one page, or one contiguous slice of a frame)
      outputs: o_ref [1, kv, g, dh_v]; m_ref/l_ref [1, kv, g]
    """
    blk = pl.program_id(1)
    sub = pl.program_id(2)
    first = (blk == 0) & (sub == 0)
    q = q_ref[0].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    nt = ntok_ref[pl.program_id(0), blk] - sub * tokens_per_step
    valid = jax.lax.broadcasted_iota(
        jnp.int32, (tokens_per_step,), 0) < nt

    @pl.when(first)
    def _init():
        _flash_step(q, k, v, valid, m_s, l_s, o_s, first=True)

    @pl.when(jnp.logical_not(first))
    def _acc():
        _flash_step(q, k, v, valid, m_s, l_s, o_s, first=False)

    @pl.when((blk == pl.num_programs(1) - 1)
             & (sub == pl.num_programs(2) - 1))
    def _flush():
        o_ref[0] = o_s[...]
        m_ref[0] = m_s[...]
        l_ref[0] = l_s[...]


# Most KV tokens one grid step reads.  A v5e compiles 128-token steps for
# every KV geometry of the config catalog (2 to 32 KV heads of 64 to 128,
# bf16 or f32 pools) and refuses 256-token steps at most of them: scoped
# VMEM holds the double-buffered k/v blocks and their f32 working copies.
STEP_TOKENS = 128


def _step_pages(pages_per_block: int, ptok: int) -> int:
    """Pages one grid step reads: the largest divisor of the block that
    holds at most :data:`STEP_TOKENS` tokens (one page if a page alone is
    larger)."""
    return max(sp for sp in range(1, pages_per_block + 1)
               if pages_per_block % sp == 0
               and (sp == 1 or sp * ptok <= STEP_TOKENS))


def paged_attention_kernel(
    q, pool_k, pool_v, tables, ntok, *,
    granularity: str,            # 'page' | 'frame'
    frame_pages: int = 16,
    scale: float = 1.0,
    interpret: bool = False,
):
    """Launch one granularity's kernel.

    q [B, H, dh]; pool_k/v [NP, ptok, kv, dh{,_v}];
    tables [B, n_blocks] (page ids or frame ids; -1 holes);
    ntok [B, n_blocks] valid tokens per block.
    Returns unnormalized (o [B,H,dh_v] f32, m [B,H] f32, l [B,H] f32).

    A frame is read as ``frame_pages / step_pages`` consecutive slices of
    one contiguous slab (an inner grid axis), so one table entry still
    addresses the whole frame while each step's block fits scoped VMEM.
    """
    B, H, dh = q.shape
    NP, ptok, n_kv, _ = pool_k.shape
    dh_v = pool_v.shape[-1]
    g = H // n_kv
    nblocks = tables.shape[1]
    pages_per_block = frame_pages if granularity == "frame" else 1
    sp = _step_pages(pages_per_block, ptok)
    nsub = pages_per_block // sp
    tps = sp * ptok

    # View pools as [NP // sp, tps, kv, dh]: block ``i * nsub + j`` is the
    # j-th slice of aligned block i (one page, or one frame's slab).
    pk = pool_k.reshape(NP // sp, tps, n_kv, dh)
    pv = pool_v.reshape(NP // sp, tps, n_kv, dh_v)
    qg = q.reshape(B, n_kv, g, dh)

    def q_index(b, blk, sub, tables, ntok):
        return (b, 0, 0, 0)

    def kv_index(b, blk, sub, tables, ntok):
        return (jnp.maximum(tables[b, blk], 0) * nsub + sub, 0, 0, 0)

    def out_index(b, blk, sub, tables, ntok):
        return (b, 0, 0)

    def out_index4(b, blk, sub, tables, ntok):
        return (b, 0, 0, 0)

    kernel = functools.partial(
        _paged_kernel, tokens_per_step=tps, scale=scale)
    o, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nblocks, nsub),
            in_specs=[
                pl.BlockSpec((1, n_kv, g, dh), q_index),
                pl.BlockSpec((1, tps, n_kv, dh), kv_index),
                pl.BlockSpec((1, tps, n_kv, dh_v), kv_index),
            ],
            out_specs=[
                pl.BlockSpec((1, n_kv, g, dh_v), out_index4),
                pl.BlockSpec((1, n_kv, g), out_index),
                pl.BlockSpec((1, n_kv, g), out_index),
            ],
            scratch_shapes=[
                pltpu.VMEM((n_kv, g), jnp.float32),
                pltpu.VMEM((n_kv, g), jnp.float32),
                pltpu.VMEM((n_kv, g, dh_v), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, n_kv, g, dh_v), jnp.float32),
            jax.ShapeDtypeStruct((B, n_kv, g), jnp.float32),
            jax.ShapeDtypeStruct((B, n_kv, g), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(tables, ntok, qg, pk, pv)
    return (o.reshape(B, H, dh_v), m.reshape(B, H), l.reshape(B, H))


def combine_granularities(parts):
    """Flash-combine [(o, m, l), ...] partials from both kernels."""
    os, ms, ls = zip(*parts)
    m_g = functools.reduce(jnp.maximum, ms)
    l_g = sum(l * jnp.exp(m - m_g) for m, l in zip(ms, ls))
    o_g = sum(o * jnp.exp(m - m_g)[..., None] for m, o in zip(ms, os))
    return o_g, m_g, l_g


# -------------------------------------------------------- fused gather-attend


def _fused_kernel(tables_ref, ntok_ref, slots_ref, meta_ref,
                  q_ref, kp_ref, vp_ref, ks_ref, vs_ref,
                  o_ref, m_ref, l_ref,
                  m_r, l_r, o_r, m_t, l_t, o_t, *,
                  tokens_per_block: int, scale: float):
    """Fused gather-attend body (DESIGN.md §13).

    Each grid step reads one page from EITHER the resident pool (slot ==
    -1, via the page table) OR the staging region (slot >= 0, via the
    slot table) — arriving pages are consumed where the DMA landed them,
    no second copy.  Two flash accumulators run in canonical block
    order: the *ready* set (pool-resident pages) and the *late* set
    (staging-slot pages); the flush combines them in fixed (ready, late)
    order.  With every page ready this executes exactly the baseline
    ``_paged_kernel`` accumulate sequence — bitwise-equal fast path —
    and once all pages have landed the staged bytes equal what a
    gather-then-scatter would have written, so the fused result matches
    gather-then-attend.

    ``meta_ref[b] = (n_late, first_ready, first_late)`` (first_* = -1
    when that set is empty) tells each row which block initializes which
    accumulator and which flush case applies.
    """
    b = pl.program_id(0)
    blk = pl.program_id(1)
    nblk = pl.num_programs(1)
    late = slots_ref[b, blk] >= 0
    q = q_ref[0].astype(jnp.float32) * scale
    k = jnp.where(late, ks_ref[0].astype(jnp.float32),
                  kp_ref[0].astype(jnp.float32))
    v = jnp.where(late, vs_ref[0].astype(jnp.float32),
                  vp_ref[0].astype(jnp.float32))
    nt = ntok_ref[b, blk]
    valid = jax.lax.broadcasted_iota(
        jnp.int32, (tokens_per_block,), 0) < nt
    n_late = meta_ref[b, 0]
    first_ready = meta_ref[b, 1]
    first_late = meta_ref[b, 2]
    ready = jnp.logical_not(late)

    @pl.when(ready & (blk == first_ready))
    def _init_ready():
        _flash_step(q, k, v, valid, m_r, l_r, o_r, first=True)

    @pl.when(ready & (blk != first_ready))
    def _acc_ready():
        _flash_step(q, k, v, valid, m_r, l_r, o_r, first=False)

    @pl.when(late & (blk == first_late))
    def _init_late():
        _flash_step(q, k, v, valid, m_t, l_t, o_t, first=True)

    @pl.when(late & (blk != first_late))
    def _acc_late():
        _flash_step(q, k, v, valid, m_t, l_t, o_t, first=False)

    last = blk == nblk - 1

    @pl.when(last & (n_late == 0))
    def _flush_all_ready():
        # Late scratch was never written: emit the ready accumulator
        # untouched — bit-for-bit the baseline kernel's flush.
        o_ref[0] = o_r[...]
        m_ref[0] = m_r[...]
        l_ref[0] = l_r[...]

    @pl.when(last & (n_late == nblk))
    def _flush_all_late():
        o_ref[0] = o_t[...]
        m_ref[0] = m_t[...]
        l_ref[0] = l_t[...]

    @pl.when(last & (n_late > 0) & (n_late < nblk))
    def _flush_combined():
        m_g = jnp.maximum(m_r[...], m_t[...])
        a_r = jnp.exp(m_r[...] - m_g)
        a_t = jnp.exp(m_t[...] - m_g)
        o_ref[0] = o_r[...] * a_r[..., None] + o_t[...] * a_t[..., None]
        m_ref[0] = m_g
        l_ref[0] = l_r[...] * a_r + l_t[...] * a_t


def readiness_meta(slots):
    """Per-row readiness summary for the fused kernel's scalar prefetch:
    ``[B, 3]`` int32 of (n_late, first_ready, first_late), where first_*
    is the lowest block index in that set or -1 when the set is empty."""
    late = slots >= 0
    ready = jnp.logical_not(late)
    n_late = late.sum(axis=1).astype(jnp.int32)
    first_late = jnp.where(late.any(axis=1),
                           jnp.argmax(late, axis=1), -1).astype(jnp.int32)
    first_ready = jnp.where(ready.any(axis=1),
                            jnp.argmax(ready, axis=1), -1).astype(jnp.int32)
    return jnp.stack([n_late, first_ready, first_late], axis=1)


def fused_paged_attention_kernel(
    q, pool_k, pool_v, stage_k, stage_v, tables, slots, ntok, *,
    scale: float = 1.0,
    interpret: bool = False,
):
    """Decode attention over partially-resident KV (DESIGN.md §13).

    q [B, H, dh]; pool_k/v [NP, ptok, kv, dh{,_v}] the resident pool;
    stage_k/v [NS, ptok, kv, dh{,_v}] the staging region late arrivals
    landed in; tables [B, n_blocks] pool page ids (-1 holes);
    slots [B, n_blocks] staging slot per page (-1 = read the pool —
    the per-page readiness mask); ntok [B, n_blocks].
    Returns unnormalized (o, m, l) like :func:`paged_attention_kernel`;
    page granularity (staging slots are page-sized).
    """
    B, H, dh = q.shape
    NP, ptok, n_kv, _ = pool_k.shape
    dh_v = pool_v.shape[-1]
    g = H // n_kv
    nblocks = tables.shape[1]
    if stage_k.shape[0] == 0:       # all-resident caller: keep specs legal
        stage_k = jnp.zeros((1, ptok, n_kv, dh), pool_k.dtype)
        stage_v = jnp.zeros((1, ptok, n_kv, dh_v), pool_v.dtype)
    qg = q.reshape(B, n_kv, g, dh)
    meta = readiness_meta(slots)

    def q_index(b, blk, tables, ntok, slots, meta):
        return (b, 0, 0, 0)

    def kv_pool_index(b, blk, tables, ntok, slots, meta):
        return (jnp.maximum(tables[b, blk], 0), 0, 0, 0)

    def kv_stage_index(b, blk, tables, ntok, slots, meta):
        return (jnp.maximum(slots[b, blk], 0), 0, 0, 0)

    def out_index(b, blk, tables, ntok, slots, meta):
        return (b, 0, 0)

    def out_index4(b, blk, tables, ntok, slots, meta):
        return (b, 0, 0, 0)

    kernel = functools.partial(
        _fused_kernel, tokens_per_block=ptok, scale=scale)
    o, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, nblocks),
            in_specs=[
                pl.BlockSpec((1, n_kv, g, dh), q_index),
                pl.BlockSpec((1, ptok, n_kv, dh), kv_pool_index),
                pl.BlockSpec((1, ptok, n_kv, dh_v), kv_pool_index),
                pl.BlockSpec((1, ptok, n_kv, dh), kv_stage_index),
                pl.BlockSpec((1, ptok, n_kv, dh_v), kv_stage_index),
            ],
            out_specs=[
                pl.BlockSpec((1, n_kv, g, dh_v), out_index4),
                pl.BlockSpec((1, n_kv, g), out_index),
                pl.BlockSpec((1, n_kv, g), out_index),
            ],
            scratch_shapes=[
                pltpu.VMEM((n_kv, g), jnp.float32),
                pltpu.VMEM((n_kv, g), jnp.float32),
                pltpu.VMEM((n_kv, g, dh_v), jnp.float32),
                pltpu.VMEM((n_kv, g), jnp.float32),
                pltpu.VMEM((n_kv, g), jnp.float32),
                pltpu.VMEM((n_kv, g, dh_v), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, n_kv, g, dh_v), jnp.float32),
            jax.ShapeDtypeStruct((B, n_kv, g), jnp.float32),
            jax.ShapeDtypeStruct((B, n_kv, g), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tables, ntok, slots, meta, qg, pool_k, pool_v, stage_k, stage_v)
    return (o.reshape(B, H, dh_v), m.reshape(B, H), l.reshape(B, H))
