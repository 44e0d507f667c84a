"""Jit'd public wrappers for the Pallas kernels.

``use_pallas`` selects the Pallas kernel, compiled for the TPU; on the
CPU a caller passes ``interpret=True`` to run the kernel body in the
Pallas interpreter instead.  ``use_pallas=False`` runs the pure-JAX
oracles in :mod:`repro.kernels.ref`.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash_kernel
from repro.kernels.page_compact import (
    page_compact as _compact_kernel,
    page_gather as _gather_kernel,
    page_scatter as _scatter_kernel,
)
from repro.kernels.paged_attention import (
    combine_granularities,
    paged_attention_kernel,
)


@functools.partial(jax.jit, static_argnames=("causal", "use_pallas",
                                             "interpret", "bq", "bk"))
def flash_attention(q, k, v, *, causal: bool = True,
                    use_pallas: bool = False, interpret: bool = False,
                    bq: int = 128, bk: int = 512):
    if use_pallas:
        return _flash_kernel(q, k, v, causal=causal, bq=bq, bk=bk,
                             interpret=interpret)
    return ref.flash_attention_ref(q, k, v, causal=causal)


@functools.partial(jax.jit, static_argnames=("frame_pages", "scale",
                                             "use_pallas", "interpret"))
def paged_attention_dual(q, pool_k, pool_v, frame_tables, frame_ntok,
                         page_tables, page_ntok, *, frame_pages: int,
                         scale: float, use_pallas: bool = False,
                         interpret: bool = False):
    """Dual-granularity paged attention over one shard's pool.

    Coalesced frames go down the frame fast path; splintered pages down the
    page path; partials flash-combined.  Returns normalized [B, H, dh_v].
    """
    if use_pallas:
        parts = [
            paged_attention_kernel(q, pool_k, pool_v, frame_tables,
                                   frame_ntok, granularity="frame",
                                   frame_pages=frame_pages, scale=scale,
                                   interpret=interpret),
            paged_attention_kernel(q, pool_k, pool_v, page_tables,
                                   page_ntok, granularity="page",
                                   scale=scale, interpret=interpret),
        ]
        o, m, l = combine_granularities(parts)
        return o / jnp.maximum(l[..., None], 1e-30)
    # Oracle path: frames expanded to pages.
    B, nf = frame_tables.shape
    fp = frame_pages
    ptok = pool_k.shape[1]
    pages_of_frames = (frame_tables[..., None] * fp
                       + jnp.arange(fp)[None, None, :])
    pages_of_frames = jnp.where(frame_tables[..., None] >= 0,
                                pages_of_frames, -1).reshape(B, nf * fp)
    slot0 = jnp.arange(fp)[None, None, :] * ptok
    ntok_pages = jnp.clip(frame_ntok[..., None] - slot0, 0, ptok)
    ntok_pages = ntok_pages.reshape(B, nf * fp)
    all_tables = jnp.concatenate([pages_of_frames, page_tables], axis=1)
    all_ntok = jnp.concatenate([ntok_pages, page_ntok], axis=1)
    return ref.paged_attention_full_ref(q, pool_k, pool_v, all_tables,
                                        all_ntok, scale=scale)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def page_compact(pool, src, dst, *, use_pallas: bool = True,
                 interpret: bool = False):
    if use_pallas:
        return _compact_kernel(pool, src, dst, interpret=interpret)
    return ref.page_compact_ref(pool, src, dst)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def page_gather(pool, idx, *, use_pallas: bool = True,
                interpret: bool = False):
    """Host-tier eviction gather: pages[i] = pool[idx[i]] (DESIGN.md §6)."""
    if use_pallas:
        return _gather_kernel(pool, idx, interpret=interpret)
    return ref.page_gather_ref(pool, idx)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def page_scatter(pool, idx, pages, *, use_pallas: bool = True,
                 interpret: bool = False):
    """Host-tier fault-in scatter: pool[idx[i]] = pages[i] (DESIGN.md §6)."""
    if use_pallas:
        return _scatter_kernel(pool, idx, pages, interpret=interpret)
    return ref.page_scatter_ref(pool, idx, pages)


@functools.partial(jax.jit, static_argnames=("chunk", "use_pallas",
                                             "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int, h0=None,
             use_pallas: bool = False, interpret: bool = False):
    """Mamba-2 SSD chunked scan (see kernels/ssd_scan.py)."""
    if use_pallas:
        from repro.kernels.ssd_scan import ssd_scan as _ssd_kernel
        return _ssd_kernel(x, dt, A, Bm, Cm, chunk=chunk, h0=h0,
                           interpret=interpret)
    return ref.ssd_scan_ref(x, dt, A, Bm, Cm, chunk=chunk, h0=h0)
