"""Kernel micro-benchmarks (beyond-paper deliverable).

Times the pure-JAX oracle paths on CPU (wall-clock, jitted, steady-state)
and derives the *structural* cost of the Pallas kernels for TPU: per-call
indirection counts and DMA contiguity at both page granularities — the
quantity Mosaic's coalescing improves (the kernel-level analogue of TLB
reach).  Wall-clock on CPU is NOT a TPU number; the structural columns are
hardware-independent.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np
import jax
import jax.numpy as jnp

from repro.models import paged


def _time(fn, *args, iters=20, warmup=3) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def paged_attention_granularity(B=4, H=8, n_kv=4, dh=64, ptok=64, fp=16,
                                ctx_tokens=16384) -> List[Dict]:
    """Oracle decode attention: coalesced-frame path vs splintered pages.

    Structural columns: indirections (scalar-prefetched table reads) and
    contiguous DMA run length — 16x better when frames are coalesced.
    """
    rng = np.random.default_rng(0)
    pages_per_seq = ctx_tokens // ptok
    NP = B * pages_per_seq
    k_pool = jnp.asarray(rng.normal(size=(NP, ptok, n_kv, dh)),
                         jnp.bfloat16)
    v_pool = jnp.asarray(rng.normal(size=(NP, ptok, n_kv, dh)),
                         jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(B, H, dh)), jnp.bfloat16)

    # Splintered: every page its own table entry (random placement).
    pt = rng.permutation(NP).reshape(B, pages_per_seq).astype(np.int32)
    pn = np.full((B, pages_per_seq), ptok, np.int32)

    # Coalesced: same pages but frame-contiguous (CoCoA layout): entries
    # ascend in runs of fp (the structural property the kernel exploits).
    ct = np.arange(NP).reshape(B, pages_per_seq).astype(np.int32)

    f = jax.jit(lambda q, k, v, t, n: paged.combine_partials(
        *paged.paged_attention_local(q, k, v, t, n, scale=dh ** -0.5), ()))
    us_split = _time(f, q, k_pool, v_pool, jnp.asarray(pt), jnp.asarray(pn))
    us_coal = _time(f, q, k_pool, v_pool, jnp.asarray(ct), jnp.asarray(pn))

    return [{
        "bench": "kernel_paged_attention",
        "ctx_tokens": ctx_tokens,
        "us_splintered": us_split,
        "us_coalesced_layout": us_coal,
        # structural: table indirections per (seq, layer) lookup
        "indirections_splintered": pages_per_seq,
        "indirections_coalesced": pages_per_seq // fp,
        "dma_run_tokens_splintered": ptok,
        "dma_run_tokens_coalesced": ptok * fp,
    }]


def page_compact_cost(NP=4096, ptok=64, n_kv=8, dh=128,
                      batch_sizes=(16, 64, 256)) -> List[Dict]:
    """CAC copy cost per compaction batch (bytes moved, µs on CPU oracle)."""
    from repro.kernels.page_compact import page_compact
    rng = np.random.default_rng(1)
    pool = jnp.asarray(rng.normal(size=(NP, ptok, n_kv, dh)), jnp.bfloat16)
    rows = []
    f = jax.jit(lambda p, s, d: page_compact(p, s, d, interpret=True))
    for n in batch_sizes:
        perm = rng.permutation(NP)
        src = jnp.asarray(perm[:n].astype(np.int32))
        dst = jnp.asarray(perm[n:2 * n].astype(np.int32))
        us = _time(f, pool, src, dst, iters=5)
        page_bytes = ptok * n_kv * dh * 2
        rows.append({
            "bench": "kernel_page_compact", "copies": n,
            "bytes_moved": n * page_bytes,
            "us_per_batch_cpu": us,
            # TPU structural estimate: HBM rd+wr at 819 GB/s
            "tpu_est_us": 2 * n * page_bytes / 819e9 * 1e6,
        })
    return rows


def pagesize_sweep(ctx_tokens=16384, B=2, H=8, n_kv=4, dh=64) -> List[Dict]:
    """TPU-native page-size trade-off (paper Fig. 1 + §1, re-tiled).

    Sweeps page_tokens: smaller pages = finer transfer granularity (less
    over-fetch on faults) but more indirections per attention call;
    frame coalescing recovers the indirection cost — which is the paper's
    whole point, in one table.
    """
    from repro.core.demand_paging import LinkModel
    link = LinkModel()
    rows = []
    kv_bytes_tok = 2 * n_kv * dh * 2
    for ptok in (16, 32, 64, 128, 256):
        pages = ctx_tokens // ptok
        page_bytes = ptok * kv_bytes_tok
        # Demand-paging term: one token's fault over-fetches page_bytes.
        fault_us = link.transfer_us(page_bytes)
        rows.append({
            "bench": "pagesize_sweep", "page_tokens": ptok,
            "indirections_base": pages,
            "indirections_coalesced": max(1, pages // 16),
            "fault_transfer_us": fault_us,
            "fault_overfetch_bytes": page_bytes,
        })
    return rows
