"""Pallas kernel sweeps: shapes × dtypes vs the pure-JNP oracles.

All kernels run in interpret=True mode on CPU (the kernel body executes in
Python); the same code path compiles for TPU with interpret=False.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.page_compact import page_compact
from repro.kernels.paged_attention import (
    combine_granularities,
    paged_attention_kernel,
)

RNG = np.random.default_rng(7)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------- flash


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,T,H,Hkv,dh,bq,bk",
    [
        (2, 256, 8, 4, 64, 64, 128),
        (1, 512, 4, 4, 32, 128, 256),
        (2, 128, 8, 2, 16, 64, 64),
        (1, 256, 16, 8, 128, 128, 128),  # MXU-aligned head dim
        (3, 192, 6, 3, 48, 64, 192),     # odd-ish shapes
    ],
)
def test_flash_attention_sweep(B, T, H, Hkv, dh, bq, bk, dtype):
    q = jnp.asarray(RNG.normal(size=(B, T, H, dh)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, T, Hkv, dh)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, T, Hkv, dh)), dtype)
    out = flash_attention(q, k, v, causal=True, bq=bq, bk=bk, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        **_tol(dtype))


def test_flash_attention_noncausal():
    q = jnp.asarray(RNG.normal(size=(2, 128, 4, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, 128, 4, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, 128, 4, 32)), jnp.float32)
    out = flash_attention(q, k, v, causal=False, bq=64, bk=64, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------- paged


def _random_tables(B, n_frames_pool, fp, ptok, seq_lens, coalesce_frac,
                   max_frames, max_pages):
    """Random Mosaic-style layout: some frames coalesced, rest splintered."""
    frame_tables = np.full((B, max_frames), -1, np.int32)
    frame_ntok = np.zeros((B, max_frames), np.int32)
    page_tables = np.full((B, max_pages), -1, np.int32)
    page_ntok = np.zeros((B, max_pages), np.int32)
    free_frames = list(RNG.permutation(n_frames_pool))
    for b in range(B):
        toks = seq_lens[b]
        vframes = (toks + fp * ptok - 1) // (fp * ptok)
        fi = pi = 0
        for vf in range(vframes):
            ft = min(fp * ptok, toks - vf * fp * ptok)
            frame = free_frames.pop()
            if RNG.random() < coalesce_frac and ft == fp * ptok:
                frame_tables[b, fi] = frame
                frame_ntok[b, fi] = ft
                fi += 1
            else:
                for s in range(fp):
                    pt = min(ptok, ft - s * ptok)
                    if pt <= 0:
                        break
                    page_tables[b, pi] = frame * fp + s
                    page_ntok[b, pi] = pt
                    pi += 1
    return (jnp.asarray(frame_tables), jnp.asarray(frame_ntok),
            jnp.asarray(page_tables), jnp.asarray(page_ntok))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,n_kv,dh,dhv,ptok,fp,coalesce",
    [
        (2, 8, 4, 32, 32, 16, 4, 0.7),
        (2, 8, 8, 64, 64, 8, 4, 1.0),    # MHA, all coalesced
        (1, 16, 1, 40, 32, 16, 4, 0.5),  # MLA-like: n_kv=1, dh_v != dh
        (4, 4, 2, 128, 128, 32, 2, 0.0), # nothing coalesced (baseline)
    ],
)
def test_paged_attention_dual_sweep(B, H, n_kv, dh, dhv, ptok, fp,
                                    coalesce, dtype):
    n_frames_pool = 16
    NP = n_frames_pool * fp
    pool_k = jnp.asarray(RNG.normal(size=(NP, ptok, n_kv, dh)), dtype)
    pool_v = jnp.asarray(RNG.normal(size=(NP, ptok, n_kv, dhv)), dtype)
    q = jnp.asarray(RNG.normal(size=(B, H, dh)), dtype)
    seq_lens = RNG.integers(1, 3 * fp * ptok, size=B)
    ft, fn, pt, pn = _random_tables(B, n_frames_pool, fp, ptok, seq_lens,
                                    coalesce, max_frames=4,
                                    max_pages=4 * fp)
    scale = dh ** -0.5
    parts = [
        paged_attention_kernel(q, pool_k, pool_v, ft, fn,
                               granularity="frame", frame_pages=fp,
                               scale=scale, interpret=True),
        paged_attention_kernel(q, pool_k, pool_v, pt, pn,
                               granularity="page", scale=scale,
                               interpret=True),
    ]
    o, m, l = combine_granularities(parts)
    out = o / np.maximum(np.asarray(l)[..., None], 1e-30)

    # Oracle over the union of pages.
    fp_pages = (np.asarray(ft)[..., None] * fp + np.arange(fp)).reshape(B, -1)
    fp_pages = np.where(np.repeat(np.asarray(ft), fp, axis=1) >= 0,
                        fp_pages, -1)
    fp_ntok = np.clip(np.repeat(np.asarray(fn), fp, axis=1)
                      - np.tile(np.arange(fp) * ptok, ft.shape[1]), 0, ptok)
    all_t = jnp.asarray(np.concatenate([fp_pages, np.asarray(pt)], axis=1))
    all_n = jnp.asarray(np.concatenate([fp_ntok, np.asarray(pn)], axis=1))
    expect = ref.paged_attention_full_ref(q, pool_k, pool_v, all_t, all_n,
                                          scale=scale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


# --------------------------------------------------------------- compact


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("NP,ptok,kv,dh,n", [(32, 8, 2, 16, 4),
                                             (64, 16, 1, 40, 9),
                                             (16, 4, 4, 8, 1)])
def test_page_compact_sweep(NP, ptok, kv, dh, n, dtype):
    pool = jnp.asarray(RNG.normal(size=(NP, ptok, kv, dh)), dtype)
    perm = RNG.permutation(NP)
    src = perm[:n].astype(np.int32)
    dst = perm[n:2 * n].astype(np.int32)
    src[n // 2] = -1
    dst[n // 2] = -1
    out = page_compact(pool, jnp.asarray(src), jnp.asarray(dst),
                       interpret=True)
    expect = ref.page_compact_ref(pool, jnp.asarray(src), jnp.asarray(dst))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


# --------------------------------------------------------------- ssd scan


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,T,nh,hd,N,Q,with_h0",
    [
        (2, 128, 3, 16, 32, 32, False),
        (1, 256, 2, 64, 128, 128, True),   # MXU-aligned dims
        (2, 64, 4, 8, 16, 16, True),
        (1, 96, 1, 32, 64, 32, False),     # nc=3, single head
    ],
)
def test_ssd_scan_sweep(B, T, nh, hd, N, Q, with_h0, dtype):
    from repro.kernels.ssd_scan import ssd_scan

    x = jnp.asarray(RNG.normal(size=(B, T, nh, hd)), dtype)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(B, T, nh)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, size=(nh,)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, T, nh, N)), dtype)
    Cm = jnp.asarray(RNG.normal(size=(B, T, nh, N)), dtype)
    h0 = (jnp.asarray(RNG.normal(size=(B, nh, hd, N)), jnp.float32)
          if with_h0 else None)
    y_k, h_k = ssd_scan(x, dt, A, Bm, Cm, chunk=Q, h0=h0, interpret=True)
    y_r, h_r = ref.ssd_scan_ref(x, dt, A, Bm, Cm, chunk=Q, h0=h0)
    tol = dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), **tol)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r), **tol)
