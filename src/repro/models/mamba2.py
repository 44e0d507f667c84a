"""Mamba-2 (SSD — state-space duality) blocks: chunked train scan + decode.

Faithful to the minimal-SSD formulation of arXiv:2405.21060 §6: per chunk,
a quadratic intra-chunk term (the "duality" — it is an attention-like
matmul, MXU-friendly) plus an inter-chunk linear recurrence on the
[heads, head_dim, d_state] state.  Decode is the O(1) recurrent update —
which is why Mosaic's paged-KV path is N/A for this family (DESIGN.md §4).

Layout notes (TPU): the intra-chunk einsums are arranged as
[B, n_chunks, Q, ...] batched matmuls with Q=chunk (default 256, a multiple
of 128) so the MXU sees well-shaped contractions; the inter-chunk
recurrence is a ``lax.scan`` over n_chunks with a [B, nh, hd, N] carry.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import dense_init, shd, split_keys
from repro.models.layers import rms_norm

from repro.models.common import BATCH as DP  # batch sentinel


def state_shapes(cfg: ModelConfig, L: int, B: int):
    """(ssm_state, conv_state) shapes for a stacked L-layer SSM."""
    s = cfg.ssm
    d_in, nh, conv_dim = dims(cfg)
    return ((L, B, nh, s.head_dim, s.d_state),
            (L, B, s.d_conv - 1, conv_dim))


def dims(cfg: ModelConfig):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    nh = d_in // s.head_dim
    conv_dim = d_in + 2 * s.n_groups * s.d_state
    return d_in, nh, conv_dim


def init_mamba_params(key, cfg: ModelConfig, L: int) -> Dict[str, Any]:
    s = cfg.ssm
    d = cfg.d_model
    d_in, nh, conv_dim = dims(cfg)
    proj = 2 * d_in + 2 * s.n_groups * s.d_state + nh
    ks = split_keys(key, 4)
    return {
        "w_in": dense_init(ks[0], (L, d, proj), in_axis=1),
        "conv_w": dense_init(ks[1], (L, s.d_conv, conv_dim), in_axis=1),
        "conv_b": jnp.zeros((L, conv_dim)),
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.linspace(1.0, 16.0, nh), (L, nh)).copy()),
        "D": jnp.ones((L, nh)),
        "dt_bias": jnp.zeros((L, nh)),
        "norm_w": jnp.ones((L, d_in)),
        "w_out": dense_init(ks[2], (L, d_in, d), in_axis=1),
    }


def _causal_conv(u, w, b):
    """Depthwise causal conv along T.  u [B,T,C], w [K,C], b [C]."""
    K = w.shape[0]
    pad = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(u)
    for i in range(K):
        out = out + pad[:, i: i + u.shape[1], :] * w[i]
    return out + b


def _split_proj(cfg: ModelConfig, zxbcdt):
    s = cfg.ssm
    d_in, nh, _ = dims(cfg)
    gn = s.n_groups * s.d_state
    z = zxbcdt[..., :d_in]
    xBC = zxbcdt[..., d_in: 2 * d_in + 2 * gn]
    dt = zxbcdt[..., 2 * d_in + 2 * gn:]
    return z, xBC, dt


def _head_expand(cfg: ModelConfig, Bc):
    """[B,T,G,N] -> [B,T,nh,N] by broadcasting groups over their heads."""
    s = cfg.ssm
    _, nh, _ = dims(cfg)
    hpg = nh // s.n_groups
    return jnp.repeat(Bc, hpg, axis=2)


def ssd_chunked(xh, dt, A, Bh, Ch, chunk: int, h0=None):
    """Chunked SSD scan (pure JNP oracle for the Pallas ``ssd_scan`` kernel).

    xh [B,T,nh,hd]; dt [B,T,nh] (post-softplus); A [nh] (negative);
    Bh/Ch [B,T,nh,N].  Returns (y [B,T,nh,hd], h_final [B,nh,hd,N]).
    """
    Bsz, T, nh, hd = xh.shape
    N = Bh.shape[-1]
    assert T % chunk == 0, (T, chunk)
    nc = T // chunk
    r = lambda t: t.reshape(Bsz, nc, chunk, *t.shape[2:])
    xc, dtc, Bc, Cc = r(xh), r(dt), r(Bh), r(Ch)
    xdt = xc * dtc[..., None]                      # dt-weighted input
    dA = dtc * A[None, None, None, :]              # [B,nc,Q,nh]
    cum = jnp.cumsum(dA, axis=2)                   # within-chunk cumsum
    # Intra-chunk (duality: attention-like lower-triangular matmul).
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # [B,nc,Q,Q,nh]
    ii = jnp.arange(chunk)
    causal = (ii[:, None] >= ii[None, :])[None, None, :, :, None]
    # Mask *before* exp: exp of a masked (i<j) positive segment overflows and
    # poisons gradients through the where (classic where-grad pitfall).
    Ldec = jnp.exp(jnp.where(causal, seg, -1e30))
    scores = jnp.einsum("bclhn,bcshn->bclsh", Cc.astype(jnp.float32),
                        Bc.astype(jnp.float32))
    scores = scores * Ldec                                 # [B,nc,Q,Q,nh]
    y_diag = jnp.einsum("bclsh,bcshp->bclhp", scores,
                        xdt.astype(jnp.float32))
    # Chunk-final states.
    decay_states = jnp.exp(cum[:, :, -1:, :] - cum)        # [B,nc,Q,nh]
    states = jnp.einsum("bcshn,bcsh,bcshp->bchpn",
                        Bc.astype(jnp.float32),
                        decay_states, xdt.astype(jnp.float32))
    # Inter-chunk recurrence.
    chunk_decay = jnp.exp(cum[:, :, -1, :])                # [B,nc,nh]

    def body(h, inp):
        st, dec = inp
        h_new = h * dec[:, :, None, None] + st
        return h_new, h                                    # emit h_{c-1}

    h_init = (jnp.zeros((Bsz, nh, hd, N), jnp.float32)
              if h0 is None else h0.astype(jnp.float32))
    h_last, h_prev = jax.lax.scan(
        body,
        h_init,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)),
    )
    h_prev = h_prev.transpose(1, 0, 2, 3, 4)               # [B,nc,nh,hd,N]
    y_off = jnp.einsum("bclhn,bchpn,bclh->bclhp",
                       Cc.astype(jnp.float32), h_prev, jnp.exp(cum))
    y = (y_diag + y_off).reshape(Bsz, T, nh, hd)
    return y, h_last


def mamba_block_train(cfg: ModelConfig, p, x, *, h0=None, conv0=None,
                      return_state: bool = False):
    """x [B,T,d] -> y [B,T,d] (+ optional (h_final, conv_tail) for prefill)."""
    s = cfg.ssm
    d_in, nh, conv_dim = dims(cfg)
    zxbcdt = jnp.einsum("btd,dp->btp", x, p["w_in"])
    z, xBC, dt = _split_proj(cfg, zxbcdt)
    xBC_pre = xBC                                          # pre-conv (cache)
    if conv0 is not None:
        xBC_in = jnp.concatenate([conv0, xBC], axis=1)
        xBC = _causal_conv(xBC_in, p["conv_w"], p["conv_b"])[:, conv0.shape[1]:]
    else:
        xBC = _causal_conv(xBC, p["conv_w"], p["conv_b"])
    xBC = jax.nn.silu(xBC.astype(jnp.float32)).astype(x.dtype)
    gn = s.n_groups * s.d_state
    xp = xBC[..., :d_in]
    Bg = xBC[..., d_in: d_in + gn].reshape(*x.shape[:2], s.n_groups, s.d_state)
    Cg = xBC[..., d_in + gn:].reshape(*x.shape[:2], s.n_groups, s.d_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    xh = xp.reshape(*x.shape[:2], nh, s.head_dim)
    Bh, Ch = _head_expand(cfg, Bg), _head_expand(cfg, Cg)
    # Pad T to a chunk multiple; dt=0 padding is inert (decay 1, no input).
    T = x.shape[1]
    chunk = min(s.chunk, T)
    pad = (-T) % chunk
    if pad:
        pt = ((0, 0), (0, pad))
        xh = jnp.pad(xh, (*pt, (0, 0), (0, 0)))
        dt = jnp.pad(dt, (*pt, (0, 0)))
        Bh = jnp.pad(Bh, (*pt, (0, 0), (0, 0)))
        Ch = jnp.pad(Ch, (*pt, (0, 0), (0, 0)))
    y, h_last = ssd_chunked(xh, dt, A, Bh, Ch, chunk, h0=h0)
    if pad:
        y = y[:, :T]
        xh = xh[:, :T]
    y = y + xh.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(*x.shape[:2], d_in).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
                 p["norm_w"], cfg.norm_eps)
    out = jnp.einsum("btp,pd->btd", y, p["w_out"])
    if return_state:
        ctx = (jnp.zeros((x.shape[0], s.d_conv - 1, conv_dim), x.dtype)
               if conv0 is None else conv0)
        conv_tail = jnp.concatenate([ctx, xBC_pre], axis=1)[:, -(s.d_conv - 1):]
        return out, (h_last, conv_tail)
    return out


def init_ssm_stack_params(key, cfg: ModelConfig, L: int):
    ks = split_keys(key, 2)
    return {"ln": jnp.ones((L, cfg.d_model)),
            "mamba": init_mamba_params(ks[0], cfg, L)}


def ssm_stack_train(cfg: ModelConfig, params, x, *, remat: bool = True):
    def layer(cfg, lp, ln, x):
        return shd(x + mamba_block_train(cfg, lp, rms_norm(x, ln,
                                                           cfg.norm_eps)),
                   DP, None, None)

    def body(x, inp):
        ln, lp = inp
        fn = jax.checkpoint(layer, static_argnums=(0,)) if remat else layer
        return fn(cfg, lp, ln, x), None

    x, _ = jax.lax.scan(body, x, (params["ln"], params["mamba"]))
    return x


def ssm_stack_prefill(cfg: ModelConfig, params, x):
    """Returns (x, ssm_states [L,B,nh,hd,N], conv_states [L,B,K-1,cd])."""

    def body(x, inp):
        ln, lp = inp
        y, (h, conv) = mamba_block_train(
            cfg, lp, rms_norm(x, ln, cfg.norm_eps), return_state=True)
        return shd(x + y, DP, None, None), (h, conv)

    x, (hs, convs) = jax.lax.scan(body, x, (params["ln"], params["mamba"]))
    return x, hs, convs


def ssm_stack_decode(cfg: ModelConfig, params, x, ssm_state, conv_state):
    def body(carry, inp):
        x = carry[0]
        ln, lp, h, conv = inp
        y, h_new, conv_new = mamba_block_decode(
            cfg, lp, rms_norm(x, ln, cfg.norm_eps), h, conv)
        return (x + y,), (h_new, conv_new)

    (x,), (hs, convs) = jax.lax.scan(
        body, (x,), (params["ln"], params["mamba"], ssm_state, conv_state))
    return x, hs, convs


def mamba_block_decode(cfg: ModelConfig, p, x, h, conv_cache):
    """One-token recurrent update.

    x [B,1,d]; h [B,nh,hd,N]; conv_cache [B,d_conv-1,conv_dim]
    -> (y [B,1,d], h', conv_cache')
    """
    s = cfg.ssm
    d_in, nh, conv_dim = dims(cfg)
    zxbcdt = jnp.einsum("btd,dp->btp", x, p["w_in"])
    z, xBC, dt = _split_proj(cfg, zxbcdt)
    window = jnp.concatenate([conv_cache, xBC], axis=1)    # [B,d_conv,cd]
    conv_out = (window * p["conv_w"][None]).sum(axis=1) + p["conv_b"]
    conv_cache = window[:, 1:]
    xBC1 = jax.nn.silu(conv_out.astype(jnp.float32)).astype(x.dtype)
    gn = s.n_groups * s.d_state
    xp = xBC1[..., :d_in]
    Bg = xBC1[..., d_in: d_in + gn].reshape(-1, s.n_groups, s.d_state)
    Cg = xBC1[..., d_in + gn:].reshape(-1, s.n_groups, s.d_state)
    dt1 = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    hpg = nh // s.n_groups
    Bh = jnp.repeat(Bg, hpg, axis=1)                       # [B,nh,N]
    Ch = jnp.repeat(Cg, hpg, axis=1)
    xh = xp.reshape(-1, nh, s.head_dim).astype(jnp.float32)
    dA = jnp.exp(dt1 * A[None, :])                         # [B,nh]
    h = h * dA[:, :, None, None] + jnp.einsum(
        "bh,bhp,bhn->bhpn", dt1, xh, Bh.astype(jnp.float32))
    y = jnp.einsum("bhn,bhpn->bhp", Ch.astype(jnp.float32), h)
    y = y + xh * p["D"][None, :, None]
    y = y.reshape(-1, 1, d_in).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
                 p["norm_w"], cfg.norm_eps)
    return jnp.einsum("btp,pd->btd", y, p["w_out"]), h, conv_cache
