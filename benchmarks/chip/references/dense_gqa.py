"""Plain reference of a dense GQA decoder (Qwen2, Phi-3 and alike).

Pre-norm blocks: RMSNorm, causal grouped-query attention with rotary
positions (rotate-half form), optional q/k/v bias, residual add, RMSNorm,
SwiGLU MLP, residual add; a final RMSNorm and the (tied or untied) output
matrix.  Everything runs in float32 at ``highest`` matmul precision, one
layer at a time, on weights it regenerates from the seed with
``weights.layer_weights``.  It imports nothing of the program under test.

``quant="fp8"`` is the control: every matmul input is rounded to
float8_e4m3fn first, which is the step below the served bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

F32 = jnp.float32


def _q(x, quant):
    if quant == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(F32)
    return x


def _mm(spec, a, b, quant):
    return jnp.einsum(spec, _q(a, quant), _q(b.astype(F32), quant),
                      precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, theta):
    """x [B, T, heads, dh] at positions 0..T-1 (rotate-half form)."""
    T, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer(x, words, layer, dm: W.Dims, quant):
    p = W.layer_weights(W.layer_key(words, layer), dm)
    a, m = p["attn"], p["mlp"]
    B, T, _ = x.shape
    h = _rms(x, p["ln1"], dm.norm_eps)
    q = _mm("btd,dhk->bthk", h, a["wq"], quant)
    k = _mm("btd,dhk->bthk", h, a["wk"], quant)
    v = _mm("btd,dhk->bthk", h, a["wv"], quant)
    if dm.qkv_bias:
        q, k, v = (q + a["bq"].astype(F32), k + a["bk"].astype(F32),
                   v + a["bv"].astype(F32))
    q, k = _rope(q, dm.rope_theta), _rope(k, dm.rope_theta)
    g = dm.n_heads // dm.n_kv_heads
    q = q.reshape(B, T, dm.n_kv_heads, g, dm.head_dim)
    s = _mm("btngd,bsnd->bngts", q, k, quant) * dm.head_dim ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = _mm("bngts,bsnd->btngd", jax.nn.softmax(s, -1), v, quant)
    o = o.reshape(B, T, dm.n_heads, dm.head_dim)
    x = x + _mm("bthk,hkd->btd", o, a["wo"], quant)
    h = _rms(x, p["ln2"], dm.norm_eps)
    gate = _mm("btd,df->btf", h, m["w_gate"], quant)
    up = _mm("btd,df->btf", h, m["w_up"], quant)
    return x + _mm("btf,fd->btd", jax.nn.silu(gate) * up, m["w_down"], quant)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head(x, words, pos, cand, dm: W.Dims, quant):
    """At positions ``pos`` [B, P] of ``x``: the best logit, the logit of
    each candidate token ``cand`` [C, B, P], and the argmax token."""
    o = W.outer_weights(words, dm)
    xs = jnp.take_along_axis(x, pos[..., None], axis=1)
    xs = _rms(xs, o["final_norm"], dm.norm_eps)
    w = o["embed"].T if dm.tied else o["unembed"]
    lg = _mm("bpd,dv->bpv", xs, w, quant)
    picked = jax.vmap(lambda c: jnp.take_along_axis(
        lg, c[..., None], axis=-1)[..., 0])(cand)
    return lg.max(-1), picked, jnp.argmax(lg, -1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=1)
def _lookup(tokens, dm: W.Dims, words):
    return W.outer_weights(words, dm)["embed"][tokens].astype(F32)


def score(seed: int, dm: W.Dims, tokens: np.ndarray, pos: np.ndarray,
          cand: np.ndarray, quant: str = "none", rows: int = 4):
    """Run the reference over ``tokens`` [B, T] and read it at ``pos``
    [B, P]: (best logit [B, P], logits of the candidate tokens
    ``cand`` [C, B, P] as [C, B, P], argmax token [B, P]).  It runs in
    blocks of ``rows`` sequences and layer by layer so that it fits."""
    words = W.seed_words(seed)
    best, picked, top = [], [], []
    for b0 in range(0, tokens.shape[0], rows):
        sl = slice(b0, b0 + rows)
        x = _lookup(jnp.asarray(tokens[sl]), dm, words)
        for layer in range(dm.n_layers):
            x = _layer(x, words, jnp.int32(layer), dm, quant)
        b, p, t = _head(x, words, jnp.asarray(pos[sl]),
                        jnp.asarray(cand[:, sl]), dm, quant)
        best.append(np.asarray(b))
        picked.append(np.asarray(p))
        top.append(np.asarray(t))
    return (np.concatenate(best), np.concatenate(picked, axis=1),
            np.concatenate(top))
