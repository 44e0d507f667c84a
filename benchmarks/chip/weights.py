"""Seeded random weights of a dense GQA transformer, made on the device.

The benchmark makes the weights itself, so that the reference can make
the very same ones without taking anything from the program under test.
Each decoder layer's leaves come from one key per layer; the whole model
is one jitted call that maps the per-layer generator over the layer keys,
and the reference calls the same generator for a single layer.  Leaves
are in the served dtype, laid out as the program's parameter tree
(``embed``, ``final_norm``, optional ``unembed``, and ``decoder`` with
layer-stacked ``ln1``, ``ln2``, ``attn`` and ``mlp``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a dense GQA transformer, read from a config file."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    tied: bool
    rope_theta: float
    norm_eps: float
    dtype: str

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        bias = cfg.get("attention_bias",
                       cfg.get("assumed", {}).get("attention_bias", False))
        return cls(n_layers=cfg["num_hidden_layers"], d_model=d, n_heads=h,
                   n_kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg.get("head_dim") or d // h,
                   d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                   qkv_bias=bool(bias), tied=bool(cfg["tie_word_embeddings"]),
                   rope_theta=float(cfg["rope_theta"]),
                   norm_eps=float(cfg["rms_norm_eps"]),
                   dtype=cfg["torch_dtype"])


def seed_words(seed: int):
    """A non-negative seed, also one wider than 32 bits, as two int32
    words: passed as data, so that a new seed compiles nothing."""
    return jnp.asarray([seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF],
                       jnp.int32)


def root_key(words):
    return jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def layer_weights(key, dm: Dims):
    """One decoder layer's leaves, in the served dtype."""
    dt = jnp.dtype(dm.dtype)
    d, H, kv, dh, f = (dm.d_model, dm.n_heads, dm.n_kv_heads, dm.head_dim,
                       dm.d_ff)
    ks = jax.random.split(key, 12)
    attn = {
        "wq": _normal(ks[0], (d, H, dh), d ** -0.5, dt),
        "wk": _normal(ks[1], (d, kv, dh), d ** -0.5, dt),
        "wv": _normal(ks[2], (d, kv, dh), d ** -0.5, dt),
        "wo": _normal(ks[3], (H, dh, d), (H * dh) ** -0.5, dt),
    }
    if dm.qkv_bias:
        attn["bq"] = _normal(ks[4], (H, dh), 0.1, dt)
        attn["bk"] = _normal(ks[5], (kv, dh), 0.1, dt)
        attn["bv"] = _normal(ks[6], (kv, dh), 0.1, dt)
    return {
        "ln1": (1.0 + _normal(ks[7], (d,), 0.1, jnp.float32)).astype(dt),
        "ln2": (1.0 + _normal(ks[8], (d,), 0.1, jnp.float32)).astype(dt),
        "attn": attn,
        "mlp": {"w_gate": _normal(ks[9], (d, f), d ** -0.5, dt),
                "w_up": _normal(ks[10], (d, f), d ** -0.5, dt),
                "w_down": _normal(ks[11], (f, d), f ** -0.5, dt)},
    }


def layer_key(words, layer):
    return jax.random.fold_in(jax.random.fold_in(root_key(words), 1), layer)


def outer_weights(words, dm: Dims):
    """Embedding, final norm and (untied) output matrix."""
    dt = jnp.dtype(dm.dtype)
    k = jax.random.fold_in(root_key(words), 0)
    ks = jax.random.split(k, 3)
    # Tied: logits = h @ embed.T, so the scale keeps the logits near 1.
    scale = dm.d_model ** -0.5 if dm.tied else 1.0
    out = {"embed": _normal(ks[0], (dm.vocab, dm.d_model), scale, dt),
           "final_norm": (1.0 + _normal(ks[1], (dm.d_model,), 0.1,
                                        jnp.float32)).astype(dt)}
    if not dm.tied:
        out["unembed"] = _normal(ks[2], (dm.d_model, dm.vocab),
                                 dm.d_model ** -0.5, dt)
    return out


@functools.partial(jax.jit, static_argnums=1)
def _model_weights(words, dm: Dims):
    keys = jax.vmap(lambda i: layer_key(words, i))(jnp.arange(dm.n_layers))
    p = outer_weights(words, dm)
    p["decoder"] = jax.vmap(lambda k: layer_weights(k, dm))(keys)
    return p


def model_weights(seed: int, dm: Dims):
    """The whole parameter tree on the default device, in one jitted call."""
    return _model_weights(seed_words(seed), dm)
