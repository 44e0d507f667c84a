"""Operations and bytes the dense family's calls need, and the chip
peaks and roofline share that every family shares.

Computed from a configuration's shapes alone (``weights.Dims``), never
from the program.  A matmul of an [m, k] by [k, n] matrix is 2·m·k·n
operations; bytes are those the work must read or write once, in the
served dtype.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from weights import Dims

# Published peaks per device kind, as JAX names the device.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 819 GB/s HBM bandwidth per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to cost.PEAKS")
    return PEAKS[device_kind]


def itemsize(dm: Dims) -> int:
    return jnp.dtype(dm.dtype).itemsize


def layer_matrix_params(dm: Dims) -> int:
    d, H, kv, dh, f = (dm.d_model, dm.n_heads, dm.n_kv_heads, dm.head_dim,
                       dm.d_ff)
    return d * (H + 2 * kv) * dh + H * dh * d + 3 * d * f


def matrix_params(dm: Dims) -> int:
    """Parameters of the matrices: layers, embedding, output matrix."""
    emb = dm.vocab * dm.d_model * (1 if dm.tied else 2)
    return emb + dm.n_layers * layer_matrix_params(dm)


def vector_params(dm: Dims) -> int:
    """Norm weights and q/k/v biases."""
    per = 2 * dm.d_model
    if dm.qkv_bias:
        per += (dm.n_heads + 2 * dm.n_kv_heads) * dm.head_dim
    return dm.n_layers * per + dm.d_model


def weight_bytes(dm: Dims) -> int:
    return (matrix_params(dm) + vector_params(dm)) * itemsize(dm)


def kv_bytes_per_token(dm: Dims) -> int:
    return 2 * dm.n_layers * dm.n_kv_heads * dm.head_dim * itemsize(dm)


def _per_token_flops(dm: Dims) -> int:
    """Matmul operations per token through the layers (no attention
    scores, no output matrix)."""
    return 2 * dm.n_layers * layer_matrix_params(dm)


def _attn_flops(dm: Dims, keys_seen: float) -> float:
    """QK^T and PV, given the keys each query attends to summed over the
    queries."""
    return 4.0 * dm.n_layers * dm.n_heads * dm.head_dim * keys_seen


def decode_flops(dm: Dims, contexts) -> float:
    """One decode step over real rows whose context lengths (tokens
    attended, the new one included) are ``contexts``."""
    rows = len(contexts)
    head = 2 * dm.d_model * dm.vocab
    return rows * (_per_token_flops(dm) + head) \
        + _attn_flops(dm, float(np.sum(contexts)))


def decode_bytes(dm: Dims, contexts) -> float:
    """Weights read once, the valid KV of every real row read, and one
    token's KV written per row.  The embedding rows looked up are
    negligible and left out where the output matrix is separate."""
    kv = kv_bytes_per_token(dm)
    w = weight_bytes(dm)
    if not dm.tied:
        w -= dm.vocab * dm.d_model * itemsize(dm)
    # The new token's KV is written, the rest read: one token each.
    return w + kv * float(np.sum(contexts))


def prefill_flops(dm: Dims, prompt_tokens: int) -> float:
    """A prompt of ``prompt_tokens`` real tokens, causal, logits at the
    last position only."""
    T = prompt_tokens
    return T * _per_token_flops(dm) + 2 * dm.d_model * dm.vocab \
        + _attn_flops(dm, T * (T + 1) / 2.0)


def page_bytes(dm: Dims, page_tokens: int) -> int:
    """One page of K or V across every layer."""
    return dm.n_layers * page_tokens * dm.n_kv_heads * dm.head_dim \
        * itemsize(dm)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   pk: dict) -> float:
    """Least time the chip could take, over the time taken, in %."""
    least = max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / seconds
