"""The dense family: decoder-only transformers with grouped-query (or
multi-head) attention, rotary positions without scaling, and a SwiGLU
MLP; Qwen2 and Phi-3 are of it.

A family module is what the harness knows of a model family.  A
configuration file names its family under ``family`` (this one where the
key is absent), and the harness loads ``families/<family>.py``, which
gives:

- ``dims(cfg)``: the sizes the harness and the reference need, frozen
  and hashable (the static argument of the jitted calls), with a
  ``vocab`` that the traffic draws its token ids from, and with what a
  chip's share holds where the configuration is cut to one (experts or
  vocabulary held here, keys the benchmark lists under ``reduced``);
- ``model_config(name, dims, max_seq)``: the program's ``ModelConfig``;
- ``model_weights(seed, dims)``: the program's parameter tree, made on
  the device from the seed by the per-layer generator that the family's
  reference calls again;
- ``decode_flops(dims, contexts)``, ``decode_bytes(dims, contexts)`` and
  ``prefill_flops(dims, tokens)``: the work of the model's calls;
- ``pool_page_bytes(dims, page_tokens)``: one page's bytes in each of the
  program's KV pools, in the order of ``ServingEngine.pools``;
- ``smoke(cfg)``: the configuration cut to the size the CPU tests run.

The reference is named by the configuration's ``reference`` key.  Here
the weights are ``weights.py``'s, the work ``cost.py``'s and the
reference ``references/dense_gqa.py``.
"""

from __future__ import annotations

from cost import decode_bytes, decode_flops, page_bytes, prefill_flops
from weights import Dims, model_weights
from repro.configs.base import ModelConfig

__all__ = ["dims", "model_config", "model_weights", "decode_flops",
           "decode_bytes", "prefill_flops", "pool_page_bytes", "smoke"]

# Keys of a published config.json that name computation this family does
# not have: latent attention, experts, scaled or partial rotary positions.
NOT_MODELED = (
    "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "n_routed_experts", "n_shared_experts",
    "num_local_experts", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "first_k_dense_replace", "rope_scaling",
    "partial_rotary_factor")


def dims(cfg: dict) -> Dims:
    """The sizes of ``cfg``; a config with a key this family does not
    model is refused, never run as dense."""
    found = [k for k in NOT_MODELED if cfg.get(k) is not None]
    if cfg.get("hidden_act", "silu") != "silu":
        found.append("hidden_act")
    if found:
        raise ValueError(
            f"the dense family does not model {', '.join(found)}: give the "
            f"config the 'family' key of a family that does")
    return Dims.of(cfg)


def model_config(name: str, dm: Dims, max_seq: int) -> ModelConfig:
    return ModelConfig(
        name=name, family="dense", n_layers=dm.n_layers,
        d_model=dm.d_model, n_heads=dm.n_heads, n_kv_heads=dm.n_kv_heads,
        d_ff=dm.d_ff, vocab_size=dm.vocab, head_dim=dm.head_dim,
        qkv_bias=dm.qkv_bias, rope_theta=dm.rope_theta,
        norm_eps=dm.norm_eps, tie_embeddings=dm.tied, max_seq_len=max_seq,
        dtype=dm.dtype)


def pool_page_bytes(dm: Dims, page_tokens: int) -> tuple:
    """K and V pages are alike."""
    return (page_bytes(dm, page_tokens),) * 2


def smoke(cfg: dict) -> dict:
    """Two layers of width 64 with 4 heads, keeping grouped-query
    attention grouped and multi-head attention multi-head."""
    gqa = cfg["num_key_value_heads"] < cfg["num_attention_heads"]
    return dict(cfg, hidden_size=64, num_attention_heads=4,
                num_key_value_heads=2 if gqa else 4, num_hidden_layers=2,
                intermediate_size=128, vocab_size=512)
