"""Model families: the dense family gives what the harness gave before
it took families (dims, ``ModelConfig``, weights and the work of logged
calls, checked against values recorded then in
``data/dense_before_families.json``), the work of mover calls is priced
by the pool each moved, and the dense family refuses configurations of
other families."""

import dataclasses
import hashlib
import json
import pathlib
import types

import jax
import numpy as np
import pytest

import harness

CHIP = pathlib.Path(__file__).resolve().parents[1]
RECORDED = json.loads((CHIP / "tests" / "data"
                       / "dense_before_families.json").read_text())
SPEC = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
FILES = {c["name"]: CHIP.parents[1] / c["file"] for c in SPEC["configs"]}


def _cfg(name):
    return json.loads(FILES[name].read_text())


def digest_tree(tree) -> str:
    """sha256 over each leaf's path, dtype, shape and bytes, in order."""
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(RECORDED["configs"]))
def test_dense_family_gives_the_recorded_model(name):
    want = RECORDED["configs"][name]
    cfg = _cfg(name)
    fam = harness.family_of(cfg)
    dm = fam.dims(cfg)
    assert dataclasses.asdict(dm) == want["dims"]
    assert repr(fam.model_config(name, dm, 2048)) == want["model_config"]
    small = fam.dims(fam.smoke(cfg))
    for seed, w in want["smoke_weights"].items():
        assert dataclasses.asdict(small) == w["dims"]
        assert digest_tree(fam.model_weights(int(seed), small)) \
            == w["digest"], seed


def _log(js: dict) -> dict:
    return {"decode": [np.asarray(n) for n in js["decode"]],
            "prefill": [(np.asarray(a), b) for a, b in js["prefill"]],
            **{m: [tuple(e) for e in js[m]] for m in harness.MOVERS}}


@pytest.mark.parametrize("cell", sorted(RECORDED["logs"]))
def test_call_work_of_recorded_logs_is_the_recorded_work(cell):
    """Logs of a smoke run of each cell, mover calls tagged K (0) and V
    (1) in the order the engine makes them, priced at the configuration's
    own size: the same floats, to the last bit."""
    rec = RECORDED["logs"][cell]
    cfg = _cfg(rec["config"])
    fam = harness.family_of(cfg)
    got = harness.call_work(_log(rec["log"]), fam, fam.dims(cfg),
                            rec["page_tokens"])
    assert got == rec["call_work"]


def test_call_work_prices_each_pool_by_its_page_bytes():
    """A family whose K and V pages differ (as latent pages do, 576 and
    512 wide) has each mover call priced by the pool it moved."""
    cfg = _cfg("qwen2.5-3b")
    dense = harness.family_of(cfg)
    dm = dense.dims(cfg)
    k_page, v_page = 576 * 64 * 2, 512 * 64 * 2
    fam = types.SimpleNamespace(**{
        f: getattr(dense, f) for f in ("decode_flops", "decode_bytes",
                                       "prefill_flops")},
        pool_page_bytes=lambda dm, ptok: (k_page, v_page))
    log = {"decode": [], "prefill": [],
           "page_gather": [(0, 3), (1, 3)], "page_scatter": [(0, 5), (1, 5)],
           "page_compact": [(0, 2), (1, 2), (0, 7), (1, 7)]}
    work = harness.call_work(log, fam, dm, 64)
    assert work["mover_bytes"] == 2 * (3 + 5 + 2 + 7) * (k_page + v_page)
    only_v = dict(log, page_gather=[(1, 3)], page_scatter=[],
                  page_compact=[])
    assert harness.call_work(only_v, fam, dm, 64)["mover_bytes"] \
        == 2 * 3 * v_page


# DeepSeek-V2-Lite's config.json (huggingface.co/deepseek-ai/
# DeepSeek-V2-Lite): latent attention, 64 routed experts, YaRN positions.
DEEPSEEK_V2_LITE = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10944,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "greedy", "v_head_dim": 128,
    "vocab_size": 102400, "torch_dtype": "bfloat16"}

PHI3 = _cfg("phi3-mini-3.8b")
FOREIGN = {
    "DeepSeek-V2-Lite": (DEEPSEEK_V2_LITE,
                         ("kv_lora_rank", "n_routed_experts", "rope_scaling")),
    "latent attention": (dict(PHI3, kv_lora_rank=512), ("kv_lora_rank",)),
    "routed experts": (dict(PHI3, n_routed_experts=64),
                       ("n_routed_experts",)),
    "local experts": (dict(PHI3, num_local_experts=8),
                      ("num_local_experts",)),
    "yarn": (dict(PHI3, rope_scaling=DEEPSEEK_V2_LITE["rope_scaling"]),
             ("rope_scaling",)),
    "gelu": (dict(PHI3, hidden_act="gelu"), ("hidden_act",)),
}


@pytest.mark.parametrize("case", sorted(FOREIGN))
def test_dense_family_refuses_other_families_configs(case):
    cfg, named = FOREIGN[case]
    with pytest.raises(ValueError) as e:
        harness.family_of({}).dims(cfg)
    for key in named:
        assert key in str(e.value)
