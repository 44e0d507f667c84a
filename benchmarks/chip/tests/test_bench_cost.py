"""The benchmark's cost functions, peaks table and seeded weights, for
each configuration through its family."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cost
import harness
import weights as W
from repro.models.common import cast
from repro.models.lm import LM

CHIP = pathlib.Path(__file__).resolve().parents[1]
CONFIGS = sorted((CHIP / "configs").glob("*.json"))


def _cfg(path):
    return json.loads(path.read_text())


def _dims(path):
    cfg = _cfg(path)
    return harness.family_of(cfg).dims(cfg)


DENSE = [p for p in CONFIGS
         if harness.family_of(_cfg(p)) is harness.family_of({})]


@pytest.mark.parametrize("path", DENSE, ids=lambda p: p.stem)
def test_matrix_bytes_equal_the_programs_param_count(path):
    dm = _dims(path)
    mcfg = harness.family_of({}).model_config(path.stem, dm, 2048)
    assert cost.matrix_params(dm) * 2 == mcfg.param_count() * 2
    assert cost.weight_bytes(dm) == 2 * (mcfg.param_count()
                                         + cost.vector_params(dm))


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_family_weights_are_the_programs_tree(path):
    """The tree the family makes has the leaves, shapes and served dtype
    of the program's own, at the configuration's size (shapes only)."""
    fam = harness.family_of(_cfg(path))
    dm = _dims(path)
    mcfg = fam.model_config(path.stem, dm, 2048)
    dt = jnp.dtype(mcfg.dtype)
    ours = jax.eval_shape(lambda: fam.model_weights(2**31 + 5, dm))
    theirs = jax.eval_shape(lambda k: cast(LM(mcfg).init(k), dt),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, dt)


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_pool_page_bytes_are_the_programs_pages(path):
    """One entry per pool the program keeps, each a page of that pool
    over every layer."""
    fam = harness.family_of(_cfg(path))
    dm = _dims(path)
    mcfg = fam.model_config(path.stem, dm, 2048)
    dt = jnp.dtype(mcfg.dtype)
    pools = LM(mcfg).pool_shapes(1, 64, dt)
    assert list(fam.pool_page_bytes(dm, 64)) == [
        int(np.prod(s.shape)) * dt.itemsize for s in pools]


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_costs_grow_with_the_work(path):
    fam = harness.family_of(_cfg(path))
    dm = _dims(path)
    for f in (fam.decode_flops, fam.decode_bytes):
        assert 0 < f(dm, [100]) < f(dm, [100, 1]) < f(dm, [100, 300])
        assert f(dm, [100, 300]) < f(dm, [101, 300])
    assert 0 < fam.prefill_flops(dm, 1) < fam.prefill_flops(dm, 1024)


def test_published_sizes():
    q = _dims(CHIP / "configs" / "qwen2.5-3b.json")
    p = _dims(CHIP / "configs" / "phi3-mini-3.8b.json")
    assert cost.kv_bytes_per_token(q) == 36_864
    assert cost.kv_bytes_per_token(p) == 393_216
    assert round(cost.weight_bytes(q) / 1e9, 2) == 6.17
    assert round(cost.weight_bytes(p) / 1e9, 2) == 7.64


def test_unknown_device_kind_raises():
    assert cost.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        cost.peaks("TPU v9 imaginary")


def test_roofline_share_takes_the_binding_bound():
    pk = cost.peaks("TPU v5 lite")
    assert cost.roofline_share(197e12, 0.0, 2.0, pk) == pytest.approx(50.0)
    assert cost.roofline_share(1.0, 819e9, 4.0, pk) == pytest.approx(25.0)


def test_decode_counts_only_valid_context():
    dm = _dims(CHIP / "configs" / "qwen2.5-3b.json")
    one = cost.decode_bytes(dm, [100])
    two = cost.decode_bytes(dm, [100, 300])
    assert two - one == pytest.approx(300 * cost.kv_bytes_per_token(dm))


@pytest.mark.parametrize("seed", [0, 2**31 + 7, 2**33 + 1])
def test_whole_model_weights_equal_the_per_layer_ones(seed):
    dm = W.Dims(n_layers=3, d_model=32, n_heads=4, n_kv_heads=2,
                head_dim=8, d_ff=48, vocab=64, qkv_bias=True, tied=False,
                rope_theta=1e4, norm_eps=1e-6, dtype="bfloat16")
    whole = W.model_weights(seed, dm)
    words = W.seed_words(seed)
    for layer in range(dm.n_layers):
        one = W.layer_weights(W.layer_key(words, layer), dm)
        for (path, a), b in zip(
                jax.tree_util.tree_leaves_with_path(one),
                jax.tree.leaves(jax.tree.map(lambda x: x[layer],
                                             whole["decoder"]))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=str(path))
    other = W.model_weights(seed + 1, dm)
    assert not np.array_equal(np.asarray(whole["embed"]),
                              np.asarray(other["embed"]))
