"""Training-substrate tests: trainer loop, checkpoint fault tolerance,
data-pipeline determinism, optimizer, gradient compression.
"""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import TrainHParams
from repro.data.pipeline import MemmapCorpus, Prefetcher, SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.train import checkpoint as ckpt
from repro.train.grad_compress import BLOCK, pad_to_block, _quant, _dequant
from repro.train.optimizer import adamw_init, adamw_update, lr_schedule
from repro.train.trainer import Trainer


HP = TrainHParams(lr=1e-3, warmup_steps=2, total_steps=50, microbatch=0,
                  remat="none", grad_compress=False)


def make_trainer(tmp, **kw):
    cfg = get_smoke_config("qwen2.5-3b")
    mesh = make_host_mesh()
    return Trainer(cfg, kw.pop("hp", HP), mesh, batch_per_step=4,
                   seq_len=32, ckpt_dir=str(tmp), ckpt_every=kw.pop(
                       "ckpt_every", 3), **kw)


# ---------------------------------------------------------------- loop


def test_trainer_loss_decreases(tmp_path):
    tr = make_trainer(tmp_path, ckpt_every=0)
    hist = tr.run(12, log_every=4)
    assert len(hist) >= 2
    first, last = hist[0][1], hist[-1][1]
    assert np.isfinite(first) and np.isfinite(last)
    assert last < first, f"loss did not fall: {first} -> {last}"


def test_trainer_checkpoint_restart_bit_identical(tmp_path):
    """Fault tolerance: kill after step 6, restart, final params match an
    uninterrupted 9-step run exactly (data pipeline is stateless)."""
    tr1 = make_trainer(tmp_path / "a", ckpt_every=3)
    tr1.run(9, log_every=100)
    p_full = jax.device_get(tr1.params)

    tr2 = make_trainer(tmp_path / "b", ckpt_every=3)
    tr2.run(6, log_every=100)
    del tr2
    tr3 = make_trainer(tmp_path / "b", resume=True)
    assert tr3.start_step == 6
    tr3.run(3, log_every=100)
    p_resumed = jax.device_get(tr3.params)

    for a, b in zip(jax.tree.leaves(p_full), jax.tree.leaves(p_resumed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trainer_microbatch_matches_full_batch(tmp_path):
    """Gradient accumulation must not change the math (mean of micro-grads
    == full-batch grad for a mean loss)."""
    hp_full = TrainHParams(lr=1e-3, warmup_steps=1, total_steps=10,
                           microbatch=0, remat="none")
    hp_micro = TrainHParams(lr=1e-3, warmup_steps=1, total_steps=10,
                            microbatch=2, remat="none")
    t_full = make_trainer(tmp_path / "f", hp=hp_full, ckpt_every=0)
    t_micro = make_trainer(tmp_path / "m", hp=hp_micro, ckpt_every=0)
    t_full.run(2, log_every=100)
    t_micro.run(2, log_every=100)
    for a, b in zip(jax.tree.leaves(jax.device_get(t_full.params)),
                    jax.tree.leaves(jax.device_get(t_micro.params))):
        # bf16 grads differ in the last bit between the two paths; Adam's
        # normalization amplifies that near zero — tolerance is absolute.
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-3, atol=5e-3)


# ---------------------------------------------------------------- ckpt


def test_checkpoint_atomicity(tmp_path):
    tree = {"w": jnp.arange(8.0), "b": {"x": jnp.ones((2, 2))}}
    d = str(tmp_path)
    ckpt.save(d, 1, tree)
    # A torn write (no _COMPLETE marker) must be invisible to readers.
    os.makedirs(os.path.join(d, "step_00000002", "arrays"))
    with open(os.path.join(d, "step_00000002", "meta.json"), "w") as f:
        f.write("{}")
    assert ckpt.latest_step(d) == 1
    restored, _ = ckpt.restore(d, 1, tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_shape_mismatch_raises(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, {"w": jnp.zeros((4,))})
    with pytest.raises(ValueError, match="shape"):
        ckpt.restore(d, 1, {"w": jnp.zeros((5,))})
    with pytest.raises(ValueError, match="missing"):
        ckpt.restore(d, 1, {"other": jnp.zeros((4,))})


def test_checkpoint_prune_keeps_newest(tmp_path):
    d = str(tmp_path)
    for s in (1, 2, 3, 4, 5):
        ckpt.save(d, s, {"w": jnp.zeros((2,))})
    ckpt.prune(d, keep=2)
    assert ckpt.latest_step(d) == 5
    left = sorted(x for x in os.listdir(d) if x.startswith("step_"))
    assert len(left) == 2


# ---------------------------------------------------------------- data


def test_pipeline_determinism_and_rank_disjointness():
    a = SyntheticLM(vocab=97, seq_len=16, batch_per_rank=4, seed=1, rank=0)
    b = SyntheticLM(vocab=97, seq_len=16, batch_per_rank=4, seed=1, rank=0)
    r1 = SyntheticLM(vocab=97, seq_len=16, batch_per_rank=4, seed=1, rank=1)
    np.testing.assert_array_equal(a.batch_at(7)["tokens"],
                                  b.batch_at(7)["tokens"])
    assert not np.array_equal(a.batch_at(7)["tokens"],
                              r1.batch_at(7)["tokens"])
    assert not np.array_equal(a.batch_at(7)["tokens"],
                              a.batch_at(8)["tokens"])
    assert a.batch_at(0)["tokens"].shape == (4, 16)


def test_prefetcher_order_and_restart():
    src = SyntheticLM(vocab=31, seq_len=8, batch_per_rank=2, seed=0)
    pf = Prefetcher(src, start_step=5, depth=2)
    try:
        s0, b0 = next(pf)
        s1, b1 = next(pf)
    finally:
        pf.stop()
    assert (s0, s1) == (5, 6)
    np.testing.assert_array_equal(b0["tokens"], src.batch_at(5)["tokens"])


def test_memmap_corpus(tmp_path):
    path = str(tmp_path / "toks.bin")
    np.arange(1000, dtype=np.int32).tofile(path)
    c = MemmapCorpus(path, seq_len=10, batch_per_rank=3)
    b = c.batch_at(0)["tokens"]
    assert b.shape == (3, 10)
    np.testing.assert_array_equal(b[0], np.arange(10))


# ---------------------------------------------------------------- optim


def test_adamw_descends_quadratic():
    hp = TrainHParams(lr=0.05, warmup_steps=0, total_steps=500,
                      grad_clip=10.0, weight_decay=0.0)
    params = {"x": jnp.array([3.0, -2.0])}
    state = adamw_init(params)
    for _ in range(200):
        grads = {"x": 2 * params["x"]}
        params, state, m = adamw_update(params, grads, state, hp)
    assert np.abs(np.asarray(params["x"])).max() < 0.5
    assert m["grad_norm"] > 0


def test_lr_schedule_shape():
    hp = TrainHParams(lr=1.0, warmup_steps=10, total_steps=100)
    assert float(lr_schedule(hp, 0)) == 0.0
    assert float(lr_schedule(hp, 10)) == pytest.approx(1.0)
    assert float(lr_schedule(hp, 100)) == pytest.approx(0.1)
    assert float(lr_schedule(hp, 55)) > float(lr_schedule(hp, 90))


# ---------------------------------------------------------------- compress


def test_quantization_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4 * BLOCK,)).astype(np.float32))
    q, s = _quant(x)
    err = np.asarray(_dequant(q, s) - x)
    # |err| per element <= scale/2 = max|block|/254
    bound = np.repeat(np.asarray(s), BLOCK) / 2 + 1e-6
    assert (np.abs(err) <= bound).all()


def test_pad_to_block():
    x = jnp.ones((BLOCK + 3,))
    padded, n = pad_to_block(x)
    assert padded.shape[0] % BLOCK == 0 and n == BLOCK + 3


def test_compressed_allreduce_single_device_exact():
    """On a 1-device axis the compressed all-reduce must be exact identity
    (and error feedback zero): the wire path is skipped."""
    from jax.sharding import PartitionSpec as P
    from repro.train.grad_compress import compressed_allreduce_flat

    mesh = jax.make_mesh((1,), ("data",))
    g = jnp.asarray(np.linspace(-1, 1, BLOCK), jnp.float32)
    e = jnp.zeros_like(g)
    fn = jax.shard_map(lambda a, b: compressed_allreduce_flat(a, b, "data"),
                   mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
                   check_vma=False)
    red, err = fn(g, e)
    # 1 device: ring skipped, result = dequant(quant(g)), err = g - that.
    np.testing.assert_allclose(np.asarray(red + err), np.asarray(g),
                               rtol=1e-6, atol=1e-6)
