"""Training engine: pjit train_step builder + fault-tolerant loop.

``make_train_step`` assembles the jitted step for a given (LM, mesh, hp):
  * gradient accumulation (``hp.microbatch``) via ``lax.scan`` over
    microbatches (sequential, activation memory = one microbatch);
  * per-block remat (``hp.remat``);
  * ZeRO-1: optimizer moments sharded with data-extended specs — XLA
    inserts the reduce-scatter / all-gather pair around the update;
  * optional int8 ring all-reduce of gradients with error feedback
    (``hp.grad_compress``) via shard_map over the data axes;
  * donation of params/opt state (in-place update at scale).

``Trainer`` runs the loop with checkpoint/restart (atomic, elastic),
SIGTERM-safe preemption handling, and step-time stats.
"""

from __future__ import annotations

import signal
import time
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, TrainHParams
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.launch.sharding import fsdp_specs, param_specs, zero1_specs
from repro.models.common import batch_axes, set_batch_axes
from repro.models.lm import LM
from repro.train import checkpoint as ckpt
from repro.train.grad_compress import (
    BLOCK,
    compressed_allreduce_flat,
    pad_to_block,
)
from repro.train.optimizer import adamw_init, adamw_update


def configure_parallelism(hp: TrainHParams) -> None:
    """Set batch-axes + TP-mode contexts for this run.

    'megatron': explicit shard_map TP blocks (bf16 psums).
    'auto':     GSPMD auto-sharding from shd() hints (the naive baseline
                kept for §Perf before/after).
    'fsdp':     every axis data-parallel, ZeRO-3 weight streaming.
    """
    from repro.models.common import set_tp_mode
    set_batch_axes(("pod", "data", "model") if hp.parallelism == "fsdp"
                   else ("pod", "data"))
    set_tp_mode("auto" if hp.parallelism == "auto" else "explicit")


def batch_spec(mesh) -> P:
    dp = tuple(a for a in batch_axes() if a in mesh.axis_names)
    return P(dp if dp else None)


def state_specs(params, hp: TrainHParams, mesh):
    """(param specs, optimizer-moment specs) for the chosen parallelism."""
    if hp.parallelism == "fsdp":
        f = fsdp_specs(params, mesh)
        return f, f
    return zero1_specs(params, mesh), zero1_specs(params, mesh)


def _accum_grads(loss_fn, params, batch, n_micro: int, accum_specs=None):
    """Gradient accumulation over ``n_micro`` sequential microbatches.

    ``accum_specs``: sharding for the running gradient sum.  Must NOT be
    data-extended (ZeRO-1) — that would force a cross-data reduce-scatter
    *per microbatch*; with TP-only specs each iteration adds local
    partial grads and the data reduction happens once, at the optimizer
    (EXPERIMENTS.md §Perf iteration 5).
    """
    B = batch["tokens"].shape[0]
    assert B % n_micro == 0
    mb = B // n_micro
    sliced = {k: v.reshape(n_micro, mb, *v.shape[1:])
              for k, v in batch.items()}

    def pin(tree):
        if accum_specs is None:
            return tree
        return jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(g, s),
            tree, accum_specs, is_leaf=lambda x: not isinstance(
                x, (dict, list, tuple)))

    def body(carry, micro):
        gsum, lsum = carry
        (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, micro)
        gsum = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), gsum, g)
        return (pin(gsum), lsum + loss), aux

    g0 = pin(jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                          params))
    (gsum, lsum), auxs = jax.lax.scan(body, (g0, jnp.float32(0.0)), sliced)
    g = jax.tree.map(lambda a: a / n_micro, gsum)
    return lsum / n_micro, g, jax.tree.map(lambda a: a[-1], auxs)


def make_train_step(lm: LM, hp: TrainHParams, mesh):
    """Returns (step_fn, init_fn, shardings dict)."""
    configure_parallelism(hp)
    remat_arg = {"none": False, "block": True}.get(hp.remat, hp.remat)

    # Compute-layout pins (EXPERIMENTS.md §Perf MoE iterations 2-3):
    #  * params are cast to bf16 ONCE per step, pinned to the TP-only
    #    layout — the ZeRO'd master copy is then gathered over data a
    #    single time outside the layer scan instead of per layer (and
    #    again per remat recompute);
    #  * grads are pinned to the ZeRO layout so the data-axis reduction
    #    lowers as a reduce-scatter (half the wire of the all-reduce XLA
    #    otherwise picks).
    compute_shardings = grad_shardings = None
    if mesh is not None and hp.parallelism != "fsdp":
        from jax.sharding import NamedSharding as _NS
        abs_params = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
        compute_shardings = jax.tree.map(
            lambda s: _NS(mesh, s), param_specs(abs_params, mesh),
            is_leaf=lambda x: isinstance(x, P))
        grad_shardings = jax.tree.map(
            lambda s: _NS(mesh, s), zero1_specs(abs_params, mesh),
            is_leaf=lambda x: isinstance(x, P))

    def loss_fn(params, batch):
        if compute_shardings is not None:
            from repro.models.common import cast as _cast
            params = _cast(params, jnp.dtype(lm.cfg.dtype))
            params = jax.tree.map(jax.lax.with_sharding_constraint,
                                  params, compute_shardings)
        return lm.loss(params, batch, remat=remat_arg)

    dp_axes = tuple(a for a in batch_axes() if a in mesh.axis_names)

    # NOTE: pinning the accumulator to TP-only specs was measured WORSE
    # (a replicated-over-data constraint all-reduces every microbatch's
    # grads; GSPMD cannot carry pending-reduction partials across scan
    # iterations) — see §Perf iteration 5 (refuted).  The accumulator
    # inherits the optimizer sharding; prefer microbatch=0 when HBM
    # allows.
    def train_step(params, opt_state, ef, batch):
        if hp.microbatch and hp.microbatch > 1:
            loss, grads, aux = _accum_grads(loss_fn, params, batch,
                                            hp.microbatch)
        else:
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        if hp.grad_compress and dp_axes and ef is not None:
            grads, ef = _compress_grads(grads, ef, mesh, dp_axes)
        if grad_shardings is not None:
            grads = jax.tree.map(jax.lax.with_sharding_constraint,
                                 grads, grad_shardings)
        new_params, new_opt, om = adamw_update(params, grads, opt_state, hp)
        metrics = {"loss": loss, **{k: aux[k] for k in ("nll", "aux")},
                   **om}
        return new_params, new_opt, ef, metrics

    def init_fn(key):
        params = lm.init(key)
        opt = adamw_init(params)
        ef = None
        if hp.grad_compress and dp_axes:
            from repro.train.grad_compress import padded_size
            n_dev = 1
            for a in dp_axes:
                n_dev *= mesh.shape[a]
            ef = jax.tree.map(
                lambda p: jnp.zeros((padded_size(p.size, n_dev),),
                                    jnp.float32), params)
        return params, opt, ef

    return train_step, init_fn


def _compress_grads(grads, ef, mesh, dp_axes):
    """int8 ring all-reduce over the data axes, per leaf, error feedback."""
    axis = dp_axes[0] if len(dp_axes) == 1 else dp_axes
    n_dev = 1
    for a in dp_axes:
        n_dev *= mesh.shape[a]

    def one(g, e):
        flat, n = pad_to_block(g.astype(jnp.float32), BLOCK * n_dev)

        def local(fl, el):
            red, e_new = compressed_allreduce_flat(
                fl, el, axis if isinstance(axis, str) else axis[0])
            return red, e_new

        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False)
        red, e_new = fn(flat, e)
        return red[:n].reshape(g.shape), e_new

    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = treedef.flatten_up_to(ef)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return (treedef.unflatten([o[0] for o in out]),
            treedef.unflatten([o[1] for o in out]))


class Trainer:
    """Fault-tolerant training loop.

    * checkpoint every ``ckpt_every`` steps (atomic; pruned to 3);
    * SIGTERM/SIGINT → finish current step, checkpoint, exit cleanly
      (preemption handling for spot/maintenance events);
    * restart: ``Trainer(..., resume=True)`` restores the newest complete
      checkpoint, re-sharding onto the current mesh (elastic).
    """

    def __init__(self, cfg: ModelConfig, hp: TrainHParams, mesh,
                 batch_per_step: int, seq_len: int,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
                 resume: bool = False, seed: int = 0):
        self.cfg, self.hp, self.mesh = cfg, hp, mesh
        self.lm = LM(cfg)
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self._preempted = False
        self.data = SyntheticLM(cfg.vocab_size, seq_len, batch_per_step,
                                seed=seed)

        step_fn, init_fn = make_train_step(self.lm, hp, mesh)
        with jax.sharding.set_mesh(mesh):
            params, opt, ef = init_fn(jax.random.PRNGKey(seed))
            pspec, mspec = state_specs(params, hp, mesh)
            ospec = {"step": P(), "mu": mspec, "nu": mspec}
            pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec)
            oshard = jax.tree.map(lambda s: NamedSharding(mesh, s), ospec,
                                  is_leaf=lambda x: isinstance(x, P))
            self.params = jax.device_put(params, pshard)
            self.opt = jax.device_put(opt, oshard)
            self.ef = ef
            bs = NamedSharding(mesh, batch_spec(mesh))
            self._bs = bs
            self.step_fn = jax.jit(
                step_fn,
                in_shardings=(pshard, oshard, None, bs),
                out_shardings=(pshard, oshard, None, None),
                donate_argnums=(0, 1),
            )
        self.start_step = 0
        if resume and ckpt_dir:
            s = ckpt.latest_step(ckpt_dir)
            if s is not None:
                state, extra = ckpt.restore(
                    ckpt_dir, s, {"params": self.params, "opt": self.opt},
                    shardings={"params": pshard, "opt": oshard})
                self.params, self.opt = state["params"], state["opt"]
                self.start_step = s
        signal.signal(signal.SIGTERM, self._on_preempt)

    def _on_preempt(self, *_):
        self._preempted = True

    def save(self, step: int):
        if self.ckpt_dir:
            ckpt.save(self.ckpt_dir, step,
                      {"params": self.params, "opt": self.opt},
                      extra={"data": self.data.state(step)})
            ckpt.prune(self.ckpt_dir)

    def run(self, n_steps: int, log_every: int = 10):
        history = []
        pf = Prefetcher(self.data, start_step=self.start_step)
        try:
            with jax.sharding.set_mesh(self.mesh):
                t0 = time.time()
                for i in range(self.start_step, self.start_step + n_steps):
                    step, batch = next(pf)
                    batch = {k: jax.device_put(v, self._bs)
                             for k, v in batch.items()}
                    self.params, self.opt, self.ef, m = self.step_fn(
                        self.params, self.opt, self.ef, batch)
                    if (i + 1) % log_every == 0 or i == self.start_step:
                        loss = float(m["loss"])
                        dt = (time.time() - t0) / max(
                            1, i + 1 - self.start_step)
                        history.append((i + 1, loss))
                        print(f"step {i+1}: loss={loss:.4f} "
                              f"gnorm={float(m['grad_norm']):.3f} "
                              f"{dt*1e3:.0f} ms/step", flush=True)
                    if self.ckpt_every and (i + 1) % self.ckpt_every == 0:
                        self.save(i + 1)
                    if self._preempted:
                        self.save(i + 1)
                        print(f"preempted at step {i+1}; checkpointed.",
                              flush=True)
                        break
        finally:
            pf.stop()
        return history
