"""Mixture-of-Experts: top-k router + capacity-based scatter dispatch.

Used by deepseek-v2-lite (64 routed top-6 + 2 shared, first layer dense)
and dbrx (16 routed top-4).  Dispatch is scatter/gather-based (GShard-style
capacity buffers without the O(S·E·C) one-hot einsum): tokens are placed
into per-expert capacity slots, experts run as one batched matmul sharded
over the ``model`` axis (EP), and outputs gather back with gate weights.

Distribution: the scatter/gather dispatch uses *batched indices*, which
GSPMD cannot partition — left to XLA's auto-spmd it materializes the
dispatch tensors at GLOBAL batch (f32[B_global, T, K, d]) and all-reduces
them every layer (~300 GB/layer/chip at deepseek-v2-lite train_4k scale;
see EXPERIMENTS.md §Perf iteration 1).  We therefore run the whole block
inside ``shard_map``: batch over the data axes, experts over ``model``.
Every scatter/gather is then shard-local; the only collective is one
bf16 ``psum`` of the combined output over ``model`` (the Megatron-style
row-parallel reduction), plus a tiny psum for the aux loss.

Capacity per sequence: C = ceil(T · top_k / E · capacity_factor); overflow
tokens are dropped (standard GShard semantics) via out-of-bounds scatter
indices.  Router runs in fp32.  A Switch-style load-balance aux loss is
returned.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.common import (
    BATCH as DP,   # batch sentinel (see common.shd)
    batch_axes,
    dense_init,
    serving_mode,
    shd,
    split_keys,
)


def ep2d_geometry(cfg: ModelConfig, mesh):
    """2D expert-parallel geometry for *serving*, or None.

    Storage: experts over 'data', expert-hidden over 'model' — per-chip
    expert bytes P_exp/(data*model), which is what lets dbrx-132b's 254 GB
    of experts fit 16 GB chips (EXPERIMENTS.md §Dry-run).  Returns
    (E_loc, fe_loc).
    """
    mo = cfg.moe
    if mo is None or mesh is None:
        return None
    d_sz = mesh.shape.get("data", 1)
    tp = mesh.shape.get("model", 1)
    if d_sz <= 1 or mo.n_experts % d_sz or mo.d_expert % tp:
        return None
    return mo.n_experts // d_sz, mo.d_expert // tp


def init_moe_params(key, cfg: ModelConfig, L: int) -> Dict[str, Any]:
    mo = cfg.moe
    d, fe, E = cfg.d_model, mo.d_expert, mo.n_experts
    ks = split_keys(key, 7)
    p = {
        "router": dense_init(ks[0], (L, d, E), in_axis=1),
        "w_gate": dense_init(ks[1], (L, E, d, fe), in_axis=2),
        "w_up": dense_init(ks[2], (L, E, d, fe), in_axis=2),
        "w_down": dense_init(ks[3], (L, E, fe, d), in_axis=2),
    }
    if mo.n_shared:
        fs = mo.n_shared * fe
        p["ws_gate"] = dense_init(ks[4], (L, d, fs), in_axis=1)
        p["ws_up"] = dense_init(ks[5], (L, d, fs), in_axis=1)
        p["ws_down"] = dense_init(ks[6], (L, fs, d), in_axis=1)
    return p


def capacity(S: int, E: int, top_k: int, cf: float) -> int:
    return max(1, math.ceil(S * top_k / E * cf))


def _ambient_mesh():
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _moe_routed(cfg: ModelConfig, p, x, e0, E_local, axes):
    """Routed-expert block over this shard's expert slice [e0, e0+E_local).

    x [G, S, d] (this shard's batch rows, replicated over ``model``).
    All scatters/gathers are local; OOB indices drop.  Returns the
    *partial* output (psum over ``axes`` pending) and the local aux stats.
    """
    mo = cfg.moe
    G, S, d = x.shape
    E, K = mo.n_experts, mo.top_k
    C = capacity(S, E, K, mo.capacity_factor)

    logits = jnp.einsum("gsd,de->gse", x, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, sel = jax.lax.top_k(probs, K)                   # [G,S,K]
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance stats (combined across shards by caller).
    me = probs.mean(axis=(0, 1))                           # [E]
    fe = jnp.zeros((E,), jnp.float32).at[sel.reshape(-1)].add(
        1.0 / sel.size)

    # Position of each (token, k) within its expert's capacity buffer.
    onehot = jax.nn.one_hot(sel, E, dtype=jnp.int32).sum(2)  # [G,S,E]
    cum = jnp.cumsum(onehot, axis=1)                         # inclusive
    pos = jnp.take_along_axis(cum, sel, axis=2) - 1          # [G,S,K]
    keep = pos < C

    # Local experts only: shift sel into [0, E_local); overflow and
    # remote-expert entries go out of bounds and are dropped.
    sel_l = jnp.where(keep, sel - e0, E_local)
    pos_l = jnp.where(keep, pos, C)
    g_idx = jnp.arange(G)[:, None, None]
    xs = jnp.zeros((G, E_local, C, d), x.dtype)
    xs = xs.at[g_idx, sel_l, pos_l].add(
        x[:, :, None, :] * keep[..., None].astype(x.dtype), mode="drop")

    # Expert FFN (SwiGLU), batched over this shard's experts.
    h = jnp.einsum("gecd,edf->gecf", xs, p["w_gate"])
    u = jnp.einsum("gecd,edf->gecf", xs, p["w_up"])
    h = jax.nn.silu(h.astype(jnp.float32)).astype(x.dtype) * u
    ys = jnp.einsum("gecf,efd->gecd", h, p["w_down"])

    # Gather back: OOB (remote/overflow) reads fill 0 -> partial sum.
    out_k = ys.at[g_idx, sel_l, pos_l].get(mode="fill", fill_value=0)
    w = (gates * keep).astype(x.dtype)
    y = jnp.einsum("gskd,gsk->gsd", out_k, w)
    return y, me, fe


def _moe_shared(cfg: ModelConfig, p, x):
    """Always-on shared experts (plain TP SwiGLU over the hidden dim)."""
    g = jnp.einsum("gsd,df->gsf", x, p["ws_gate"])
    u2 = jnp.einsum("gsd,df->gsf", x, p["ws_up"])
    hs = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u2
    return jnp.einsum("gsf,fd->gsd", hs, p["ws_down"])


def moe_block(cfg: ModelConfig, p, x) -> Tuple[jax.Array, jax.Array]:
    """x [B, T, d] -> (y [B, T, d], aux load-balance loss scalar)."""
    mo = cfg.moe
    E = mo.n_experts
    mesh = _ambient_mesh()

    # Local/auto path: no mesh, no model axis, or fsdp mode ('model' is a
    # batch axis: experts stay replicated and FSDP streams their weights).
    if (mesh is None or "model" not in mesh.axis_names
            or "model" in batch_axes()):
        y, me, fe = _moe_routed(cfg, p, x, 0, E, ())
        if mo.n_shared:
            y = y + _moe_shared(cfg, p, x)
        return y, E * jnp.sum(me * fe)

    if serving_mode() and ep2d_geometry(cfg, mesh) is not None:
        return _moe_block_serving(cfg, p, x, mesh)

    tp = mesh.shape["model"]
    assert E % tp == 0, f"n_experts={E} not divisible by model={tp}"
    E_local = E // tp
    dp = tuple(a for a in batch_axes() if a in mesh.axis_names)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    # Batch shards over dp only when divisible (long_500k decodes B=1:
    # replicate the row, shard experts only).
    if not dp or x.shape[0] % dp_size != 0:
        dp = ()
    bs = dp if dp else None
    fs_ax = "model"   # shared experts: hidden dim over model (TP)

    def local(x, router, wg, wu, wd, *shared):
        e0 = jax.lax.axis_index("model") * E_local
        pl = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
        y, me, fe = _moe_routed(cfg, pl, x, e0, E_local, ("model",))
        # One bf16 reduction of the combined output (row-parallel style).
        y = jax.lax.psum(y, "model")
        if shared:
            ps = dict(zip(("ws_gate", "ws_up", "ws_down"), shared))
            y = y + jax.lax.psum(_moe_shared(cfg, ps, x), "model")
        # aux stats are identical on every model shard (router is
        # replicated); average over data shards only.
        if dp:
            me = jax.lax.pmean(me, dp)
            fe = jax.lax.pmean(fe, dp)
        return y, E * jnp.sum(me * fe)

    in_specs = [
        P(bs, None, None),            # x: batch over dp, repl. over model
        P(None, None),                # router (replicated)
        P("model", None, None),       # w_gate  [E, d, fe] -> EP
        P("model", None, None),       # w_up
        P("model", None, None),       # w_down  [E, fe, d]
    ]
    args = [x, p["router"], p["w_gate"], p["w_up"], p["w_down"]]
    if mo.n_shared:
        in_specs += [P(None, fs_ax), P(None, fs_ax), P(fs_ax, None)]
        args += [p["ws_gate"], p["ws_up"], p["ws_down"]]
    fn = jax.shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                   out_specs=(P(bs, None, None), P()), check_vma=False)
    y, aux = fn(*args)
    return shd(y, DP, None, None), aux


def _moe_block_serving(cfg: ModelConfig, p, x, mesh):
    """Serving MoE over 2D-EP storage (experts x 'data', hidden x 'model').

    Two compute schedules off the same layout, chosen statically by T:

      decode (T == 1): token-gather EP — all_gather the (tiny) token
        batch over the batch axes, every (data, model) cell runs its
        resident expert slice over all tokens (dense-masked: the E/top_k
        redundancy is irrelevant at decode scale), one psum returns the
        combined rows, each shard keeps its own.  Weights never move.

      prefill (T > 1): weight-streaming EP — all_gather the expert
        weights over 'data' (transient, per layer) and dispatch locally;
        token traffic never crosses shards.  The gather amortizes over
        the 32k-token prefill (~0.3 s vs 2.8 s compute on dbrx).
    """
    mo = cfg.moe
    E = mo.n_experts
    E_loc, fe_loc = ep2d_geometry(cfg, mesh)
    dp = tuple(a for a in batch_axes() if a in mesh.axis_names)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    if not dp or x.shape[0] % dp_size != 0:
        dp = ()
    bs = dp if dp else None
    gather_axes = tuple(dp) + ("model",)
    decode = x.shape[1] == 1

    def local_decode(x, router, wg, wu, wd, *shared):
        # x [B_loc, 1, d] -> gather all rows everywhere (tiny at decode).
        # Gather innermost-axis-first so the final layout is dp[0]-major,
        # matching the row0 linearization below.
        xg = x[:, 0, :]
        for a in reversed(dp):
            xg = jax.lax.all_gather(xg, a, axis=0, tiled=True)  # [Ball, d]
        logits = (xg @ router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, sel = jax.lax.top_k(probs, mo.top_k)              # [Ball, K]
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        gate_full = jnp.zeros((xg.shape[0], E), jnp.float32)
        gate_full = gate_full.at[jnp.arange(xg.shape[0])[:, None],
                                 sel].add(gates)
        e0 = jax.lax.axis_index("data") * E_loc if "data" in \
            mesh.axis_names else 0
        g_loc = jax.lax.dynamic_slice_in_dim(gate_full, e0, E_loc, axis=1)
        # Dense-masked expert FFN over the resident slice.
        h = jnp.einsum("bd,edf->bef", xg, wg)
        u = jnp.einsum("bd,edf->bef", xg, wu)
        h = jax.nn.silu(h.astype(jnp.float32)).astype(xg.dtype) * u
        ye = jnp.einsum("bef,efd->bed", h, wd)
        y = jnp.einsum("bed,be->bd", ye, g_loc.astype(xg.dtype))
        if shared:
            ps = dict(zip(("ws_gate", "ws_up", "ws_down"), shared))
            y = y + _moe_shared(cfg, ps, xg[:, None, :])[:, 0, :]
        y = jax.lax.psum(y, gather_axes)
        # Keep this shard's rows.
        B_loc = x.shape[0]
        row0 = 0
        for a in dp:
            row0 = row0 * mesh.shape[a] + jax.lax.axis_index(a)
        y = jax.lax.dynamic_slice_in_dim(y, row0 * B_loc, B_loc, axis=0)
        return y[:, None, :], jnp.float32(0.0)

    def local_prefill(x, router, wg, wu, wd, *shared):
        if "data" in mesh.axis_names:
            wg = jax.lax.all_gather(wg, "data", axis=0, tiled=True)
            wu = jax.lax.all_gather(wu, "data", axis=0, tiled=True)
            wd = jax.lax.all_gather(wd, "data", axis=0, tiled=True)
        pl = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
        y, me, fe = _moe_routed(cfg, pl, x, 0, E, ("model",))
        if shared:
            ps = dict(zip(("ws_gate", "ws_up", "ws_down"), shared))
            y = y + _moe_shared(cfg, ps, x)
        return jax.lax.psum(y, "model"), E * jnp.sum(me * fe)

    e_ax = "data" if "data" in mesh.axis_names else None
    in_specs = [
        P(bs, None, None),                 # x
        P(None, None),                     # router (replicated)
        P(e_ax, None, "model"),            # w_gate [E, d, fe]
        P(e_ax, None, "model"),            # w_up
        P(e_ax, "model", None),            # w_down [E, fe, d]
    ]
    args = [x, p["router"], p["w_gate"], p["w_up"], p["w_down"]]
    if mo.n_shared:
        in_specs += [P(None, "model"), P(None, "model"), P("model", None)]
        args += [p["ws_gate"], p["ws_up"], p["ws_down"]]
    fn = jax.shard_map(local_decode if decode else local_prefill, mesh=mesh,
                   in_specs=tuple(in_specs),
                   out_specs=(P(bs, None, None), P()), check_vma=False)
    y, aux = fn(*args)
    return shd(y, DP, None, None), aux
