"""Unified decoder-only transformer stack (dense / MoE / MLA / VLM families).

One codebase, three lowerings:
  * ``train`` / ``prefill``: full-sequence causal flash attention
    (:func:`repro.models.layers.attention`), scan-over-layers with optional
    per-block remat.  Prefill additionally scatters K/V into the Mosaic
    paged pool (en-masse allocation — the paper's key observation).
  * ``decode``: one token per sequence against the paged pool, partial
    flash per page-shard combined with psum/pmax inside ``shard_map``
    (context-parallel paged attention; DESIGN.md §3).

Parameters are stacked with a leading layer axis and consumed by
``jax.lax.scan`` so compile time is layer-count independent.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import paged
from repro.models.common import dense_init, psum_point, shd, split_keys
from repro.models.layers import (
    apply_rope,
    attention,
    gqa_qkv,
    rms_norm,
    rope,
    rope_angles,
    swiglu,
)
from repro.models.moe import init_moe_params, moe_block

from repro.models.common import BATCH as DP  # batch sentinel (see common.shd)


# ------------------------------------------------------------------ page ctx


@dataclasses.dataclass
class PageCtx:
    """Device-side paged-KV addressing for one engine step.

    tables/ntok: [B, S, mpps]; wpage: [B, S]; wslot: [B].
    ``batch_sharded``: batch dim is split over the data axes (decode_32k)
    vs. replicated with pages spread over every axis (long_500k).
    """

    tables: jax.Array
    ntok: jax.Array
    wpage: jax.Array
    wslot: jax.Array
    # Fused gather-attend decode over partially-resident KV (DESIGN.md
    # §13): per-page staging slot mirroring `tables` (-1 = pool-resident,
    # >= 0 = read the page from the staging region) plus the step-local
    # staging pools [L, NS, ptok, n_kv, dh{,_v}].  None = the classic
    # all-resident path (sync/async fault modes, and fused steps with
    # nothing in flight).
    slots: Optional[jax.Array] = None
    stage_k: Optional[jax.Array] = None
    stage_v: Optional[jax.Array] = None
    batch_sharded: bool = True
    frame_pages: int = 16       # frame striping granularity (prefill scatter)

    def page_axes(self, mesh) -> tuple:
        """Axes a sequence's pages are striped over (== combine axes)."""
        names = set(mesh.axis_names)
        if self.batch_sharded:
            return tuple(a for a in ("model",) if a in names)
        return tuple(a for a in ("pod", "data", "model") if a in names)

    def pool_axes(self, mesh) -> tuple:
        """Axes the physical pool's page dim is sharded over.

        batch_sharded: every (data, model) cell owns a private sub-pool
        (its sub-batch's pages striped over model) — pages shard over
        dp x model, NOT model alone, or the pool would be replicated
        per data shard and blow per-chip HBM.
        """
        names = set(mesh.axis_names)
        return tuple(a for a in ("pod", "data", "model") if a in names)

    def batch_spec(self, mesh):
        names = set(mesh.axis_names)
        if not self.batch_sharded:
            return None
        dp = tuple(a for a in ("pod", "data") if a in names)
        return dp if dp else None


jax.tree_util.register_dataclass(
    PageCtx,
    data_fields=["tables", "ntok", "wpage", "wslot",
                 "slots", "stage_k", "stage_v"],
    meta_fields=["batch_sharded", "frame_pages"],
)


def _ambient_mesh():
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


# ------------------------------------------------------------------ attention


def init_attn_params(key, cfg: ModelConfig, L: int) -> Dict[str, Any]:
    d, H, Hkv = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    dh = cfg.resolved_head_dim
    ks = split_keys(key, 8)
    p = {
        "wq": dense_init(ks[0], (L, d, H, dh), in_axis=1),
        "wk": dense_init(ks[1], (L, d, Hkv, dh), in_axis=1),
        "wv": dense_init(ks[2], (L, d, Hkv, dh), in_axis=1),
        "wo": dense_init(ks[3], (L, H, dh, d), in_axis=1),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((L, H, dh))
        p["bk"] = jnp.zeros((L, Hkv, dh))
        p["bv"] = jnp.zeros((L, Hkv, dh))
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((L, dh))
        p["k_norm"] = jnp.ones((L, dh))
    return p


def _project_qkv(cfg: ModelConfig, p, x, positions):
    """x [B,T,d] -> roped q [B,T,H,dh], k/v [B,T,Hkv,dh]."""
    q, k, v = gqa_qkv(
        x, p["wq"], p["wk"], p["wv"],
        p.get("bq"), p.get("bk"), p.get("bv"),
    )
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    cos, sin = rope_angles(positions, q.shape[-1], cfg.rope_theta)
    q = apply_rope(q, cos[..., :, None, :], sin[..., :, None, :])
    k = apply_rope(k, cos[..., :, None, :], sin[..., :, None, :])
    return q, k, v


def _tp_geometry(cfg: ModelConfig, mesh):
    """Static explicit-TP geometry, or None if this config can't use it.

    Each model shard owns H_loc consecutive query heads and the (static)
    kv-head slice they attend to; returns (tp, H_loc, nkv_loc, kv_lo_of)
    where kv_lo_of[s] is shard s's first kv head.  None when heads don't
    divide, or a shard's q heads map to a non-uniform kv block (the
    grouped attention inside the shard would be wrong).
    """
    if mesh is None or "model" not in mesh.axis_names:
        return None
    from repro.models.common import batch_axes, tp_mode
    if tp_mode() == "auto" or "model" in batch_axes():
        return None
    tp = mesh.shape["model"]
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    if H == 0 or H % tp or H % Hkv:
        return None
    H_loc, group = H // tp, H // Hkv
    kv_lo, span = [], 0
    for s in range(tp):
        lo = (s * H_loc) // group
        hi = (s * H_loc + H_loc - 1) // group
        kv_lo.append(lo)
        span = max(span, hi - lo + 1)
    # Uniform grouped mapping inside the shard requires H_loc % span == 0
    # and every local q head j hitting kv (lo + j // (H_loc // span)).
    if H_loc % span:
        return None
    for s in range(tp):
        for j in range(H_loc):
            if (s * H_loc + j) // group != kv_lo[s] + j // (H_loc // span):
                return None
    return tp, H_loc, span, tuple(kv_lo)


def attn_block_train(cfg: ModelConfig, p, x, positions, *, causal=True,
                     kv_len=None, prefix_kv=None):
    """Full-sequence attention; ``prefix_kv=(pk, pv)`` ([B, P, Hkv, dh],
    already roped — cached pool bytes) prepends P cached-context keys the
    in-flight queries attend to causally (suffix-only prefill; the caller
    must offset ``positions`` by P).  Returns (out, k, v) with k/v
    covering the in-flight tokens only."""
    mesh = _ambient_mesh()
    geo = _tp_geometry(cfg, mesh)
    if geo is None or prefix_kv is not None:
        # Auto-sharded fallback (no mesh / fsdp / awkward head counts),
        # and the only path carrying cached-prefix KV (suffix prefill is
        # engine-side, mesh-free; the TP path asserts it never sees one).
        q, k, v = _project_qkv(cfg, p, x, positions)
        q = shd(q, DP, None, "model", None)
        k = shd(k, DP, None, None, None)
        v = shd(v, DP, None, None, None)
        if prefix_kv is not None:
            pk, pv = prefix_kv
            o = attention(q, jnp.concatenate([pk.astype(k.dtype), k], axis=1),
                          jnp.concatenate([pv.astype(v.dtype), v], axis=1),
                          causal=causal, q_offset=pk.shape[1], kv_len=kv_len)
        else:
            o = attention(q, k, v, causal=causal, kv_len=kv_len)
        o = shd(o, DP, None, "model", None)
        return psum_point(jnp.einsum("bthd,hdk->btk", o, p["wo"])), k, v
    return _attn_block_train_tp(cfg, p, x, positions, mesh, geo,
                                causal=causal, kv_len=kv_len)


def _attn_block_train_tp(cfg: ModelConfig, p, x, positions, mesh, geo, *,
                         causal, kv_len):
    """Explicit Megatron TP attention: one bf16 psum per layer.

    Column-parallel q / out-proj over heads; k/v are computed in full on
    every shard (kv heads rarely divide tp — the redundant kv-projection
    compute equals what the auto path already does) and each shard slices
    its static kv block.  The psum dtype is pinned to the activation
    dtype — the partitioner can no longer attach the reduction to an
    f32-upcast dot (EXPERIMENTS.md §Perf iteration 2).
    """
    tp, H_loc, nkv, kv_lo = geo
    from repro.models.common import batch_axes
    dp = tuple(a for a in batch_axes() if a in mesh.axis_names)
    if dp and x.shape[0] % int(np.prod([mesh.shape[a] for a in dp])):
        dp = ()
    bs = dp if dp else None
    kv_lo_arr = jnp.asarray(kv_lo, jnp.int32)
    has_bias, has_qkn = "bq" in p, "q_norm" in p
    # When kv heads divide tp *and* shard s's q heads attend exactly its
    # kv slice, shard the kv projection too: no redundant kv compute, no
    # kv-grad psums (otherwise compute k/v in full on every shard).
    Hkv = cfg.n_kv_heads
    kv_sharded = (Hkv % tp == 0 and nkv == Hkv // tp
                  and all(kv_lo[s] == s * (Hkv // tp) for s in range(tp)))

    def local(x, positions, wq, wk, wv, wo, *extra):
        extra = list(extra)
        bq = extra.pop(0) if has_bias else None
        bk = extra.pop(0) if has_bias else None
        bv = extra.pop(0) if has_bias else None
        qn = extra.pop(0) if has_qkn else None
        kn = extra.pop(0) if has_qkn else None
        s = jax.lax.axis_index("model")
        q = jnp.einsum("btd,dhk->bthk", x, wq)
        k = jnp.einsum("btd,dhk->bthk", x, wk)
        v = jnp.einsum("btd,dhk->bthk", x, wv)
        if has_bias:
            q, k, v = q + bq, k + bk, v + bv
        if has_qkn:
            q = rms_norm(q, qn, cfg.norm_eps)
            k = rms_norm(k, kn, cfg.norm_eps)
        cos, sin = rope_angles(positions, q.shape[-1], cfg.rope_theta)
        q = apply_rope(q, cos[..., :, None, :], sin[..., :, None, :])
        k = apply_rope(k, cos[..., :, None, :], sin[..., :, None, :])
        if kv_sharded:
            k_loc, v_loc = k, v
        else:
            k_loc = jax.lax.dynamic_slice_in_dim(k, kv_lo_arr[s], nkv,
                                                 axis=2)
            v_loc = jax.lax.dynamic_slice_in_dim(v, kv_lo_arr[s], nkv,
                                                 axis=2)
        o = attention(q, k_loc, v_loc, causal=causal, kv_len=kv_len)
        y = jnp.einsum("bthd,hdk->btk", o, wo)
        return jax.lax.psum(y, "model"), k, v

    kvs = P(None, "model", None) if kv_sharded else P(None, None, None)
    kvb = P("model", None) if kv_sharded else P(None, None)
    kv_out = (P(bs, None, "model", None) if kv_sharded
              else P(bs, None, None, None))
    in_specs = [P(bs, None, None), P(bs, None),
                P(None, "model", None),            # wq (heads col-parallel)
                kvs,                               # wk
                kvs,                               # wv
                P("model", None, None)]            # wo (heads row-parallel)
    args = [x, positions, p["wq"], p["wk"], p["wv"], p["wo"]]
    if has_bias:
        in_specs += [P("model", None), kvb, kvb]
        args += [p["bq"], p["bk"], p["bv"]]
    if has_qkn:
        in_specs += [P(None), P(None)]
        args += [p["q_norm"], p["k_norm"]]
    fn = jax.shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                   out_specs=(P(bs, None, None), kv_out, kv_out),
                   check_vma=False)
    return fn(*args)


def paged_attn_op(q, k_new, v_new, k_pool, v_pool, ctx: PageCtx, *, scale):
    """Decode paged attention + pool write, sharded over page shards.

    q [B,H,dh]; k_new/v_new [B,n_kv,dh]; pools [NP, ptok, n_kv, dh].
    Returns (o [B,H,dh_v], k_pool', v_pool').
    """
    mesh = _ambient_mesh()

    def local(q, k_new, v_new, k_pool, v_pool, tables, ntok, wpage, wslot,
              axes=(), stage_k=None, stage_v=None, slots=None):
        tables = tables.reshape(tables.shape[0], -1)
        ntok = ntok.reshape(ntok.shape[0], -1)
        if slots is not None:
            slots = slots.reshape(slots.shape[0], -1)
        # One shard column holds the write page; the rest are -1 (also the
        # unsharded test path, where all S columns arrive at once).
        wpage = wpage.reshape(wpage.shape[0], -1).max(axis=1)
        k_pool, v_pool = paged.write_kv(k_pool, v_pool, k_new, v_new,
                                        wpage, wslot)
        o, m, l = paged.paged_attention_local(
            q, k_pool, v_pool, tables, ntok, scale=scale,
            stage_k=stage_k, stage_v=stage_v, slots=slots)
        o = paged.combine_partials(o, m, l, axes)
        return o.astype(q.dtype), k_pool, v_pool

    if mesh is None:
        return local(q, k_new, v_new, k_pool, v_pool,
                     ctx.tables, ctx.ntok, ctx.wpage, ctx.wslot,
                     stage_k=ctx.stage_k, stage_v=ctx.stage_v,
                     slots=ctx.slots)
    if ctx.slots is not None:
        # Staging consumption is an engine-local (mesh-free) decode path;
        # the sharded path would need staging sub-pools per page shard.
        raise NotImplementedError(
            "fused staging decode (PageCtx.slots) has no mesh path")

    axes = ctx.page_axes(mesh)
    bs = ctx.batch_spec(mesh)
    pool_spec = P(ctx.pool_axes(mesh) or None)
    fn = jax.shard_map(
        functools.partial(local, axes=axes),
        mesh=mesh,
        in_specs=(
            P(bs),                      # q replicated over model
            P(bs), P(bs),               # k_new, v_new
            pool_spec, pool_spec,       # pools split on page dim
            P(bs, axes), P(bs, axes),   # tables, ntok
            P(bs, axes), P(bs),         # wpage, wslot
        ),
        out_specs=(P(bs), pool_spec, pool_spec),
        check_vma=False,
    )
    return fn(q, k_new, v_new, k_pool, v_pool, ctx.tables, ctx.ntok,
              ctx.wpage, ctx.wslot)


def prefill_write_op(k_seq, v_seq, k_pool, v_pool, ctx: PageCtx,
                     tok_offset: int = 0):
    """Scatter prefilled K/V [B,T,n_kv,dh] into the paged pool.

    Each page shard owns the stripe of frames f ≡ shard (mod S); the local
    writer reconstructs every local page's global vpn from that striping
    (ShardedKVCache contract) and gathers its tokens from the replicated
    sequence.  ``tok_offset`` (a page multiple) shifts the window for
    suffix-only prefill: only pages at token positions ≥ the offset are
    written (prefix-cache reuse, DESIGN.md §8).
    """
    mesh = _ambient_mesh()

    def local(k_seq, v_seq, k_pool, v_pool, tables, *, axes=()):
        tables = tables.reshape(tables.shape[0], -1)
        shard, n_shards = 0, 1
        for a in axes:
            n = jax.lax.axis_size(a)
            shard = shard * n + jax.lax.axis_index(a)
            n_shards *= n
        return paged.write_prefill_kv(
            k_pool, v_pool, k_seq, v_seq, tables, shard_idx=shard,
            n_shards=n_shards, frame_pages=ctx.frame_pages,
            tok_offset=tok_offset)

    if mesh is None:
        return local(k_seq, v_seq, k_pool, v_pool, ctx.tables)
    axes = ctx.page_axes(mesh)
    bs = ctx.batch_spec(mesh)
    pool_spec = P(ctx.pool_axes(mesh) or None)
    fn = jax.shard_map(
        functools.partial(local, axes=axes), mesh=mesh,
        in_specs=(P(bs), P(bs), pool_spec, pool_spec, P(bs, axes)),
        out_specs=(pool_spec, pool_spec),
        check_vma=False,
    )
    return fn(k_seq, v_seq, k_pool, v_pool, ctx.tables)


def attn_block_decode(cfg: ModelConfig, p, x, pos, k_pool, v_pool,
                      ctx: PageCtx):
    """x [B,1,d], pos [B] -> ([B,1,d], k_pool', v_pool')."""
    q, k, v = _project_qkv(cfg, p, x, pos[:, None])
    dh = cfg.resolved_head_dim
    o, k_pool, v_pool = paged_attn_op(
        q[:, 0], k[:, 0], v[:, 0], k_pool, v_pool, ctx, scale=dh ** -0.5)
    y = jnp.einsum("bhd,hdk->bk", o, p["wo"])[:, None, :]
    return y, k_pool, v_pool


# ------------------------------------------------------------------ FFN


def init_ffn_params(key, cfg: ModelConfig, L: int) -> Dict[str, Any]:
    d, f = cfg.d_model, cfg.d_ff
    ks = split_keys(key, 3)
    return {
        "w_gate": dense_init(ks[0], (L, d, f), in_axis=1),
        "w_up": dense_init(ks[1], (L, d, f), in_axis=1),
        "w_down": dense_init(ks[2], (L, f, d), in_axis=1),
    }


def ffn_block(cfg: ModelConfig, p, x):
    mesh = _ambient_mesh()
    from repro.models.common import batch_axes, tp_mode
    tp = mesh.shape["model"] if (mesh is not None
                                 and "model" in mesh.axis_names) else 0
    if (not tp or tp_mode() == "auto" or "model" in batch_axes()
            or cfg.d_ff % tp):
        return swiglu(x, p["w_gate"], p["w_up"], p["w_down"])

    # Explicit TP SwiGLU: hidden column-parallel, down row-parallel, one
    # bf16 psum (same rationale as _attn_block_train_tp).
    dp = tuple(a for a in batch_axes() if a in mesh.axis_names)
    if dp and x.shape[0] % int(np.prod([mesh.shape[a] for a in dp])):
        dp = ()
    bs = dp if dp else None

    def local(x, wg, wu, wd):
        g = jnp.einsum("btd,df->btf", x, wg)
        u = jnp.einsum("btd,df->btf", x, wu)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        return jax.lax.psum(jnp.einsum("btf,fd->btd", h, wd), "model")

    fn = jax.shard_map(local, mesh=mesh,
                   in_specs=(P(bs, None, None), P(None, "model"),
                             P(None, "model"), P("model", None)),
                   out_specs=P(bs, None, None), check_vma=False)
    return fn(x, p["w_gate"], p["w_up"], p["w_down"])


# ------------------------------------------------------------------ stack


def init_decoder_params(key, cfg: ModelConfig, L: Optional[int] = None):
    """Stacked decoder-layer params for the scanned stack."""
    L = cfg.n_layers if L is None else L
    ks = split_keys(key, 4)
    p: Dict[str, Any] = {
        "ln1": jnp.ones((L, cfg.d_model)),
        "ln2": jnp.ones((L, cfg.d_model)),
    }
    if cfg.mla is not None:
        from repro.models.mla import init_mla_params
        p["attn"] = init_mla_params(ks[0], cfg, L)
    else:
        p["attn"] = init_attn_params(ks[0], cfg, L)
    if cfg.moe is not None:
        p["moe"] = init_moe_params(ks[1], cfg, L)
    else:
        p["mlp"] = init_ffn_params(ks[1], cfg, L)
    return p


def _layer_train(cfg: ModelConfig, lp, x, positions):
    from jax.ad_checkpoint import checkpoint_name
    if cfg.mla is not None:
        from repro.models.mla import mla_block_train
        a, _ = mla_block_train(cfg, lp["attn"], rms_norm(x, lp["ln1"],
                                                         cfg.norm_eps),
                               positions)
    else:
        a, _, _ = attn_block_train(cfg, lp["attn"],
                                   rms_norm(x, lp["ln1"], cfg.norm_eps),
                                   positions)
    # Named so the 'save_collectives' remat policy can keep the psum'd
    # block outputs: the backward recompute then re-runs only *local*
    # math — no re-all-reduce (EXPERIMENTS.md §Perf iteration 3).
    a = checkpoint_name(a, "tp_psum")
    x = x + a
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        f, aux = moe_block(cfg, lp["moe"], h)
    else:
        f, aux = ffn_block(cfg, lp["mlp"], h), jnp.float32(0.0)
    f = checkpoint_name(f, "tp_psum")
    x = x + f
    return shd(x, DP, None, None), aux


def decoder_stack_train(cfg: ModelConfig, params, x, positions, *,
                        remat=True):
    """Returns (x, total MoE load-balance aux loss).

    remat: False | True (recompute everything, collectives included) |
    'save_collectives' (recompute local math only; the two psum'd block
    outputs per layer are saved — 4 instead of 6 all-reduces per layer
    at the cost of 2 activations/layer of residency).
    """

    def body(carry, lp):
        x, aux = carry
        fn = _layer_train
        if remat == "save_collectives":
            fn = jax.checkpoint(
                _layer_train, static_argnums=(0,),
                policy=jax.checkpoint_policies.save_only_these_names(
                    "tp_psum"))
        elif remat:
            fn = jax.checkpoint(_layer_train, static_argnums=(0,))
        x, a = fn(cfg, lp, x, positions)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), params)
    return x, aux


def _layer_prefill(cfg: ModelConfig, lp, x, positions, k_pool, v_pool, ctx,
                   prefix_kv=None, tok_offset: int = 0):
    """Like train, but also scatters this layer's K/V into its pool slice.

    ``prefix_kv``: this layer's cached-prefix K/V ([B, P, Hkv, dh] pair)
    for suffix-only prefill; the cached pages themselves are NOT
    re-written (``tok_offset`` masks them out of the scatter) — the
    host-tier fault-in restores them from the prefix cache instead.
    """
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if cfg.mla is not None:
        assert prefix_kv is None, "prefix-cache reuse unsupported for MLA"
        from repro.models.mla import mla_block_train
        a, lat = mla_block_train(cfg, lp["attn"], h, positions)
        k_pool, v_pool = prefill_write_op(lat["k"], lat["v"], k_pool,
                                          v_pool, ctx)
    else:
        a, k, v = attn_block_train(cfg, lp["attn"], h, positions,
                                   prefix_kv=prefix_kv)
        k_pool, v_pool = prefill_write_op(k, v, k_pool, v_pool, ctx,
                                          tok_offset=tok_offset)
    x = x + a
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    f = moe_block(cfg, lp["moe"], h)[0] if cfg.moe is not None else \
        ffn_block(cfg, lp["mlp"], h)
    return shd(x + f, DP, None, None), k_pool, v_pool


def decoder_stack_prefill(cfg: ModelConfig, params, x, positions, pools, ctx,
                          prefix_kv=None, tok_offset: int = 0):
    """pools: (k_pool [L,...], v_pool [L,...]) stacked over layers.

    ``prefix_kv``: stacked cached-prefix K/V ([L, B, P, Hkv, dh] pair)
    for suffix-only prefill (prefix-cache reuse, DESIGN.md §8); each
    layer's slice rides the scan alongside its pool slice."""
    k_pools, v_pools = pools

    def body(carry, inp):
        x = carry
        l, lp = inp
        pkv = (None if prefix_kv is None
               else (prefix_kv[0][l], prefix_kv[1][l]))
        x, kp, vp = _layer_prefill(cfg, lp, x, positions,
                                   k_pools[l], v_pools[l], ctx,
                                   prefix_kv=pkv, tok_offset=tok_offset)
        return x, (kp, vp)

    L = k_pools.shape[0]
    x, (kp, vp) = jax.lax.scan(body, x, (jnp.arange(L), params))
    return x, (kp, vp)


def decoder_stack_decode(cfg: ModelConfig, params, x, pos, pools, ctx):
    k_pools, v_pools = pools

    def body(carry, inp):
        x, kps, vps = carry
        l, lp = inp
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        # Staging pools arrive layer-stacked [L, NS, ...] (DESIGN.md §13);
        # each layer's attention drains its own slice.
        lctx = ctx if ctx.stage_k is None else dataclasses.replace(
            ctx, stage_k=ctx.stage_k[l], stage_v=ctx.stage_v[l])
        if cfg.mla is not None:
            from repro.models.mla import mla_block_decode
            a, kp, vp = mla_block_decode(cfg, lp["attn"], h, pos,
                                         kps[l], vps[l], lctx)
        else:
            a, kp, vp = attn_block_decode(cfg, lp["attn"], h, pos,
                                          kps[l], vps[l], lctx)
        x = x + a
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        f = moe_block(cfg, lp["moe"], h)[0] if cfg.moe is not None else \
            ffn_block(cfg, lp["mlp"], h)
        x = x + f
        kps = kps.at[l].set(kp)
        vps = vps.at[l].set(vp)
        return (x, kps, vps), None

    L = k_pools.shape[0]
    (x, k_pools, v_pools), _ = jax.lax.scan(
        body, (x, k_pools, v_pools), (jnp.arange(L), params))
    return x, (k_pools, v_pools)
