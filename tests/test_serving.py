"""Serving-engine integration tests.

The central semantic claim: the memory manager must be invisible to the
model.  Greedy outputs must be identical whether the pool is managed by
Mosaic (with pressure-induced CAC compaction mid-stream) or by a pressure-
free pool — because coalescing is metadata-only and compaction moves
payloads coherently with the table updates.
"""

import dataclasses

import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import PoolGeometry
from repro.serving.engine import Request, ServingEngine
from repro.serving.kv_cache import ShardedKVCache

GEO = PoolGeometry(page_tokens=8, frame_pages=4, headroom=1.25,
                   compact_threshold=0.4)


def make_engine(arch="qwen2.5-3b", manager="mosaic", max_batch=3,
                max_seq=96, seed=0, **kw):
    cfg = get_smoke_config(arch)
    return ServingEngine(cfg, geometry=GEO, max_batch=max_batch,
                         max_seq=max_seq, manager_kind=manager, seed=seed,
                         **kw)


def run_workload(eng, prompts, max_new=6):
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, tenant=i % 2, prompt=p, max_new=max_new))
    eng.run_until_drained(max_steps=200)
    return eng


PROMPTS = [np.array(p, np.int32) for p in
           ([5, 6, 7, 8, 9, 10, 11, 12],
            [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8],
            [2, 7, 1, 8],
            [9, 9, 8, 2, 1, 0, 4, 5, 6, 7, 1, 2, 3],
            [11, 3, 5])]


def test_engine_outputs_independent_of_manager():
    """Mosaic vs GPU-MMU pools: same greedy continuations."""
    results = {}
    for kind in ("mosaic", "gpu-mmu"):
        eng = make_engine(manager=kind)
        reqs = [Request(rid=i, tenant=i % 2, prompt=p, max_new=6)
                for i, p in enumerate(PROMPTS)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained(max_steps=200)
        assert all(r.done for r in reqs)
        results[kind] = {r.rid: list(r.out) for r in reqs}
    assert results["mosaic"] == results["gpu-mmu"]


def test_engine_compaction_preserves_outputs_under_pressure():
    """A tight pool forces mid-stream CAC compaction; outputs must match a
    pressure-free run token-for-token."""
    cfg = get_smoke_config("qwen2.5-3b")

    def run(max_batch, max_seq):
        eng = ServingEngine(cfg, geometry=GEO, max_batch=max_batch,
                            max_seq=max_seq, manager_kind="mosaic", seed=0)
        reqs = [Request(rid=i, tenant=0, prompt=p, max_new=8)
                for i, p in enumerate(PROMPTS)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained(max_steps=300)
        assert all(r.done for r in reqs)
        return {r.rid: list(r.out) for r in reqs}, eng

    # Loose run: big pool, no pressure.
    loose, eng_loose = run(max_batch=5, max_seq=192)
    # Tight run: small batch → churn (alloc/dealloc interleave) → frames
    # fragment → CAC fires.
    tight, eng_tight = run(max_batch=2, max_seq=96)
    assert loose == tight
    eng_tight.cache.check_invariants()


def test_engine_multi_tenant_isolation():
    """Concurrent tenants share the pool; the soft guarantee keeps every
    frame single-owner throughout."""
    eng = make_engine(max_batch=4)
    reqs = [Request(rid=i, tenant=i, prompt=PROMPTS[i % len(PROMPTS)],
                    max_new=5) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.step()
        for mgr in eng.cache.mgrs:
            mgr.check_invariants()   # includes the soft-guarantee assert
    eng.run_until_drained(max_steps=200)
    # Full teardown: every frame returns to the free pool.
    for mgr in eng.cache.mgrs:
        assert mgr.pool.occupancy() == 0.0


def test_engine_tracks_coalescing_stats():
    # Prompts longer than one frame (32 tokens) so en-masse prefill
    # allocation produces fully-covered frames to coalesce.
    ftok = GEO.frame_pages * GEO.page_tokens
    long_prompts = [np.arange(2 * ftok + 3 * i, dtype=np.int32) % 17
                    for i in range(3)]
    eng = run_workload(make_engine(max_seq=160), long_prompts, max_new=4)
    assert eng.stats.decode_steps > 0
    assert eng.stats.prefill_tokens == sum(len(p) for p in long_prompts)
    assert 0.0 <= eng.stats.coalesced_mean <= 1.0
    # En-masse prefill allocation ⇒ a healthy share of pages coalesced.
    assert eng.stats.coalesced_mean > 0.3


# ----------------------------------------------------- host tier / paging


def _oversub_requests(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        # Decode-heavy so the working set outgrows the pool mid-run.
        T = int(rng.integers(24, 56))
        reqs.append(Request(
            rid=i, tenant=i % 3,
            prompt=rng.integers(0, cfg.vocab_size, T).astype(np.int32),
            max_new=int(rng.integers(24, 40))))
    return reqs


def test_engine_oversubscribed_completes_under_both_managers():
    """A 2x oversubscribed multi-tenant run drains under both managers,
    with identical greedy outputs and clean invariants."""
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("qwen2.5-3b")
    results = {}
    for kind in ("mosaic", "gpu-mmu"):
        eng = ServingEngine(cfg, geometry=GEO, max_batch=6, max_seq=96,
                            manager_kind=kind, seed=0, oversubscription=2.0)
        reqs = _oversub_requests(cfg, 10)
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained(max_steps=2000)
        assert all(r.done for r in reqs)
        eng.cache.check_invariants()
        assert eng.host.request_pages() == 0, \
            "drained engine must not hold request-owned host pages " \
            "(cached prefixes under negative owners may persist)"
        results[kind] = {r.rid: list(r.out) for r in reqs}
    assert results["mosaic"] == results["gpu-mmu"]


def test_engine_preempted_request_resumes_token_identical():
    """A preempted-then-resumed request must produce exactly the tokens of
    an un-preempted run, and every swap cycle must leave the pool's
    invariants intact."""
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("qwen2.5-3b")

    def run(with_preempt):
        eng = ServingEngine(cfg, geometry=GEO, max_batch=4, max_seq=96,
                            manager_kind="mosaic", seed=0)
        rng = np.random.default_rng(3)
        spec = [(20, 24), (5, 30), (7, 30)]
        reqs = [Request(rid=i, tenant=i,
                        prompt=rng.integers(0, cfg.vocab_size, T)
                        .astype(np.int32), max_new=mn)
                for i, (T, mn) in enumerate(spec)]
        for r in reqs:
            eng.submit(r)
        for step in range(60):
            eng.step()
            if with_preempt and step in (3, 9):
                # Two full swap cycles: hold across a few steps so other
                # requests decode (and may compact) in between.
                assert eng.preempt(0, hold=True)
                eng.cache.check_invariants()
                for _ in range(2):
                    eng.step()
                    eng.cache.check_invariants()
                assert eng.release(0)
                eng.step()                    # resume + first fault-in
                eng.cache.check_invariants()
            if all(r.done for r in reqs):
                break
        eng.run_until_drained(max_steps=200)
        assert all(r.done for r in reqs)
        return eng, {r.rid: list(r.out) for r in reqs}

    eng_plain, plain = run(with_preempt=False)
    eng_swap, swapped = run(with_preempt=True)
    assert eng_swap.stats.swaps_out >= 2 and eng_swap.stats.faults > 0
    assert eng_plain.stats.swaps_out == 0
    assert plain == swapped
    eng_swap.cache.check_invariants()
    assert eng_swap.host.request_pages() == 0


def test_engine_priority_preemption_under_admission_pressure():
    """A high-priority arrival preempts the lowest-priority active request
    instead of waiting, and everyone still finishes with correct state."""
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("qwen2.5-3b")
    eng = ServingEngine(cfg, geometry=GEO, max_batch=3, max_seq=96,
                        manager_kind="mosaic", seed=0, oversubscription=1.6)
    rng = np.random.default_rng(4)
    low = [Request(rid=i, tenant=0, priority=0,
                   prompt=rng.integers(0, cfg.vocab_size, 64)
                   .astype(np.int32), max_new=16) for i in range(3)]
    for r in low:
        eng.submit(r)
    for _ in range(3):
        eng.step()
    hi = Request(rid=99, tenant=1, priority=5,
                 prompt=rng.integers(0, cfg.vocab_size, 64)
                 .astype(np.int32), max_new=8)
    eng.submit(hi)
    for _ in range(4):
        eng.step()
        eng.cache.check_invariants()
    assert hi in eng.active or hi.done, \
        "high-priority request should displace a low-priority one"
    assert eng.stats.swaps_out >= 1
    eng.run_until_drained(max_steps=500)
    assert all(r.done for r in low + [hi])
    eng.cache.check_invariants()


# ------------------------------------------------------------- kv cache


def test_sharded_cache_frames_never_straddle_shards():
    cache = ShardedKVCache(GEO, pages_per_shard=64, n_shards=4,
                           manager_kind="mosaic")
    cache.allocate(0, 10 * GEO.frame_pages * GEO.page_tokens)
    ftok = GEO.frame_pages * GEO.page_tokens
    # Global frame f must live wholly in sub-pool f % S.
    for s, mgr in enumerate(cache.mgrs):
        if 0 not in mgr.tables:
            continue
        n_local = len(mgr.tables[0].ppn)
        assert n_local % GEO.frame_pages == 0 or s == (10 - 1) % 4
    ctx = cache.pack_ctx([0], mpps=64)
    tb = np.asarray(ctx.tables)[0]            # [S, mpps]
    # Each shard's table only references its local pool.
    assert tb.max() < 64
    total_pages = (tb >= 0).sum()
    assert total_pages == 10 * GEO.frame_pages
    cache.check_invariants()


def test_sharded_cache_pack_dual_splits_by_granularity():
    cache = ShardedKVCache(GEO, pages_per_shard=64, n_shards=1)
    fp, ptok = GEO.frame_pages, GEO.page_tokens
    cache.allocate(0, fp * ptok)        # one full frame -> coalesced
    cache.allocate(1, 2 * ptok)         # partial -> splintered
    ft, fn, pt, pn = cache.pack_dual([0, 1], shard=0, max_frames=4,
                                     max_pages=4 * fp)
    ft, fn, pt, pn = map(np.asarray, (ft, fn, pt, pn))
    assert (ft[0] >= 0).sum() == 1 and fn[0, 0] == fp * ptok
    assert (pt[0] >= 0).sum() == 0      # fully coalesced: no page entries
    assert (ft[1] >= 0).sum() == 0
    assert (pt[1] >= 0).sum() == 2 and pn[1, :2].tolist() == [ptok, ptok]


def test_sharded_cache_random_ops_property():
    """Hypothesis-style invariant sweep: arbitrary allocate/append/free
    interleavings keep every sub-pool's invariants and the striping
    contract (global frame f of a sequence lives in sub-pool f % S)."""
    pytest.importorskip("hypothesis",
                        reason="property tests need hypothesis")
    from hypothesis import given, settings, HealthCheck
    from hypothesis import strategies as st

    ops_st = st.lists(
        st.one_of(
            st.tuples(st.just("alloc"), st.integers(0, 3),
                      st.integers(1, 3 * GEO.frame_pages * GEO.page_tokens)),
            st.tuples(st.just("append"), st.integers(0, 3),
                      st.integers(1, 24)),
            st.tuples(st.just("free"), st.integers(0, 3), st.just(0)),
        ),
        min_size=1, max_size=25,
    )

    @settings(max_examples=30, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(ops=ops_st)
    def run(ops):
        cache = ShardedKVCache(GEO, pages_per_shard=256, n_shards=4,
                               manager_kind="mosaic")
        ftok = GEO.frame_pages * GEO.page_tokens
        for op, seq, n in ops:
            if op == "alloc":
                cache.allocate(seq, n)
            elif op == "append":
                cache.append(seq, n)
            elif op == "free":
                cache.free(seq)
            cache.check_invariants()
            # Striping contract: per-shard local page count implies the
            # shard holds exactly the frames striped to it.
            for s, mgr in enumerate(cache.mgrs):
                for owner, tok in mgr.seq_tokens.items():
                    total = cache.seq_tokens.get(owner, 0)
                    frames = (total + ftok - 1) // ftok
                    mine = sum(1 for f in range(frames) if f % 4 == s)
                    local_frames = (len(mgr.tables[owner].ppn)
                                    + GEO.frame_pages - 1) // GEO.frame_pages
                    assert local_frames <= mine, (owner, s)
        for seq in list(cache.seq_tokens):
            cache.free(seq)
        for mgr in cache.mgrs:
            assert mgr.pool.occupancy() == 0.0

    run()


def test_summary_pins_prestage_counters():
    """Satellite: summary() must expose the pre-stage hit/wasted/
    cancelled split (DESIGN.md §14) — and omit it entirely when no
    pages were ever pre-staged, keeping older engines' lines stable."""
    from repro.serving.engine import EngineStats

    s = EngineStats(prestaged_pages=4, prestage_hits=2,
                    prestage_wasted=1, prestage_cancelled=1)
    assert "prestage 4 pages (2/1/1 hit/wasted/cancelled)" in s.summary()
    assert "prestage" not in EngineStats().summary()


def test_engine_holds_params_in_model_dtype():
    """Weights sit in ``cfg.dtype`` from construction on: no float32 copy
    of the tree next to the serving one, and the model's per-call cast is
    a no-op."""
    import jax
    import jax.numpy as jnp
    eng = make_engine()
    want = jnp.dtype(eng.cfg.dtype)
    leaves = jax.tree.leaves(eng.params)
    assert leaves and all(x.dtype == want for x in leaves
                          if jnp.issubdtype(x.dtype, jnp.floating))
    assert all(p.dtype == jnp.bfloat16 for p in eng.pools)
    # A replica on the same device shares the weights' buffers: no copy.
    twin = make_engine(params=eng.params)
    assert all(a.unsafe_buffer_pointer() == b.unsafe_buffer_pointer()
               for a, b in zip(leaves, jax.tree.leaves(twin.params)))
