"""Cluster serving tier tests (DESIGN.md §10).

Covers the host-tier frame leases (single-domain-per-frame, whole-frame
recycling, migration owner flips), the shared-store views, cross-engine
prefix sharing, the deadline-aware router's dispatch order, SLO
deadline accounting, work-stealing migration (zero re-prefill, token
identity across engine counts), and the MoE/MLA park fallback.
"""

import os

import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import PoolGeometry
from repro.serving.cluster import (HostFrameTable, ServingCluster,
                                   SharedHostTier, aggregate_engine_stats)
from repro.serving.engine import EngineStats, Request, ServingEngine
from repro.serving.host_tier import HostPageStore, PrefixIndex

GEO = PoolGeometry(page_tokens=8, frame_pages=4, compact_threshold=0.4)
PTOK = GEO.page_tokens


def _payload(tag: float = 0.0):
    return (np.full((1, PTOK, 1, 4), tag, np.float32),
            np.full((1, PTOK, 1, 4), -tag, np.float32))


# --------------------------------------------------------- frame leases


def test_frame_table_single_domain_per_frame():
    ft = HostFrameTable(frame_pages=4)
    for vpn in range(5):                       # domain 0: 5 pages, 2 frames
        ft.place(0, (1, 0, vpn))
    f_other = ft.place(1, (2, 0, 0))           # domain 1: own frame,
    assert len(ft) == 3                        # despite free slots above
    assert ft.owner_of((2, 0, 0)) == 1
    assert ft.owner_of((1, 0, 4)) == 0
    assert {ft.owner_of((1, 0, v)) for v in range(5)} == {0}
    assert f_other not in {ft._key_frame[(1, 0, v)] for v in range(5)}
    ft.check_invariants()
    # rid collision across domains is an error, not silent sharing.
    with pytest.raises(AssertionError):
        ft.place(1, (1, 0, 0))


def test_frame_table_whole_frame_recycle():
    ft = HostFrameTable(frame_pages=2)
    ft.place(0, (1, 0, 0))
    ft.place(0, (1, 0, 1))
    assert len(ft) == 1
    ft.release((1, 0, 0))
    ft.release((1, 0, 1))
    assert len(ft) == 0 and ft.stats["frames_recycled"] == 1
    # The recycled frame is reusable by a *different* domain (it was
    # returned whole, so no mixing can occur).
    f = ft.place(7, (9, 0, 0))
    assert f == 0 and ft.owner_of((9, 0, 0)) == 7
    ft.check_invariants()


def test_frame_table_migrate_flips_exclusive_frames():
    ft = HostFrameTable(frame_pages=2)
    a = [(1, 0, 0), (1, 0, 1)]                 # fills one frame exactly
    b = [(2, 0, 0)]                            # shares its frame with c
    c = [(3, 0, 0)]
    for k in a + b + c:
        ft.place(0, k)
    moved = ft.migrate(a + b, dst=1)
    assert moved == 3
    # a's frame flipped owner without re-placement; b was re-placed out
    # of the frame it shared with (non-migrating) c.
    assert ft.stats["whole_frame_moves"] == 1
    assert ft.stats["page_moves"] == 1
    assert {ft.owner_of(k) for k in a + b} == {1}
    assert ft.owner_of(c[0]) == 0
    assert ft._key_frame[b[0]] != ft._key_frame[c[0]]
    ft.check_invariants()


def test_shared_tier_views_share_payloads_not_frames():
    tier = SharedHostTier(GEO, n_engines=2)
    v0, v1 = tier.view(0), tier.view(1)
    v0.put(1, 0, 0, *_payload(1.0))
    v1.put(2, 0, 0, *_payload(2.0))
    # Both engines see both payloads (the shared store)...
    assert v0.has(2, 0, 0) and v1.has(1, 0, 0)
    # ...but the pages sit in frames of their own domains.
    assert tier.frames.owner_of((1, 0, 0)) == 0
    assert tier.frames.owner_of((2, 0, 0)) == 1
    tier.check_invariants()
    # pop / drop_seq release the leases.
    v1.pop(2, 0, 0)
    assert tier.frames.owner_of((2, 0, 0)) is None
    assert v0.drop_seq(1) == 1
    assert len(tier.frames) == 0


def test_per_engine_prefix_indexes_never_collide_owners():
    tier = SharedHostTier(GEO, n_engines=2, share_prefix=False)
    toks = np.arange(2 * PTOK, dtype=np.int32)
    for i in range(2):
        idx = tier.prefix_for(i)
        parent = None
        for j, h in enumerate(idx.chain_hashes(toks)):
            idx.park(h, parent, j, 0, j, *_payload(i))
            parent = h
    owners0 = {p.owner for p in tier.prefix_for(0)._pages.values()}
    owners1 = {p.owner for p in tier.prefix_for(1)._pages.values()}
    assert not owners0 & owners1
    # Same (shard, vpn) pages, two indexes, one store: 4 payloads.
    assert len(tier.store) == 4
    tier.check_invariants()


# ------------------------------------------------------------- cluster


def _shared_prefix_reqs(cfg, n, shared_tokens=24, suffix_tokens=8,
                        max_new=3, seed=0):
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab_size, shared_tokens).astype(np.int32)
    return [Request(rid=i, tenant=i % 2,
                    prompt=np.concatenate(
                        [shared, rng.integers(0, cfg.vocab_size,
                                              suffix_tokens)
                         .astype(np.int32)]),
                    max_new=max_new)
            for i in range(n)]


def _run_cluster(n_engines, *, share_prefix=True, n=5):
    cfg = get_smoke_config("qwen2.5-3b")
    cluster = ServingCluster(cfg, geometry=GEO, n_engines=n_engines,
                             max_batch=4, max_seq=96, seed=0,
                             share_prefix=share_prefix,
                             decode_window_us=1000.0)
    reqs = _shared_prefix_reqs(cfg, n)
    cluster.submit(reqs[0], engine=0)
    cluster.run_until_drained(max_steps=300)
    for r in reqs[1:]:
        cluster.submit(r)
    cluster.run_until_drained(max_steps=600)
    assert all(r.done for r in reqs)
    cluster.check_invariants()
    return cluster, {r.rid: tuple(r.out) for r in reqs}


@pytest.fixture(scope="module")
def cluster_runs():
    one = _run_cluster(1)
    two = _run_cluster(2)
    return one, two


def test_cluster_tokens_identical_across_engine_counts(cluster_runs):
    (_, outs1), (_, outs2) = cluster_runs
    assert outs1 == outs2


def test_cluster_shared_index_hits_across_engines(cluster_runs):
    _, (cluster, _) = cluster_runs
    # Wave 1 ran (and parked) on replica 0 only; every replica that
    # served wave 2 hit the shared index — including replica 1, which
    # never saw the prefix before.
    by_eng = [e.stats for e in cluster.engines]
    assert by_eng[0].prefix_parked_pages > 0
    assert by_eng[1].prefix_hits > 0
    t = cluster.stats().totals
    assert t.prefix_hits >= len(cluster.engines)
    # Drained cluster holds no request-owned host pages; the index's
    # pages persist under negative owners, leased to the prefix domain.
    assert cluster.tier.store.request_pages() == 0
    for key in cluster.tier.store._pages:
        assert key[0] < 0
        assert cluster.tier.frames.owner_of(key) is not None


def test_cluster_requires_unique_rids():
    cfg = get_smoke_config("qwen2.5-3b")
    cluster = ServingCluster(cfg, geometry=GEO, n_engines=2, max_batch=2,
                             max_seq=64, seed=0)
    r = Request(rid=0, tenant=0, prompt=np.arange(8, dtype=np.int32),
                max_new=2)
    cluster.submit(r, engine=0)
    dup = Request(rid=0, tenant=1, prompt=np.arange(8, dtype=np.int32),
                  max_new=2)
    with pytest.raises(AssertionError):
        cluster.submit(dup, engine=1)


# ----------------------------------------------------------- migration


def _run_steal(migrate):
    cfg = get_smoke_config("qwen2.5-3b")
    cluster = ServingCluster(cfg, geometry=GEO, n_engines=2, max_batch=2,
                             max_seq=96, seed=0, migrate=migrate,
                             prefix_cache=False, decode_window_us=1000.0)
    rng = np.random.default_rng(2)
    victim = Request(rid=0, tenant=0, priority=0,
                     prompt=rng.integers(0, cfg.vocab_size, 40)
                     .astype(np.int32), max_new=16)
    premium = [Request(rid=i, tenant=1, priority=2,
                       prompt=rng.integers(0, cfg.vocab_size, 48)
                       .astype(np.int32), max_new=10)
               for i in range(1, 3)]
    cluster.submit(victim, engine=0)
    for _ in range(2):
        cluster.step()
    for r in premium:
        cluster.submit(r, engine=0)
    cluster.run_until_drained(max_steps=600)
    assert all(r.done for r in [victim] + premium)
    cluster.check_invariants()
    return cluster, {r.rid: tuple(r.out) for r in [victim] + premium}


def test_cluster_work_stealing_migrates_with_zero_reprefill():
    steal, outs_steal = _run_steal(True)
    stay, outs_stay = _run_steal(False)
    assert outs_steal == outs_stay          # migration never changes tokens
    r = steal.router.stats
    assert r.migrations >= 1 and r.migrated_pages > 0
    dst = steal.engines[1]
    # The thief decoded the victim without prefilling a single token:
    # only host-resident base pages changed hands (frame-lease moves +
    # fault-in over the thief's own DMA lanes).
    assert dst.stats.prefill_tokens == 0
    assert dst.stats.decode_tokens > 0
    assert dst.stats.migrations_in >= 1
    assert steal.engines[0].stats.migrations_out >= 1
    assert dst.stats.faults >= r.migrated_pages
    fs = steal.tier.frames.stats
    assert fs["whole_frame_moves"] + fs["page_moves"] > 0
    # No stealing without migration enabled.
    assert stay.router.stats.migrations == 0
    assert stay.engines[1].stats.decode_tokens == 0


# ------------------------------------------------------------- routing


def test_router_slack_dispatch_prefers_idle_engine():
    cfg = get_smoke_config("qwen2.5-3b")
    rng = np.random.default_rng(0)

    def burst():
        return [Request(rid=100 + i, tenant=1,
                        prompt=rng.integers(0, cfg.vocab_size, 16)
                        .astype(np.int32),
                        max_new=4, deadline_us=9000.0) for i in range(2)]

    for policy, expect_idle in (("slack", True), ("fifo", False)):
        cluster = ServingCluster(cfg, geometry=GEO, n_engines=2,
                                 max_batch=2, max_seq=96, seed=0,
                                 router_policy=policy, migrate=False)
        for i in range(3):                  # load replica 0's queue
            cluster.submit(Request(rid=i, tenant=0,
                                   prompt=np.arange(16, dtype=np.int32),
                                   max_new=12), engine=0)
        for r in burst():
            cluster.submit(r)
        cluster.router.dispatch()
        on_idle = [r.rid for r in cluster.engines[1].queue]
        if expect_idle:
            assert sorted(on_idle) == [100, 101], \
                "slack dispatch must route the burst to the idle replica"
        else:
            assert len(on_idle) < 2, \
                "fifo round-robin splits the burst regardless of load"


def test_router_rank_orders_priority_then_slack():
    cfg = get_smoke_config("qwen2.5-3b")
    cluster = ServingCluster(cfg, geometry=GEO, n_engines=1, max_batch=2,
                             max_seq=64, seed=0)
    mk = lambda rid, pri, dl: Request(
        rid=rid, tenant=0, prompt=np.arange(8, dtype=np.int32),
        max_new=2, priority=pri, deadline_us=dl)
    items = [(i, r) for i, r in enumerate([
        mk(0, 0, None), mk(1, 0, 1200.0), mk(2, 1, None),
        mk(3, 0, 500.0), mk(4, 1, 800.0)])]
    order = [r.rid for _, r in sorted(items, key=cluster.router._rank)]
    assert order == [4, 2, 3, 1, 0]


# ------------------------------------------------- deadline accounting


def test_engine_stats_deadline_accounting_and_summary():
    s = EngineStats(decode_steps=1, decode_tokens=1, wall_s=1.0)
    assert s.slo_attainment() is None       # no SLOs ≠ all SLOs met
    s.note_deadline(1, True)
    s.note_deadline(1, True)
    s.note_deadline(0, False)
    assert s.slo_attainment() == pytest.approx(2 / 3)
    assert s.slo_attainment(1) == 1.0 and s.slo_attainment(0) == 0.0
    line = s.summary()
    assert "SLO 66.7% (t1 2/2, t0 0/1)" in line
    assert "SLO" not in EngineStats().summary()


def test_engine_records_deadline_hits_and_misses():
    cfg = get_smoke_config("qwen2.5-3b")
    eng = ServingEngine(cfg, geometry=GEO, max_batch=2, max_seq=64,
                        manager_kind="mosaic", seed=0,
                        decode_window_us=1000.0, prefix_cache=False)
    hit = Request(rid=0, tenant=0, prompt=np.arange(8, dtype=np.int32),
                  max_new=2, priority=1, deadline_us=1e9)
    miss = Request(rid=1, tenant=0, prompt=np.arange(8, dtype=np.int32),
                   max_new=2, priority=0, deadline_us=1e-3)
    for r in (hit, miss):
        eng.submit(r)
    eng.run_until_drained(max_steps=100)
    assert eng.stats.deadline_hits == {1: 1}
    assert eng.stats.deadline_misses == {0: 1}


def test_cluster_stats_aggregation():
    a, b = EngineStats(), EngineStats()
    a.faults, b.faults = 3, 4
    a.prefill_tokens, b.prefill_tokens = 10, 20
    a.note_deadline(0, True)
    b.note_deadline(0, False)
    b.note_deadline(2, True)
    agg = aggregate_engine_stats([a, b])
    assert agg.faults == 7 and agg.prefill_tokens == 30
    assert agg.deadline_hits == {0: 1, 2: 1}
    assert agg.deadline_misses == {0: 1}
    assert agg.slo_attainment() == pytest.approx(2 / 3)


def test_cluster_stats_aggregation_merges_spans_and_wait_rings():
    """Span counters merge name by name, and the wait rings pool every
    engine's samples, keeping the ring's bound."""
    from repro.serving.trace import WAIT_SAMPLES
    a, b = EngineStats(), EngineStats()
    a.span_n.update({"engine.step": 2, "mm.preempt": 1})
    b.span_n.update({"engine.step": 3})
    a.span_ns.update({"engine.step": 50, "mm.preempt": 20})
    b.span_ns.update({"engine.step": 70})
    a.span_self_ns.update({"engine.step": 30, "mm.preempt": 20})
    b.span_self_ns.update({"engine.step": 70})
    a.compiles.update({"engine.prefill": 2})
    b.compiles.update({"engine.prefill": 1, "model.decode": 1})
    a.queue_wait_ns.extend([5, 6])
    b.queue_wait_ns.extend([7])
    b.resume_wait_ns.extend(range(WAIT_SAMPLES))
    a.resume_wait_ns.append(-1)
    agg = aggregate_engine_stats([a, b])
    assert agg.span_n == {"engine.step": 5, "mm.preempt": 1}
    assert agg.span_ns == {"engine.step": 120, "mm.preempt": 20}
    assert agg.span_self_ns == {"engine.step": 100, "mm.preempt": 20}
    assert agg.compiles == {"engine.prefill": 3, "model.decode": 1}
    assert list(agg.queue_wait_ns) == [5, 6, 7]
    # a's one sample came first, so b's full ring pushes it out.
    assert list(agg.resume_wait_ns) == list(range(WAIT_SAMPLES))
    assert a.span_n == {"engine.step": 2, "mm.preempt": 1}   # inputs kept


# ------------------------------------------------- MoE/MLA park fallback


def test_moe_engine_skips_park_into_shared_index():
    """Regression (satellite): a non-dense replica attached to a shared
    index must never park its KV (MoE routing is batch-shape-dependent —
    the cached pages would be unreplayable) and must never match."""
    cfg = get_smoke_config("dbrx-132b")
    assert cfg.family == "moe"
    store = HostPageStore()
    idx = PrefixIndex(store, GEO.page_tokens)
    eng = ServingEngine(cfg, geometry=GEO, max_batch=2, max_seq=64,
                        manager_kind="mosaic", seed=0, prefix_index=idx)
    assert not eng.prefix_supported and eng.prefix is idx
    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab_size, 24).astype(np.int32)
    reqs = [Request(rid=i, tenant=0,
                    prompt=np.concatenate(
                        [shared, rng.integers(0, cfg.vocab_size, 6)
                         .astype(np.int32)]), max_new=2)
            for i in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained(max_steps=200)
    assert all(r.done for r in reqs)
    assert eng.stats.prefix_park_skipped == 3      # one per completion
    assert eng.stats.prefix_hits == 0 and len(idx) == 0
    assert len(store) == 0                         # nothing unreplayable
    assert "parks skipped 3" in eng.stats.summary()


def test_mla_config_is_prefix_incompatible():
    cfg = get_smoke_config("deepseek-v2-lite-16b")
    assert cfg.mla is not None
    eng = ServingEngine(cfg, geometry=GEO, max_batch=1, max_seq=32,
                        manager_kind="mosaic", seed=0)
    # MLA caches latents, not K/V — no index is built, and a shared one
    # would be skip-counted (prefix_supported gates both paths).
    assert not eng.prefix_supported and eng.prefix is None


# --------------------------------------------- §11 correctness satellites


def test_frame_table_migrate_excludes_stale_keys():
    """Regression: migrate() used to report len(keys) even when some
    keys were never placed (or already released) — the router's
    migrated_pages stat over-counted.  Only re-leased pages count."""
    ft = HostFrameTable(frame_pages=2)
    live = [(1, 0, 0), (1, 0, 1)]
    for k in live:
        ft.place(0, k)
    stale = (1, 0, 99)                         # never placed
    moved = ft.migrate(live + [stale], dst=1)
    assert moved == 2
    assert ft.owner_of(stale) is None
    released = (1, 0, 1)
    ft.release(released)
    assert ft.migrate(live, dst=2) == 1        # released key now stale too
    ft.check_invariants()


def test_frame_table_migrate_partially_shared_frames_invariants():
    """Every migrate shape at once — whole-frame flip, re-placement out
    of a shared frame, stale keys, and a same-owner no-op — with the
    lease invariants checked after."""
    ft = HostFrameTable(frame_pages=2)
    whole = [(1, 0, 0), (1, 0, 1)]             # exclusive, full frame
    shared_mig = [(2, 0, 0)]                   # shares a frame with...
    shared_stay = [(3, 0, 0)]                  # ...a non-migrating page
    for k in whole + shared_mig + shared_stay:
        ft.place(0, k)
    already = [(4, 0, 0)]
    ft.place(1, already[0])                    # already at dst: no-op
    moved = ft.migrate(whole + shared_mig + already + [(9, 9, 9)], dst=1)
    assert moved == 3                          # stale + same-dst excluded
    assert ft.stats["whole_frame_moves"] == 1
    assert ft.stats["page_moves"] == 1
    assert {ft.owner_of(k) for k in whole + shared_mig + already} == {1}
    assert ft.owner_of(shared_stay[0]) == 0
    # The split frame holds one page per domain — in different frames.
    assert ft._key_frame[shared_mig[0]] != ft._key_frame[shared_stay[0]]
    ft.check_invariants()


def test_view_drop_seq_releases_every_frame_slot():
    """drop_seq must release each dropped page's frame slot: a frame
    shared by two sequences survives (slots partially freed), and fully
    freed frames recycle for another domain."""
    tier = SharedHostTier(GEO, n_engines=2)
    v = tier.view(0)
    for i in range(5):                         # 5 pages → 2 frames of 4
        v.put(1, 0, i, *_payload(float(i)))
    v.put(2, 0, 0, *_payload(9.0))             # co-tenant in frame 2
    shared_frame = tier.frames._key_frame[(2, 0, 0)]
    assert shared_frame == tier.frames._key_frame[(1, 0, 4)]
    assert v.drop_seq(1) == 5
    tier.check_invariants()
    # The exclusive frame recycled; the shared one kept only seq 2.
    assert len(tier.frames) == 1
    assert tier.frames.keys_of(shared_frame) == {(2, 0, 0)}
    assert tier.frames.stats["frames_recycled"] == 1
    # Freed slots are reusable by a different domain immediately.
    v1 = tier.view(1)
    for i in range(4):
        v1.put(3, 0, i, *_payload(float(i)))
    assert len(tier.frames) == 2               # reuses the recycled frame
    tier.check_invariants()
    assert v.drop_seq(2) == 1 and v1.drop_seq(3) == 4
    assert len(tier.frames) == 0


def _prefix_index_invariants(idx, store):
    # (a) Prefix-closure: every cached page's parent chain is cached.
    for p in idx._pages.values():
        if p.parent is not None:
            assert p.parent in idx._pages, "orphaned prefix page"
            assert idx._pages[p.parent].page_index == p.page_index - 1
    # (b/c) Index ↔ store payload consistency, both directions.
    index_keys = {(p.owner, p.shard, p.vpn) for p in idx._pages.values()}
    store_keys = set(store._pages)
    assert index_keys == store_keys, "index and store disagree"


def test_prefix_index_prefix_closed_randomized():
    """Property test (seeded): random park/match/evict interleavings
    keep the index prefix-closed and index↔store consistent."""
    rng = np.random.default_rng(42)
    store = HostPageStore()
    idx = PrefixIndex(store, PTOK, capacity_pages=6)
    streams = [rng.integers(0, 997, 4 * PTOK).astype(np.int32)
               for _ in range(5)]
    vpn = 0
    for _ in range(300):
        op = rng.integers(0, 3)
        toks = streams[rng.integers(0, len(streams))]
        n_pages = int(rng.integers(1, 5))
        toks = toks[:n_pages * PTOK]
        if op == 0:                            # park the missing suffix
            hashes = idx.chain_hashes(toks)
            start = idx.missing_from(hashes)
            for i in range(start, len(hashes)):
                parent = hashes[i - 1] if i > 0 else None
                idx.park(hashes[i], parent, i, 0, vpn,
                         *_payload(float(vpn % 50)))
                vpn += 1
        elif op == 1:                          # match touches LRU ticks
            n, pages = idx.match(toks)
            assert n <= n_pages
            for pg in pages:                   # every hit is readable
                idx.payload(pg)
        else:                                  # external owner eviction
            if idx._pages:
                victims = rng.choice(
                    [p.owner for p in idx._pages.values()],
                    size=min(2, len(idx._pages)), replace=False)
                idx.evict_owner_pages(int(o) for o in victims)
        assert len(idx) <= idx.capacity_pages
        _prefix_index_invariants(idx, store)
    assert idx.stats["parked_pages"] > 0 and idx.stats["evicted_pages"] > 0
    assert idx.stats["hit_pages"] > 0


def test_engine_wall_clock_survives_wall_time_jumps(monkeypatch):
    """Regression: engine timing used time.time(), so an NTP step (or a
    frozen clock, as here) corrupted wall_s/tok_per_s.  perf_counter is
    monotonic — a constant time.time() must not zero the throughput."""
    import time as time_mod
    monkeypatch.setattr(time_mod, "time", lambda: 1234.5)
    cfg = get_smoke_config("qwen2.5-3b")
    eng = ServingEngine(cfg, geometry=GEO, max_batch=2, max_seq=64,
                        manager_kind="mosaic", seed=0, prefix_cache=False)
    r = Request(rid=0, tenant=0, prompt=np.arange(16, dtype=np.int32),
                max_new=2)
    eng.submit(r)
    eng.run_until_drained(max_steps=100)
    assert r.done
    assert eng.stats.wall_s > 0.0
    assert eng.stats.tok_per_s() > 0.0


def test_frame_table_invariants_under_randomized_failure_sequences():
    """Property test (seeded, DESIGN.md §12): random interleavings of
    page placement, write-back pumping, spill/promote, sequence
    migration, whole-sequence drops, and whole-domain crash reclaim
    keep HostFrameTable.check_invariants() (and the tier's stronger
    cross-tier checks) true after every operation."""
    rng = np.random.default_rng(11)
    geo = PoolGeometry(page_tokens=8, frame_pages=2, compact_threshold=0.4)
    tier = SharedHostTier(geo, n_engines=3, capacity_frames=4, spill=True)
    home = {}                                  # seq → owning domain
    next_vpn = {}                              # seq → next fresh page
    for _ in range(250):
        op = int(rng.integers(0, 7))
        if op <= 1 or not home:                # place a fresh page
            seq = int(rng.integers(0, 12))
            d = home.setdefault(seq, int(rng.integers(0, 3)))
            vpn = next_vpn.get(seq, 0)
            tier.view(d).put(seq, 0, vpn, *_payload(float(seq + vpn)))
            next_vpn[seq] = vpn + 1
        elif op == 2:                          # advance the pump
            tier.pump(tier._now_us + float(rng.integers(1, 5000)))
        elif op == 3:                          # settle every write-back
            tier.flush()
        elif op == 4 and tier._spilled:        # promote-on-touch
            key = sorted(tier._spilled)[
                int(rng.integers(0, len(tier._spilled)))]
            tier.ensure_resident([key])
        elif op == 5:                          # migrate a sequence
            seq = sorted(home)[int(rng.integers(0, len(home)))]
            dst = int(rng.integers(0, 3))
            if dst != home[seq]:
                tier.migrate_seq(seq, dst)
                home[seq] = dst
        else:                                  # crash: reclaim a domain
            d = int(rng.integers(0, 3))
            if rng.random() < 0.5:
                tier.reclaim_domain(d)
                for seq in [s for s, dd in home.items() if dd == d]:
                    home.pop(seq)
                    next_vpn.pop(seq, None)
            elif home:                         # or drop one sequence
                seq = sorted(home)[int(rng.integers(0, len(home)))]
                tier.view(home.pop(seq)).drop_seq(seq)
                next_vpn.pop(seq, None)
        tier.frames.check_invariants()
        tier.check_invariants()
        for seq, d in home.items():            # leases track the tracker
            for k in tier.seq_pages(seq):
                assert tier.frames.owner_of(k) == d
    assert tier.stats["spilled_frames"] > 0
    assert tier.stats["promoted_frames"] > 0
    assert tier.stats["reclaimed_frames"] > 0
    tier.flush()
    tier.check_invariants()
    tier.spill_store.close()


# -------------------------------------------------- victim scoring (§11/§13)


def test_spill_victim_cost_scoring_diverges_from_lru():
    """A/B the two policies on the same access trace: frame A is full
    (2 pages) but cold, frame B holds one hot page.  Pure LRU spills B
    (stalest tick once A is touched last); cost scoring spills A — its
    hit-frequency × promote-cost score is lower despite the fresh tick."""
    picks = {}
    for policy in ("lru", "cost"):
        ft = HostFrameTable(frame_pages=2, victim_scoring=policy)
        ft.place(0, (1, 0, 0))                 # frame A …
        ft.place(0, (1, 0, 1))                 # … full at 2 pages
        ft.place(0, (1, 0, 2))                 # frame B, 1 page
        for _ in range(8):
            ft.touch((1, 0, 2))                # B is hot
        ft.touch((1, 0, 0))                    # A touched last (fresh tick)
        picks[policy] = ft.spill_victim()
    assert picks["lru"] == 1                   # stalest tick
    assert picks["cost"] == 0                  # cheapest to re-promote
    assert picks["lru"] != picks["cost"]


def test_spill_victim_cost_ties_break_by_lru_tick():
    ft = HostFrameTable(frame_pages=1, victim_scoring="cost")
    ft.place(0, (1, 0, 0))
    ft.place(0, (1, 0, 1))
    ft.touch((1, 0, 0))                        # equal hits+size, older tick
    assert ft.spill_victim() == 1              # (1,0,1) never re-touched


def test_recycled_frame_does_not_inherit_heat():
    ft = HostFrameTable(frame_pages=1, victim_scoring="cost")
    ft.place(0, (1, 0, 0))
    for _ in range(10):
        ft.touch((1, 0, 0))                    # frame 0 runs hot
    hot = ft._frame_hits[0]
    ft.release((1, 0, 0))                      # frame 0 recycled …
    ft.place(0, (2, 0, 0))                     # … by a fresh lease
    assert ft.frame_of((2, 0, 0)) == 0
    assert ft._frame_hits[0] < hot             # heat wiped, not inherited


def test_victim_scoring_flag_validated():
    with pytest.raises(ValueError, match="victim_scoring"):
        HostFrameTable(frame_pages=2, victim_scoring="mru")


# --------------------------------- cross-feature stress (§11/§12/§14)


@pytest.mark.router
@pytest.mark.faults
def test_cluster_crashes_spill_steal_prestage_randomized():
    """Property test (seeded via ROUTER_TEST_SEED): a randomized
    schedule mixing engine crashes (FaultPlan), spill back-pressure
    under a tight frame cap, queued-steal, and pre-staging drains
    completely with no leaked host-frame leases and no orphaned
    staging slots or pre-stage entries on the survivors."""
    from repro.serving.faults import FaultInjector, FaultPlan

    seed = int(os.environ.get("ROUTER_TEST_SEED", "0"))
    rng = np.random.default_rng(seed)
    cfg = get_smoke_config("qwen2.5-3b")
    dead = (0, 2)
    inj = FaultInjector(FaultPlan(seed=seed,
                                  engine_crashes=((4, dead[0]),
                                                  (9, dead[1]))))
    cluster = ServingCluster(cfg, geometry=GEO, n_engines=3, max_batch=2,
                             max_seq=128, seed=0, capacity_frames=4,
                             spill=True, wb_queue_frames=2,
                             router_prestage=True,
                             decode_window_us=1000.0,
                             fault_injector=inj)
    shared = [rng.integers(0, cfg.vocab_size,
                           PTOK * int(rng.integers(3, 6))).astype(np.int32)
              for _ in range(2)]
    reqs, rid = [], 0
    for _ in range(12):
        for _ in range(int(rng.integers(0, 3))):
            if rng.random() < 0.6:          # shared-prefix request
                base = shared[int(rng.integers(0, 2))]
                prompt = np.concatenate([base, rng.integers(
                    0, cfg.vocab_size,
                    int(rng.integers(8, 25))).astype(np.int32)])
            else:                           # cold request
                prompt = rng.integers(0, cfg.vocab_size,
                                      int(rng.integers(8, 49))
                                      ).astype(np.int32)
            req = Request(
                rid=rid, tenant=rid % 3,
                priority=int(rng.integers(0, 3)), prompt=prompt,
                max_new=int(rng.integers(2, 9)),
                deadline_us=(None if rng.random() < 0.5 else
                             float(rng.integers(5_000, 40_000))))
            reqs.append(req)
            cluster.submit(req)
            rid += 1
        cluster.step()
    cluster.run_until_drained(max_steps=3000)
    assert all(r.done for r in reqs), \
        [r.rid for r in reqs if not r.done]
    cluster.check_invariants()
    tier = cluster.tier
    # Crashed domains were reclaimed whole: no lease survives them.
    leaked = [k for k in tier.frames._key_frame
              if tier.frames.owner_of(k) in dead]
    assert not leaked, leaked
    # Survivors hold no orphaned staging slots or pre-stage entries.
    for e in cluster.engines:
        if e.alive:
            assert len(e.staging) == 0, (e.engine_id, len(e.staging))
            assert not e._prestage_keys
            assert not e.prefetch.in_flight
    # The schedule actually exercised every feature under test.
    assert cluster.router.stats.crashes == 2
    assert cluster.router.stats.prestaged_requests > 0
    assert tier.stats["spilled_frames"] > 0


_PLACEMENT = """
import jax, numpy as np
from repro.configs import get_smoke_config
from repro.configs.base import PoolGeometry
from repro.serving.cluster import ServingCluster
from repro.serving.engine import Request
devs = jax.devices()
assert len(devs) == 4, devs
cl = ServingCluster(get_smoke_config("qwen2.5-3b"),
                    geometry=PoolGeometry(page_tokens=8, frame_pages=4),
                    n_engines=4, max_batch=2, max_seq=64)
for i, e in enumerate(cl.engines):
    held = {d for x in jax.tree.leaves((e.params, e.pools))
            for d in x.devices()}
    assert held == {devs[i]}, (i, held)
rng = np.random.default_rng(0)
for i in range(6):
    cl.submit(Request(rid=i, tenant=i % 2, max_new=3,
                      prompt=rng.integers(0, 512, 12).astype(np.int32)))
cl.run_until_drained()
assert all(e.stats.decode_tokens > 0 for e in cl.engines)
print("PLACED")
"""


def test_cluster_places_engine_i_on_device_i():
    """On four (virtual CPU) devices each replica's weights and pools are
    committed to its own device, and every replica serves from there."""
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", _PLACEMENT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PLACED" in out.stdout
