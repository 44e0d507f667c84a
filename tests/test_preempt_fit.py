"""The admission fit test: evict only when the candidate will then fit.

``ServingEngine._alloc_with_preemption`` (and the batch-slot displacement
in ``_admit``) first asks whether the candidate could be placed with every
eligible victim gone.  If it could not, nobody is evicted — a preemption
would pay a device→host copy for an admission that fails anyway, and the
victim would resume in the same round.  The test is a necessary condition
(frames after compaction under Mosaic, pages under GPU-MMU), so it never
refuses an admission the eviction loop would complete.
"""

import dataclasses
import importlib.util
import json
import pathlib
import sys

import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import PoolGeometry
from repro.core.cocoa import OutOfMemory
from repro.serving.cluster import SharedHostTier
from repro.serving.engine import EngineStats, Request, ServingEngine
from repro.serving.host_tier import HostPageStore
from repro.serving.kv_cache import ShardedKVCache

GEO = PoolGeometry(page_tokens=8, frame_pages=4, headroom=1.25,
                   compact_threshold=0.4)
MANAGERS = ("mosaic", "gpu-mmu")
REPO = pathlib.Path(__file__).resolve().parents[1]


def pooled_engine(manager, pool_pages, *, max_batch, max_seq=128,
                  cfg=None, geo=GEO, **kw):
    """An engine whose pool holds exactly ``pool_pages`` pages."""
    cfg = cfg or get_smoke_config("qwen2.5-3b")
    over = geo.pages_for(max_seq, max_batch) / pool_pages
    eng = ServingEngine(cfg, geometry=geo, max_batch=max_batch,
                        max_seq=max_seq, manager_kind=manager, seed=0,
                        oversubscription=over, **kw)
    assert eng.cache.pages_per_shard == pool_pages
    return eng


def req(rid, priority, n_prompt, max_new=3, seed=0):
    rng = np.random.default_rng(seed + rid)
    return Request(rid=rid, tenant=priority, priority=priority,
                   prompt=rng.integers(0, 512, n_prompt).astype(np.int32),
                   max_new=max_new)


def loaded_engine(manager, max_batch):
    """A 24-page (6-frame) pool holding a priority-2 request of 12 pages
    (3 frames) and a priority-0 request of 4 pages (1 frame): 8 pages
    free.  A priority-1 candidate may evict only the priority-0 one, which
    leaves it at most 12 pages."""
    eng = pooled_engine(manager, 24, max_batch=max_batch)
    keep, victim = req(0, 2, 90, max_new=6), req(1, 0, 26, max_new=6)
    eng.submit(keep)
    eng.submit(victim)
    eng.step()
    assert eng.active == [keep, victim]
    return eng, keep, victim


def assert_resident(eng, r):
    assert r in eng.active
    assert eng.cache.resident_page_count(r.rid) \
        == len(eng.cache.mapped_pages(r.rid)) > 0


@pytest.mark.parametrize("manager", MANAGERS)
def test_candidate_that_cannot_fit_evicts_nobody(manager):
    """(a) 13 pages cannot fit in the 12 that evicting every eligible
    victim would leave: no swap-out, the would-be victim stays active and
    resident, and the refusal is counted."""
    eng, keep, victim = loaded_engine(manager, max_batch=3)
    big = req(2, 1, 100)
    eng.submit(big)
    eng.step()
    assert eng.stats.swaps_out == 0
    assert eng.stats.evictions_declined >= 1
    assert big in eng.queue
    assert_resident(eng, keep)
    assert_resident(eng, victim)
    eng.cache.check_invariants()
    eng.run_until_drained(max_steps=200)
    assert big.done and keep.done and victim.done


@pytest.mark.parametrize("max_batch", [3, 2], ids=["memory", "batch-slot"])
@pytest.mark.parametrize("manager", MANAGERS)
def test_candidate_that_can_fit_still_preempts(manager, max_batch):
    """(b) 9 pages fit once the priority-0 request leaves (growth headroom
    included): it is preempted and the candidate admitted, as before —
    through the memory path, and through the batch-slot path when the
    batch is full."""
    eng, keep, victim = loaded_engine(manager, max_batch=max_batch)
    fits = req(2, 1, 72)
    eng.submit(fits)
    eng.step()
    assert fits in eng.active
    assert victim not in eng.active
    assert eng.stats.swaps_out == 1
    assert eng.stats.evictions_declined == 0
    eng.run_until_drained(max_steps=200)
    assert fits.done and keep.done and victim.done
    eng.cache.check_invariants()


def test_fit_that_needs_compaction_still_admits():
    """(c) The Mosaic bound counts frames after compaction at its best, so
    it is no tighter than the allocator: a candidate that fits only once
    the victim leaves *and* CAC merges another owner's splintered frames
    is still admitted."""
    eng = pooled_engine("mosaic", 20, max_batch=2, max_seq=96)
    mgr = eng.cache.mgrs[0]
    other = 10 ** 6                      # an owner the engine never evicts
    eng.cache.allocate(other, 8 * GEO.page_tokens)     # frames 0 and 1
    mgr.free_pages(other, [1, 2, 3])     # frame 0 keeps one page
    mgr.append_tokens(other, 1)          # frame 2 takes one page
    assert mgr.pool.stats["compaction_copies"] == 0
    victim = req(1, 0, 28)               # frame 3
    eng.submit(victim)
    eng.step()
    cand = req(2, 1, 92)                 # 12 pages: 3 frames of the 5
    assert eng.cache.fits_if_released(92, [victim.rid])
    assert not eng.cache.fits_if_released(92, [])
    eng.submit(cand)
    eng.step()
    assert cand in eng.active
    assert eng.stats.swaps_out == 1 and eng.stats.evictions_declined == 0
    assert mgr.pool.stats["compaction_copies"] >= 1
    eng.cache.check_invariants()


@pytest.mark.parametrize("manager", MANAGERS)
def test_batch_slot_displaces_nobody_for_a_candidate_memory_cannot_hold(
        manager):
    """(d) With a full batch, a priority-1 candidate may displace the
    priority-0 request for its slot — but not when memory could not hold
    the candidate even then."""
    eng, keep, victim = loaded_engine(manager, max_batch=2)
    big = req(2, 1, 100)
    eng.submit(big)
    eng.step()
    assert eng.stats.swaps_out == 0
    assert eng.stats.evictions_declined >= 1
    assert_resident(eng, keep)
    assert_resident(eng, victim)
    eng.run_until_drained(max_steps=200)
    assert big.done and keep.done and victim.done


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod          # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def _closed_loop(eng, plan, clients, steps):
    """Drive ``plan`` as ``clients`` closed-loop clients for ``steps``
    engine steps; returns {plan index: tokens}."""
    reqs = {}

    def submit(j):
        p = plan.upto(j)
        reqs[p.idx] = Request(rid=p.idx, tenant=p.tenant,
                              priority=p.priority, prompt=p.prompt,
                              max_new=p.max_new)
        eng.submit(reqs[p.idx])

    for c in range(clients):
        submit(c)
    nxt = {c: c + clients for c in range(clients)}
    seen = set()
    for _ in range(steps):
        eng.step()
        for r in list(reqs.values()):
            if r.done and r.rid not in seen:
                seen.add(r.rid)
                c = r.rid % clients
                submit(nxt[c])
                nxt[c] += clients
    return {k: list(r.out) for k, r in reqs.items()}


def test_oversubscribed_tenant_geometry_preempts_rarely_token_identical():
    """(e) The phi3 tenants-oversub cell's geometry and traffic (64-token
    pages, 16-page frames, a 96-page pool, 8 closed-loop clients at
    priorities 0-2, the benchmark's fixed order) at tiny widths: over 150
    steps the fit test turns away the big candidates that evicting could
    not seat, at most 5 preemptions remain (decode growth's), and every
    token matches a run whose pool holds everything."""
    traffic = _load("bench_traffic", REPO / "benchmarks/chip/traffic.py")
    mix = json.loads((REPO / "benchmarks/chip/traffic/tenants-oversub.json")
                     .read_text())
    e = mix["engine"]
    cfg = dataclasses.replace(get_smoke_config("phi3-mini-3.8b"),
                              max_seq_len=e["max_seq"])
    geo = PoolGeometry(page_tokens=e["page_tokens"],
                       frame_pages=e["frame_pages"])
    kw = dict(prefix_capacity_pages=e["prefix_capacity_pages"])
    tight = pooled_engine("mosaic", e["pool_pages"], cfg=cfg, geo=geo,
                          max_batch=e["max_batch"], max_seq=e["max_seq"],
                          **kw)
    roomy = ServingEngine(cfg, geometry=geo, max_batch=e["max_batch"],
                          max_seq=e["max_seq"], seed=0, **kw)
    outs = [_closed_loop(eng, traffic.plan(mix, 0, cfg.vocab_size, 40),
                         mix["clients"], 150) for eng in (tight, roomy)]
    assert tight.stats.swaps_out <= 5
    assert tight.stats.evictions_declined > 0
    assert tight.stats.decode_steps == 150
    compared = 0
    for rid, toks in outs[0].items():
        other = outs[1].get(rid, [])
        n = min(len(toks), len(other))
        assert toks[:n] == other[:n], rid
        compared += n
    assert compared >= 500


@pytest.mark.parametrize("manager", MANAGERS)
def test_fit_bound_is_never_tighter_than_the_allocator(manager):
    """Random owners on a striped, fragmented pool: whenever a fresh
    allocation succeeds (with compaction), ``fits_if_released`` said it
    could; and the bound with every owner released is the whole pool."""
    rng = np.random.default_rng(7)
    cache = ShardedKVCache(GEO, pages_per_shard=32, n_shards=2,
                           manager_kind=manager)
    ptok = GEO.page_tokens
    owners = []
    succeeded = 0
    for i in range(300):
        op = rng.random()
        if owners and op < 0.25:
            cache.free(owners.pop(int(rng.integers(len(owners)))))
        elif owners and op < 0.4:
            o = owners[int(rng.integers(len(owners)))]
            s = int(rng.integers(cache.S))
            table = cache.mgrs[s].tables.get(o)
            mapped = table.mapped_vpns() if table else []
            if len(mapped) > 1:          # trim holes: CAC compacts
                k = int(rng.integers(1, len(mapped)))
                cache.mgrs[s].free_pages(
                    o, [int(v) for v in rng.choice(mapped, k, replace=False)])
        else:
            n = int(rng.integers(1, 20 * ptok))
            said = cache.fits_if_released(n, [])
            try:
                cache.allocate(i, n)
            except OutOfMemory:
                cache.free(i)
            else:
                assert said, (i, n)
                owners.append(i)
                succeeded += 1
    assert succeeded > 20
    full = cache.S * cache.pages_per_shard * ptok
    assert cache.fits_if_released(full, owners)
    assert not cache.fits_if_released(full + 1, owners)


def test_shard_pages_follow_frame_striping():
    cache = ShardedKVCache(GEO, pages_per_shard=64, n_shards=3,
                           manager_kind="mosaic")
    ftok = GEO.frame_pages * GEO.page_tokens
    assert cache.shard_pages(1) == [1, 0, 0]
    assert cache.shard_pages(ftok) == [4, 0, 0]
    assert cache.shard_pages(ftok + 1) == [4, 1, 0]
    assert cache.shard_pages(4 * ftok + 9) == [8, 6, 4]
    cache.allocate(0, 4 * ftok + 9)
    assert [len(m.tables[0].mapped_vpns()) for m in cache.mgrs] \
        == cache.shard_pages(4 * ftok + 9)


def test_summary_prints_declined_evictions_next_to_swaps():
    line = EngineStats(swaps_out=3, swaps_in=2,
                       evictions_declined=5).summary()
    assert "swaps 3/2 (5 declined)" in line


def test_is_lost_peeks_without_clearing_the_flag():
    """The batch-slot fit test sizes a lost resume by its prompt: it asks
    ``is_lost``, which must leave the flag for ``_resume``'s
    ``take_lost``."""
    tier = SharedHostTier(GEO, n_engines=1, capacity_frames=2)
    view = tier.view(0)
    tier.lost_seqs.add(9)
    assert view.is_lost(9) and view.is_lost(9)
    assert view.take_lost(9)
    assert not view.is_lost(9) and not view.take_lost(9)
    assert not HostPageStore().is_lost(9)
    tier.spill_store.close()
