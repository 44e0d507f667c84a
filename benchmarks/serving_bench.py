"""Multi-tenant serving benchmark: Mosaic vs GPU-MMU manager on the real
engine (the LLM-serving analogue of the paper's Figs. 5/6 setting).

Identical request streams through both managers; reports tokens/s (CPU
wall-clock — relative only), coalesced fraction (the structural quantity
that becomes TLB reach / kernel indirection savings on TPU), compaction
copy counts, and memory bloat.

Host-tier scenarios (DESIGN.md §6):

* ``oversubscribed_compare`` — the pool holds only 1/factor of the
  sized-for-peak KV working set; requests are preempted to host DRAM under
  ``OutOfMemory`` and resumed via base-page demand fault-in.  Reports
  faults, DMA descriptors, bytes_in, transfer_us and swap counts per
  manager; outputs stay identical.  Absolute swap counts are *not*
  comparable across managers here — whole-frame reservation makes Mosaic
  hit pressure at different moments than the page-packed baseline, so the
  two runs preempt different requests at different times.  The same-trace
  contiguity claim lives in ``swap_cycle_compare``.
* ``swap_cycle_compare`` — a *controlled* preempt→churn→resume cycle over
  the exact same trace for both managers, isolating the paper's
  contiguity-helps-transfer claim: Mosaic re-maps the resumed request into
  whole frames, so its fault batch merges into few contiguous DMAs, while
  the GPU-MMU baseline's scattered free list pays per-page setup.
* ``overlap_compare`` — the same oversubscribed trace under
  ``fault_mode="sync"`` (PR 1's blocking fault-in) vs ``"async"`` (the
  double-buffered prefetch pipeline, DESIGN.md §7) across
  oversubscription ratios: byte-identical tokens, and the async pipeline
  hides the bulk of the transfer µs behind decode compute.
* ``overlap_link_contention`` — the DMA-channel overlap model transplanted
  into the TLB-timing simulator's multi-app runs: cross-app queueing on
  the shared host↔device link (contention cycles) shrinks as channels are
  added.
* ``prefix_reuse_compare`` — the content-hash prefix cache (DESIGN.md §8):
  requests sharing a system-prompt prefix admitted with the cache on vs
  off.  Byte-identical tokens both ways; with the cache on, the shared
  prefix's KV pages fault in from the host tier at admission (merged
  DMAs through the async pipeline) instead of being re-decoded, so hit
  admissions compute ~suffix/prompt of the cold prefill and complete
  faster.
* ``duplex_compare`` — outbound eviction/parking gathers on vs off the
  DMA timeline (full-duplex "out" lanes): tokens unchanged, outbound
  traffic visible with per-direction hidden/exposed/queue invariants.
* ``duplex_sim_compare`` — the TLB simulator under an HBM capacity cap:
  capacity writebacks ride the link; a full-duplex link keeps them off
  the fault path, half-duplex queues faults behind them.

Cluster tier (DESIGN.md §10):

* ``cluster_prefix_share_compare`` — one process-wide ``PrefixIndex``
  over the shared host tier vs per-engine indexes: a prefix parked by
  replica 0 is a cache hit on replica 1 only when the index is shared,
  so the shared configuration achieves a strictly higher hit rate on a
  shared-prefix workload (tokens byte-identical either way).
* ``cluster_router_compare`` — deadline-aware (slack-ordered) dispatch
  vs FIFO round-robin on an unevenly loaded cluster: SLO attainment is
  higher when the router sends tight-deadline requests to the idle
  replica instead of queueing them behind long best-effort work.
* ``cluster_migration_compare`` — work-stealing migration of a preempted
  request to an idle replica: the destination decodes it with **zero
  re-prefill** (only its host-resident base pages change hands, via
  frame-lease re-assignment + fault-in over the destination's own DMA
  lanes), and tokens are byte-identical across 1-engine, N-engine, and
  N-engine-with-migration runs.
* ``cluster_sim_compare`` — the TLB simulator's cluster model:
  per-engine links remove cross-engine link contention, the shared host
  store re-serializes transfers on its DRAM lanes, and widening
  ``host_lanes`` relieves it.

Spill tier (DESIGN.md §11):

* ``spill_compare`` — the host tier under a hard ``capacity_frames``
  cap, spill on vs off, on a grouped-prefix two-wave workload that
  overflows the cap.  With spill on, LRU prefix frames ride the "out"
  DMA lanes to frame-granular disk files and promote back on wave-2
  touches, so every wave-2 admission is still a prefix hit and pays
  only a modeled promote stall; with spill off the same frames are
  hard-evicted through the prefix index, wave 2 re-prefills the full
  prompt, and p99 admission latency jumps.  Tokens byte-identical
  either way — the spill tier is pure memory management.
* ``spill_backpressure_compare`` — a saturated write-back buffer
  (1-deep queue, slow disk) makes ``park_allowed()`` go False: new
  prefix parks are *refused* (``prefix_park_refused``) instead of
  queueing unboundedly, and the queue never exceeds its bound.
* ``spill_sim_compare`` — the TLB simulator's disk model: capacity
  writebacks stream host→disk after their link transfer; the disk is an
  order of magnitude slower than the link, so one lane queues evictions
  (``disk_contention_cycles``) and a second lane relieves them.

Fused gather-attend decode (DESIGN.md §13):

* ``fused_decode_compare`` — sync vs async vs ``fault_mode="fused"`` on
  the oversubscribed trace: the fused path never blocks on the DMA
  engine before decode — arriving pages are consumed straight from the
  staging buffer by the readiness-masked attention path and only the
  transfer *tail* past the decode window is exposed.  Tokens are
  byte-identical across all three modes, and at the starved 2 µs
  window the fused path's exposed µs sit strictly below the async
  pipeline's (which must stall for every page before launch).
* ``fused_kernel_compare`` — the readiness-masked kernel against the
  gather-then-attend baseline on one synthetic batch: with every page
  resident the fused kernel's output is bitwise identical to the
  baseline paged kernel, and with half the pages staged it matches the
  scatter-then-attend result to float32 round-off.

Fault tolerance (DESIGN.md §12):

* ``faults_crash_compare`` — a seeded engine crash mid-decode vs the
  same crash with no failover, vs fault-free: the router re-homes the
  victim's preempted bundle to a survivor (zero re-prefill) and
  re-dispatches its in-flight/queued requests from the prompt;
  recovered tokens are byte-identical to the fault-free run and the
  deadline-met fraction is strictly above the no-failover baseline.
* ``faults_spill_compare`` — the spill workload under injected disk
  faults: bit-flipped spill frames are caught 100 % by the per-frame
  checksum (quarantine + re-derive, never decoded from); unbounded
  write errors trigger bounded retries with backoff then a graceful
  degrade to the hard-cap path with zero dropped requests; injected
  DMA stalls shift timing only, reproducibly under the same seed.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from repro.configs import get_smoke_config
from repro.configs.base import PoolGeometry
from repro.serving.engine import Request, ServingEngine

GEO = PoolGeometry(page_tokens=8, frame_pages=4, compact_threshold=0.4)


def run_engine(manager_kind: str, n_requests=8, max_new=8, seed=0):
    cfg = get_smoke_config("qwen2.5-3b")
    eng = ServingEngine(cfg, geometry=GEO, max_batch=4, max_seq=128,
                        manager_kind=manager_kind, seed=seed)
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_requests):
        T = int(rng.integers(24, 64))
        prompt = rng.integers(0, cfg.vocab_size, size=T).astype(np.int32)
        r = Request(rid=i, tenant=i % 3, prompt=prompt, max_new=max_new)
        reqs.append(r)
        eng.submit(r)
    eng.run_until_drained(max_steps=500)
    assert all(r.done for r in reqs)
    return eng, reqs


def serving_compare(n_requests=8) -> List[Dict]:
    rows = []
    outs = {}
    for kind in ("mosaic", "gpu-mmu"):
        eng, reqs = run_engine(kind, n_requests=n_requests)
        outs[kind] = {r.rid: tuple(r.out) for r in reqs}
        st = eng.cache.stats()
        rows.append({
            "bench": "serving", "manager": kind,
            "tok_per_s_cpu": round(eng.stats.tok_per_s(), 1),
            "coalesced_mean": round(eng.stats.coalesced_mean, 3),
            "compaction_copies": eng.stats.compaction_copies,
            "coalesce_ops": int(st.get("coalesce_ops", 0)),
            "memory_bloat": round(st.get("memory_bloat", 1.0), 3),
        })
    # Application-transparency check: identical outputs.
    identical = outs["mosaic"] == outs["gpu-mmu"]
    rows.append({"bench": "serving", "manager": "CHECK",
                 "outputs_identical": identical})
    assert identical, "manager changed model outputs!"
    return rows


# ------------------------------------------------------------ host tier


def run_oversubscribed(manager_kind: str, *, factor: float = 2.0,
                       n_requests: int = 12, seed: int = 0,
                       fault_mode: str = "async",
                       decode_window_us=None, duplex: bool = True):
    """2× (by default) oversubscribed multi-tenant run to completion.

    The prefix cache stays OFF here: these prompts share no prefixes,
    so parking on completion would only add gather traffic unrelated to
    what the PR 1/PR 2 suites measure (their BENCH_serving.json
    trajectories must stay comparable across PRs); reuse is measured by
    its own ``prefix-reuse`` suite."""
    cfg = get_smoke_config("qwen2.5-3b")
    eng = ServingEngine(cfg, geometry=GEO, max_batch=6, max_seq=96,
                        manager_kind=manager_kind, seed=0,
                        oversubscription=factor, fault_mode=fault_mode,
                        decode_window_us=decode_window_us, duplex=duplex,
                        prefix_cache=False)
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_requests):
        # Decode-heavy: the working set grows well past the pool mid-run,
        # so pressure comes from appends, not just admission.
        T = int(rng.integers(24, 56))
        prompt = rng.integers(0, cfg.vocab_size, size=T).astype(np.int32)
        r = Request(rid=i, tenant=i % 3, prompt=prompt,
                    max_new=int(rng.integers(24, 40)))
        reqs.append(r)
        eng.submit(r)
    eng.run_until_drained(max_steps=5000)
    assert all(r.done for r in reqs), "oversubscribed run did not drain"
    eng.cache.check_invariants()
    return eng, reqs


def oversubscribed_compare(factor: float = 2.0,
                           n_requests: int = 12) -> List[Dict]:
    rows = []
    outs = {}
    for kind in ("mosaic", "gpu-mmu"):
        eng, reqs = run_oversubscribed(kind, factor=factor,
                                       n_requests=n_requests)
        outs[kind] = {r.rid: tuple(r.out) for r in reqs}
        s = eng.stats
        rows.append({
            "bench": "serving-oversub", "manager": kind, "factor": factor,
            "tok_per_s_cpu": round(s.tok_per_s(), 1),
            "swaps_out": s.swaps_out, "swaps_in": s.swaps_in,
            "faults": s.faults, "fault_dmas": s.fault_dmas,
            "bytes_in": s.bytes_in,
            "transfer_us": round(s.transfer_us, 1),
            "exposed_us": round(s.fault_exposed_us, 1),
            "hidden_us": round(s.fault_hidden_us, 1),
            "host_peak_pages": eng.host.stats["peak_pages"],
        })
    identical = outs["mosaic"] == outs["gpu-mmu"]
    paged = any(r["swaps_out"] > 0 and r["faults"] > 0
                for r in rows if "swaps_out" in r)
    rows.append({"bench": "serving-oversub", "manager": "CHECK",
                 "outputs_identical": identical,
                 "paging_exercised": paged})
    assert identical, "oversubscription changed model outputs!"
    assert paged, "oversubscribed run never touched the host tier"
    return rows


def run_swap_cycle(manager_kind: str):
    """Controlled preempt→churn→resume cycle (same trace per manager).

    r1/r3/r5 finish early (their frees pepper the pool), r2/r4 keep
    decoding into the holes while r0 is held swapped out; the resume
    fault-in then measures how contiguous the re-mapped pages are.
    """
    cfg = get_smoke_config("qwen2.5-3b")
    eng = ServingEngine(cfg, geometry=GEO, max_batch=6, max_seq=96,
                        manager_kind=manager_kind, seed=0)
    rng = np.random.default_rng(0)
    spec = [(35, 40), (4, 18), (5, 70), (6, 20), (7, 70), (8, 22)]
    reqs = []
    for i, (T, mn) in enumerate(spec):
        r = Request(rid=i, tenant=i % 3,
                    prompt=rng.integers(0, cfg.vocab_size, T)
                    .astype(np.int32), max_new=mn)
        reqs.append(r)
        eng.submit(r)
    for _ in range(80):
        eng.step()
        if reqs[1].done and reqs[3].done and reqs[5].done:
            break
    assert not reqs[0].done, "preempt target finished too early"
    eng.preempt(0, hold=True)
    eng.cache.check_invariants()
    for _ in range(16):              # churn: live requests fill the holes
        eng.step()
    pre_dmas, pre_faults = eng.stats.fault_dmas, eng.stats.faults
    eng.release(0)
    eng.run_until_drained(max_steps=400)
    assert all(r.done for r in reqs)
    eng.cache.check_invariants()
    return (eng, reqs, eng.stats.fault_dmas - pre_dmas,
            eng.stats.faults - pre_faults)


def swap_cycle_compare() -> List[Dict]:
    rows = []
    outs = {}
    dmas = {}
    for kind in ("mosaic", "gpu-mmu"):
        eng, reqs, resume_dmas, resume_faults = run_swap_cycle(kind)
        outs[kind] = {r.rid: tuple(r.out) for r in reqs}
        dmas[kind] = resume_dmas
        rows.append({
            "bench": "swap-cycle", "manager": kind,
            "resume_faults": resume_faults,
            "resume_fault_dmas": resume_dmas,
            "pages_per_dma": round(resume_faults / max(resume_dmas, 1), 2),
            "transfer_us_total": round(eng.stats.transfer_us, 1),
        })
    identical = outs["mosaic"] == outs["gpu-mmu"]
    rows.append({"bench": "swap-cycle", "manager": "CHECK",
                 "outputs_identical": identical,
                 "mosaic_fewer_dmas": dmas["mosaic"] < dmas["gpu-mmu"]})
    assert identical, "swap cycle changed model outputs!"
    # The paper's contiguity-helps-transfer claim, as a measured fact.
    assert dmas["mosaic"] < dmas["gpu-mmu"], \
        f"expected fewer merged DMAs under mosaic: {dmas}"
    return rows


# ---------------------------------------------------- async fault-in overlap


def overlap_compare(factors=(1.5, 2.0), n_requests: int = 12) -> List[Dict]:
    """Sync vs async fault-in on the same oversubscribed trace.

    The async pipeline must (a) produce byte-identical decode tokens —
    prefetching never alters allocation or scheduling — and (b) hide at
    least half of the transfer µs the blocking path exposes (the claim is
    checked at 2× oversubscription, the ISSUE's acceptance point).

    The DMA timeline uses *modeled* decode windows (deterministic, not
    CPU wall time, which would include seconds of jit compilation): a
    1 ms window models a realistic accelerator decode step, and the
    "async-tight" 2 µs window deliberately starves the overlap so the
    partial-wait path (stall only for the transfer remainder) shows up
    in the measurements.
    """
    configs = (("sync", "sync", None),
               ("async", "async", 1000.0),
               ("async-tight", "async", 2.0))
    rows = []
    hidden_frac_at_2x = None
    all_identical = True
    for factor in factors:
        outs, stats = {}, {}
        for mode, fault_mode, window in configs:
            eng, reqs = run_oversubscribed(
                "mosaic", factor=factor, n_requests=n_requests,
                fault_mode=fault_mode, decode_window_us=window)
            outs[mode] = {r.rid: tuple(r.out) for r in reqs}
            stats[mode] = eng.stats
            s = eng.stats
            rows.append({
                "bench": "serving-overlap", "mode": mode, "factor": factor,
                "tok_per_s_cpu": round(s.tok_per_s(), 1),
                "faults": s.faults, "dma_count": s.fault_dmas,
                "transfer_us": round(s.transfer_us, 1),
                "exposed_us": round(s.fault_exposed_us, 1),
                "hidden_us": round(s.fault_hidden_us, 1),
                "prefetch_hits": s.prefetch_hits,
                "prefetch_misses": s.prefetch_misses,
                "prefetch_wasted": s.prefetch_wasted,
            })
        identical = all(o == outs["sync"] for o in outs.values())
        all_identical = all_identical and identical
        assert identical, f"async fault-in changed tokens at {factor}x!"
        # Fraction of the blocking path's exposed µs the pipeline hides.
        frac = 1.0 - (stats["async"].fault_exposed_us
                      / max(stats["sync"].fault_exposed_us, 1e-9))
        tight = 1.0 - (stats["async-tight"].fault_exposed_us
                       / max(stats["sync"].fault_exposed_us, 1e-9))
        if factor == 2.0:
            hidden_frac_at_2x = frac
        rows.append({"bench": "serving-overlap", "mode": "CHECK",
                     "factor": factor,
                     "hidden_fraction": round(frac, 3),
                     "hidden_fraction_tight": round(tight, 3),
                     "outputs_identical": identical})
    rows.append({"bench": "serving-overlap", "mode": "CLAIM", "factor": 2.0,
                 "claim_outputs_identical": all_identical,
                 "claim_hides_half_transfer":
                     bool(hidden_frac_at_2x is not None
                          and hidden_frac_at_2x >= 0.5)})
    return rows


def overlap_link_contention(n_access: int = 2000) -> List[Dict]:
    """The DMA-channel overlap model in the TLB simulator's multi-app
    setting: cross-app interference on the shared host↔device link
    (queueing cycles a fault pays because the link is busy, almost always
    with another app's transfer) shrinks as channels are added."""
    from repro.core.tlb_sim import SimConfig, TranslationSim
    from repro.core.workloads import build_workload, homogeneous_names

    names = homogeneous_names("dct", 3)
    traces, _ = build_workload(names, "mosaic", seed=0, n_access=n_access)
    rows = []
    contention = {}
    for ch in (1, 2, 4):
        sim = TranslationSim(
            SimConfig(mode="mosaic", paging=True, dma_channels=ch), traces)
        sim.run()
        contention[ch] = sim.link.contention_total()
        rows.append({"bench": "overlap-sim", "dma_channels": ch,
                     "faults": sim.link.faults,
                     "contention_cycles": round(contention[ch], 1),
                     "fault_cycles": round(sim.link.fault_cycles_total, 1)})
    rows.append({"bench": "overlap-sim", "dma_channels": "CHECK",
                 "claim_channels_cut_contention":
                     bool(contention[4] < contention[1]
                          and contention[1] > 0)})
    return rows


# ------------------------------------------------- prefix cache + duplex


def run_prefix_workload(prefix_cache: bool, *, n_requests: int = 8,
                        shared_tokens: int = 40, suffix_tokens: int = 8,
                        max_new: int = 6, seed: int = 0,
                        fault_mode: str = "async"):
    """Shared-system-prompt workload in two waves (DESIGN.md §8).

    Every prompt = one shared ``shared_tokens`` prefix (page-aligned) +
    a distinct suffix.  Wave 1 (two requests) runs to completion and
    parks the prefix; wave 2 (the rest) then admits against a warm
    index — with the cache on, each admission faults the prefix's pages
    in from the host tier and prefills only the suffix.
    """
    cfg = get_smoke_config("qwen2.5-3b")
    eng = ServingEngine(cfg, geometry=GEO, max_batch=4, max_seq=128,
                        manager_kind="mosaic", seed=0,
                        prefix_cache=prefix_cache, fault_mode=fault_mode,
                        decode_window_us=1000.0)
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab_size,
                          shared_tokens).astype(np.int32)
    reqs = []
    for i in range(n_requests):
        suf = rng.integers(0, cfg.vocab_size,
                           suffix_tokens).astype(np.int32)
        reqs.append(Request(rid=i, tenant=i % 3,
                            prompt=np.concatenate([shared, suf]),
                            max_new=max_new))
    for r in reqs[:2]:
        eng.submit(r)
    eng.run_until_drained(max_steps=500)
    for r in reqs[2:]:
        eng.submit(r)
    eng.run_until_drained(max_steps=1000)
    assert all(r.done for r in reqs), "prefix workload did not drain"
    eng.cache.check_invariants()
    return eng, reqs


def prefix_reuse_compare(n_requests: int = 8) -> List[Dict]:
    """Cache-hit admission vs cold admission on the same request stream.

    The claims: (a) tokens are byte-identical with the cache on or off
    (application transparency extends to reuse); (b) a cache-hit
    admission is cheaper than re-decoding the shared prefix — it
    computes only the suffix's prefill tokens, and the modeled µs to
    fault the reused pages in is below even the most conservative
    recompute bound (one decode-window of compute per hit admission;
    in reality re-prefilling the prefix costs far more); (c) the reused
    pages really move through the DMA pipeline (admission-time
    fault-in, not recompute): every reused page is a prefetch hit or
    demand fault.  Wall-clock admission latencies are reported
    (hit vs cold) but not gated on — the smoke model is op-dispatch
    bound on CPU, so wall time under-states the compute saved.
    """
    rows = []
    outs, engines = {}, {}
    for mode, on in (("cache-on", True), ("cache-off", False)):
        eng, reqs = run_prefix_workload(on, n_requests=n_requests)
        outs[mode] = {r.rid: tuple(r.out) for r in reqs}
        engines[mode] = eng
        s = eng.stats
        rows.append({
            "bench": "prefix-reuse", "mode": mode,
            "tok_per_s_cpu": round(s.tok_per_s(), 1),
            "prefill_tokens": s.prefill_tokens,
            "prefix_hits": s.prefix_hits,
            "prefix_misses": s.prefix_misses,
            "reused_tokens": s.prefix_reused_tokens,
            "parked_pages": s.prefix_parked_pages,
            "admit_hit_ms": round(s.admit_hit_mean_us() / 1e3, 1),
            "admit_cold_ms": round(s.admit_cold_mean_us() / 1e3, 1),
            "prefix_fault_us": round(s.prefix_fault_us, 1),
            "faults": s.faults, "dma_count": s.fault_dmas,
            "transfer_us": round(s.transfer_us, 1),
            "exposed_us": round(s.fault_exposed_us, 1),
            "hidden_us": round(s.fault_hidden_us, 1),
            "prefetch_hits": s.prefetch_hits,
        })
    on, off = engines["cache-on"].stats, engines["cache-off"].stats
    identical = outs["cache-on"] == outs["cache-off"]
    # Modeled cost: faulting every reused prefix in cost prefix_fault_us;
    # re-decoding it costs ≥ one decode window of compute per hit
    # admission (a deliberately loose lower bound — full-prefix prefill
    # is far more).  Deterministic, unlike CPU wall clock.
    redecode_floor_us = on.prefix_hits * 1000.0
    cheaper = (on.prefill_tokens < off.prefill_tokens
               and on.prefix_hits > 0
               and on.prefix_fault_us < redecode_floor_us)
    via_dma = on.prefix_reused_tokens > 0 and on.faults >= (
        on.prefix_reused_tokens // GEO.page_tokens)
    rows.append({"bench": "prefix-reuse", "mode": "CHECK",
                 "outputs_identical": identical,
                 "saved_prefill_tokens":
                     off.prefill_tokens - on.prefill_tokens,
                 "admit_speedup": round(
                     on.admit_cold_mean_us()
                     / max(on.admit_hit_mean_us(), 1e-9), 2)})
    rows.append({"bench": "prefix-reuse", "mode": "CLAIM",
                 "claim_prefix_tokens_identical": identical,
                 "claim_prefix_hit_cheaper_than_redecode": bool(cheaper),
                 "claim_prefix_faulted_via_dma": bool(via_dma)})
    assert identical, "prefix cache changed model outputs!"
    return rows


def duplex_compare(factor: float = 2.0, n_requests: int = 10) -> List[Dict]:
    """Outbound (eviction/parking) traffic on vs off the DMA timeline.

    ``duplex=True`` puts device→host gathers on the channels' "out"
    lanes; ``duplex=False`` is PR 2's fault-in-only timeline.  Tokens
    must not change — outbound modeling is accounting, not scheduling —
    and the per-direction ``hidden + exposed == transfer`` invariant
    must hold with eviction traffic visible.
    """
    rows = []
    outs, engines = {}, {}
    for mode, duplex in (("duplex", True), ("fault-in-only", False)):
        eng, reqs = run_oversubscribed(
            "mosaic", factor=factor, n_requests=n_requests,
            decode_window_us=1000.0, duplex=duplex)
        outs[mode] = {r.rid: tuple(r.out) for r in reqs}
        engines[mode] = eng
        s, d = eng.stats, eng.dma.stats
        rows.append({
            "bench": "serving-duplex", "mode": mode, "factor": factor,
            "tok_per_s_cpu": round(s.tok_per_s(), 1),
            "evict_pages": s.evict_pages, "evict_dmas": s.evict_dmas,
            "bytes_out": s.bytes_out,
            "evict_us": round(s.evict_us, 1),
            "out_hidden_us": round(d["hidden_us_out"], 1),
            "out_queue_us": round(d["queue_us_out"], 1),
            "exposed_us": round(s.fault_exposed_us, 1),
            "hidden_us": round(s.fault_hidden_us, 1),
            "transfer_us": round(s.transfer_us, 1),
        })
    don = engines["duplex"].dma.stats
    inv_in = abs(don["hidden_us"] + don["exposed_us"]
                 - don["transfer_us"]) < 1e-6
    inv_out = abs(don["hidden_us_out"] + don["exposed_us_out"]
                  - don["transfer_us_out"]) < 1e-6
    identical = outs["duplex"] == outs["fault-in-only"]
    visible = (engines["duplex"].stats.bytes_out > 0
               and engines["fault-in-only"].stats.bytes_out == 0)
    rows.append({"bench": "serving-duplex", "mode": "CLAIM",
                 "claim_duplex_tokens_identical": identical,
                 "claim_duplex_outbound_on_timeline": bool(visible),
                 "claim_duplex_split_invariants":
                     bool(inv_in and inv_out)})
    assert identical, "duplex outbound modeling changed model outputs!"
    return rows


def duplex_sim_compare(n_access: int = 2000,
                       hbm_pages: int = 192) -> List[Dict]:
    """Capacity writebacks in the TLB simulator: full- vs half-duplex.

    With ``hbm_pages_per_app`` capped, every fault past the cap evicts
    an LRU page — outbound link traffic.  Full-duplex keeps writebacks
    on their own lanes (inbound fault contention unchanged-ish);
    half-duplex makes faults queue behind them.
    """
    from repro.core.tlb_sim import SimConfig, TranslationSim
    from repro.core.workloads import build_workload, homogeneous_names

    names = homogeneous_names("dct", 3)
    traces, _ = build_workload(names, "mosaic", seed=0, n_access=n_access)
    rows = []
    contention = {}
    for duplex in (True, False):
        sim = TranslationSim(
            SimConfig(mode="mosaic", paging=True, dma_channels=1,
                      duplex=duplex, hbm_pages_per_app=hbm_pages),
            traces)
        sim.run()
        contention[duplex] = sim.link.contention_total()
        rows.append({
            "bench": "duplex-sim", "duplex": duplex,
            "faults": sim.link.faults,
            "writebacks": sim.link.writebacks,
            "contention_cycles_in": round(sim.link.contention_total(), 1),
            "contention_cycles_out":
                round(sim.link.contention_out_total(), 1),
        })
    writebacks = rows[0]["writebacks"]
    rows.append({"bench": "duplex-sim", "duplex": "CHECK",
                 "claim_duplex_cuts_fault_contention":
                     bool(writebacks > 0
                          and contention[True] < contention[False])})
    return rows


# ------------------------------------------------------------ cluster tier


def _shared_prefix_reqs(cfg, n, shared_tokens=40, suffix_tokens=8,
                        max_new=4, seed=0, **req_kw):
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab_size, shared_tokens).astype(np.int32)
    return [Request(rid=i, tenant=i % 3,
                    prompt=np.concatenate(
                        [shared, rng.integers(0, cfg.vocab_size,
                                              suffix_tokens)
                         .astype(np.int32)]),
                    max_new=max_new, **req_kw)
            for i in range(n)]


def run_cluster_prefix(share_prefix: bool, *, n_engines: int = 2,
                       n_requests: int = 8):
    """Two-wave shared-prefix workload over the cluster: wave 1 (one
    request, pinned to replica 0) parks the prefix; wave 2 is
    load-balanced over all replicas — only a *shared* index lets the
    replicas that never saw wave 1 hit."""
    from repro.serving.cluster import ServingCluster

    cfg = get_smoke_config("qwen2.5-3b")
    cluster = ServingCluster(cfg, geometry=GEO, n_engines=n_engines,
                             max_batch=4, max_seq=128, seed=0,
                             share_prefix=share_prefix,
                             decode_window_us=1000.0)
    reqs = _shared_prefix_reqs(cfg, n_requests)
    cluster.submit(reqs[0], engine=0)
    cluster.run_until_drained(max_steps=500)
    for r in reqs[1:]:
        cluster.submit(r)
    cluster.run_until_drained(max_steps=1000)
    assert all(r.done for r in reqs), "cluster prefix workload not drained"
    cluster.check_invariants()
    return cluster, reqs


def cluster_prefix_share_compare(n_requests: int = 8) -> List[Dict]:
    rows = []
    outs, rates = {}, {}
    for mode, share in (("shared-index", True), ("per-engine", False)):
        cluster, reqs = run_cluster_prefix(share, n_requests=n_requests)
        outs[mode] = {r.rid: tuple(r.out) for r in reqs}
        cs = cluster.stats()
        t = cs.totals
        rates[mode] = cs.prefix_hit_rate()
        rows.append({
            "bench": "cluster-prefix", "mode": mode,
            "engines": len(cluster.engines),
            "tok_per_s_cpu": round(t.tok_per_s(), 1),
            "prefix_hits": t.prefix_hits,
            "prefix_misses": t.prefix_misses,
            "hit_rate": round(rates[mode], 3),
            "reused_tokens": t.prefix_reused_tokens,
            "parked_pages": t.prefix_parked_pages,
            "prefill_tokens": t.prefill_tokens,
            "host_frames_peak": cluster.tier.frames.stats["peak_frames"],
        })
    identical = outs["shared-index"] == outs["per-engine"]
    rows.append({"bench": "cluster-prefix", "mode": "CLAIM",
                 "claim_cluster_shared_index_higher_hit_rate":
                     bool(rates["shared-index"] > rates["per-engine"]),
                 "claim_cluster_prefix_tokens_identical": identical})
    assert identical, "prefix-index sharing changed model outputs!"
    return rows


def run_cluster_slo(policy: str, *, n_engines: int = 2):
    """Unevenly loaded cluster: replica 0 starts busy with long
    best-effort work; a burst of tight-deadline requests then arrives.
    Slack-ordered dispatch sends the burst to the idle replica; FIFO
    round-robin queues half of it behind the long work."""
    from repro.serving.cluster import ServingCluster

    cfg = get_smoke_config("qwen2.5-3b")
    # Queued steal (§14) would re-dispatch FIFO's misrouted burst and
    # erase the policy contrast — off here to isolate the dispatch
    # ordering; the steal is measured on its own in the `router` suite.
    cluster = ServingCluster(cfg, geometry=GEO, n_engines=n_engines,
                             max_batch=2, max_seq=128, seed=0,
                             router_policy=policy, migrate=False,
                             router_steal_queued=False,
                             decode_window_us=1000.0)
    rng = np.random.default_rng(0)
    long_reqs = [Request(rid=i, tenant=0,
                         prompt=rng.integers(0, cfg.vocab_size, 32)
                         .astype(np.int32), max_new=24)
                 for i in range(4)]
    for r in long_reqs:
        cluster.submit(r, engine=0)
    for _ in range(2):
        cluster.step()
    burst = [Request(rid=100 + i, tenant=1,
                     prompt=rng.integers(0, cfg.vocab_size, 24)
                     .astype(np.int32), max_new=6,
                     deadline_us=18_000.0)
             for i in range(4)]
    for r in burst:
        cluster.submit(r)
    cluster.run_until_drained(max_steps=1000)
    assert all(r.done for r in long_reqs + burst)
    cluster.check_invariants()
    return cluster, long_reqs + burst


def cluster_router_compare() -> List[Dict]:
    rows = []
    outs, att = {}, {}
    for policy in ("slack", "fifo"):
        cluster, reqs = run_cluster_slo(policy)
        outs[policy] = {r.rid: tuple(r.out) for r in reqs}
        cs = cluster.stats()
        t = cs.totals
        att[policy] = cs.slo_attainment()
        rows.append({
            "bench": "cluster-router", "mode": policy,
            "engines": len(cluster.engines),
            "tok_per_s_cpu": round(t.tok_per_s(), 1),
            "deadline_hits": sum(t.deadline_hits.values()),
            "deadline_misses": sum(t.deadline_misses.values()),
            "slo_attainment": round(att[policy], 3),
            "dispatched": "/".join(
                str(cluster.router.stats.dispatched.get(i, 0))
                for i in range(len(cluster.engines))),
        })
    identical = outs["slack"] == outs["fifo"]
    rows.append({"bench": "cluster-router", "mode": "CLAIM",
                 "claim_cluster_router_raises_slo_attainment":
                     bool(att["slack"] > att["fifo"]),
                 "claim_cluster_router_tokens_identical": identical})
    assert identical, "router policy changed model outputs!"
    return rows


def run_router_burst(cost_model: str, prestage: bool, *,
                     steal_queued: bool = True,
                     deadline_us: float = 12_000.0):
    """Heterogeneous load where token counting misroutes (DESIGN.md §14).

    Replica 0 carries two *decode-heavy* requests (few prompt pages, many
    windows: cheap in token-units, expensive in modeled µs); replica 1
    carries a queue of *prompt-heavy* requests (many prompt pages, two
    tokens each: expensive in token-units, cheap in µs — prefill is wall
    work hidden inside the decode window).  A burst of tight-deadline
    shared-prefix requests then arrives unpinned: token counting sends it
    behind replica 0's long decodes, the modeled cost to replica 1.

    The shared prefix is parked up front and then deliberately spilled to
    disk by a wave of large parks, so admissions pay a disk promote —
    unless pre-staging already promoted and staged the pages at dispatch
    time.  A final idle-cluster wave (prefix re-spilled first) isolates
    that effect: per-engine ``admit_lat_us`` counts are snapshotted just
    before it so the caller can take a wave-local admit p99.
    """
    from repro.serving.cluster import ServingCluster

    cfg = get_smoke_config("qwen2.5-3b")
    cluster = ServingCluster(cfg, geometry=GEO, n_engines=2,
                             max_batch=2, max_seq=128, seed=0,
                             capacity_frames=3, spill=True, migrate=False,
                             router_cost_model=cost_model,
                             router_prestage=prestage,
                             router_steal_queued=steal_queued,
                             decode_window_us=1000.0)
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab_size, 40).astype(np.int32)

    def _req(rid, tokens, max_new, *, shared_prefix=False, tenant=0,
             deadline_us=None):
        suf = rng.integers(0, cfg.vocab_size, tokens).astype(np.int32)
        prompt = np.concatenate([shared, suf]) if shared_prefix else suf
        return Request(rid=rid, tenant=tenant, prompt=prompt,
                       max_new=max_new, deadline_us=deadline_us)

    # Park the shared prefix, then spill it with a wave of large parks.
    warm = _req(0, 8, 2, shared_prefix=True)
    spillers = [_req(1 + i, 64, 2) for i in range(3)]
    cluster.submit(warm, engine=0)
    cluster.run_until_drained(max_steps=200)
    for r in spillers:
        cluster.submit(r, engine=0)
    cluster.run_until_drained(max_steps=400)

    # Pre-load: decode-heavy on replica 0, prompt-heavy on replica 1 —
    # queued (not yet stepped) so both cost models see the full backlog.
    heavy = [_req(10, 16, 28, tenant=1), _req(11, 16, 24, tenant=1)]
    wide = [_req(12 + i, 64, 2, tenant=1) for i in range(7)]
    for r in heavy:
        cluster.submit(r, engine=0)
    for r in wide:
        cluster.submit(r, engine=1)
    # One step admits the decode-heavy pair into replica 0's batch slots
    # (equal priority: the burst cannot displace them, only queue).
    cluster.step()

    # The burst: unpinned, tight deadlines, heterogeneous suffixes.
    now = max(e._clock_us for e in cluster.engines)
    burst = [_req(100 + i, suf_tok, 3, shared_prefix=True, tenant=2,
                  deadline_us=now + deadline_us)
             for i, suf_tok in enumerate((8, 16, 8, 16))]
    for r in burst[:2]:
        cluster.submit(r)
    cluster.step()
    for r in burst[2:]:
        cluster.submit(r)
    cluster.run_until_drained(max_steps=1500)

    # Re-spill the prefix, then measure admission cost on an idle
    # cluster: with pre-staging the disk promote happens at dispatch
    # time, so the admit sample is prefill compute alone.
    respill = [_req(20 + i, 64, 2) for i in range(3)]
    for r in respill:
        cluster.submit(r, engine=0)
    cluster.run_until_drained(max_steps=400)
    starts = [len(e.stats.admit_lat_us) for e in cluster.engines]
    probe = [_req(200 + i, 8, 2, shared_prefix=True, tenant=2)
             for i in range(4)]
    for r in probe:
        cluster.submit(r)
    cluster.run_until_drained(max_steps=400)

    reqs = [warm] + spillers + heavy + wide + burst + respill + probe
    assert all(r.done for r in reqs), "router bench not drained"
    cluster.check_invariants()
    probe_lat = [x for e, s in zip(cluster.engines, starts)
                 for x in e.stats.admit_lat_us[s:]]
    return cluster, reqs, probe_lat


def router_cost_compare() -> List[Dict]:
    """Token-count vs modeled-µs routing vs modeled + pre-staging.

    Claims: (a) tokens byte-identical across all three configs (routing
    and pre-staging move *when* bytes arrive, never what decode
    computes); (b) modeled cost beats token counting on SLO attainment
    under the heterogeneous burst; (c) pre-staging cuts the probe-wave
    admit p99 versus the same modeled router without it.
    """
    rows = []
    outs, att, p99s = {}, {}, {}
    # "tokens" is the pre-§14 router verbatim: token-count load, no
    # queued steal, no pre-staging.  The modeled rows are the new router
    # with and without pre-staging.
    configs = (("tokens", "tokens", False, False),
               ("modeled", "modeled", False, True),
               ("modeled+prestage", "modeled", True, True))
    for mode, cost_model, prestage, steal in configs:
        cluster, reqs, probe_lat = run_router_burst(
            cost_model, prestage, steal_queued=steal)
        outs[mode] = {r.rid: tuple(r.out) for r in reqs}
        cs = cluster.stats()
        t = cs.totals
        att[mode] = cs.slo_attainment()
        p99s[mode] = float(np.percentile(probe_lat, 99)) \
            if probe_lat else 0.0
        rs = cluster.router.stats
        rows.append({
            "bench": "router", "mode": mode,
            "engines": len(cluster.engines),
            "tok_per_s_cpu": round(t.tok_per_s(), 1),
            "deadline_hits": sum(t.deadline_hits.values()),
            "deadline_misses": sum(t.deadline_misses.values()),
            "slo_attainment": round(att[mode], 3),
            "dispatched": "/".join(
                str(rs.dispatched.get(i, 0))
                for i in range(len(cluster.engines))),
            "queued_steals": rs.queued_steals,
            "prestaged_requests": rs.prestaged_requests,
            "prestage_hits": t.prestage_hits,
            "prestage_wasted": t.prestage_wasted,
            "prestage_cancelled": t.prestage_cancelled,
            "admit_p99_probe_us": round(p99s[mode], 1),
            "promote_stall_us": round(t.promote_stall_us, 1),
        })
    identical = (outs["tokens"] == outs["modeled"]
                 == outs["modeled+prestage"])
    rows.append({
        "bench": "router", "mode": "CLAIM",
        "claim_router_tokens_identical": identical,
        "claim_router_modeled_cost_raises_slo_attainment":
            bool(att["modeled"] > att["tokens"]),
        "claim_router_prestage_cuts_admit_p99":
            bool(p99s["modeled+prestage"] < p99s["modeled"]),
    })
    assert identical, "router cost model / pre-staging changed tokens!"
    return rows


def run_cluster_migration(n_engines: int, migrate: bool):
    """Controlled steal scenario: a long best-effort request on replica 0
    is displaced by a premium burst; with migration on, the idle replica
    adopts it via host-frame handoff instead of leaving it parked."""
    from repro.serving.cluster import ServingCluster

    cfg = get_smoke_config("qwen2.5-3b")
    cluster = ServingCluster(cfg, geometry=GEO, n_engines=n_engines,
                             max_batch=2, max_seq=96, seed=0,
                             migrate=migrate, prefix_cache=False,
                             decode_window_us=1000.0)
    rng = np.random.default_rng(2)
    victim = Request(rid=0, tenant=0, priority=0,
                     prompt=rng.integers(0, cfg.vocab_size, 40)
                     .astype(np.int32), max_new=20)
    premium = [Request(rid=i, tenant=1, priority=2,
                       prompt=rng.integers(0, cfg.vocab_size, 48)
                       .astype(np.int32), max_new=12)
               for i in range(1, 3)]
    cluster.submit(victim, engine=0)
    for _ in range(2):
        cluster.step()
    for r in premium:
        cluster.submit(r, engine=0)
    cluster.run_until_drained(max_steps=800)
    assert all(r.done for r in [victim] + premium)
    cluster.check_invariants()
    return cluster, [victim] + premium


def cluster_migration_compare() -> List[Dict]:
    rows = []
    outs = {}
    clusters = {}
    for mode, n_eng, migrate in (("1-engine", 1, False),
                                 ("2-engines", 2, False),
                                 ("2-engines-steal", 2, True)):
        cluster, reqs = run_cluster_migration(n_eng, migrate)
        outs[mode] = {r.rid: tuple(r.out) for r in reqs}
        clusters[mode] = cluster
        t = cluster.stats().totals
        r = cluster.router.stats
        rows.append({
            "bench": "cluster-migration", "mode": mode,
            "engines": n_eng,
            "tok_per_s_cpu": round(t.tok_per_s(), 1),
            "prefill_tokens": t.prefill_tokens,
            "decode_tokens": t.decode_tokens,
            "migrations": r.migrations,
            "migrated_pages": r.migrated_pages,
            "whole_frame_moves":
                cluster.tier.frames.stats["whole_frame_moves"],
            "swaps_out": t.swaps_out, "swaps_in": t.swaps_in,
            "transfer_us": round(t.transfer_us, 1),
        })
    steal = clusters["2-engines-steal"]
    dst = steal.engines[1]
    r = steal.router.stats
    identical = (outs["1-engine"] == outs["2-engines"]
                 == outs["2-engines-steal"])
    # Zero re-prefill: the thief decoded the migrated request without
    # ever prefilling (its pages arrived as host-resident base pages),
    # and cluster-wide prefill compute is unchanged by migration.
    zero_reprefill = (r.migrations >= 1 and r.migrated_pages > 0
                      and dst.stats.prefill_tokens == 0
                      and dst.stats.decode_tokens > 0
                      and dst.stats.faults >= r.migrated_pages
                      and clusters["2-engines-steal"].stats().totals
                          .prefill_tokens
                      == clusters["2-engines"].stats().totals
                          .prefill_tokens)
    # Handoff cost: restoring the migrated pages on the thief is modeled
    # DMA µs; re-prefilling the prompt would cost ≥ one decode window
    # per migration (deliberately loose floor, cf. prefix_reuse_compare).
    cheaper = dst.stats.transfer_us < r.migrations * 1000.0
    rows.append({"bench": "cluster-migration", "mode": "CLAIM",
                 "claim_cluster_migration_zero_reprefill":
                     bool(zero_reprefill),
                 "claim_cluster_tokens_identical_1_vs_n": bool(identical),
                 "claim_cluster_migration_cheaper_than_reprefill":
                     bool(cheaper)})
    assert identical, "cluster scale-out changed model outputs!"
    return rows


def cluster_sim_compare(n_access: int = 2000) -> List[Dict]:
    """The TLB simulator's cluster model: 4 apps across engine counts.

    One engine = one shared link (the pre-cluster model).  Two engines
    with private links remove cross-engine link contention; adding a
    shared host store (1 DRAM lane) re-serializes the transfers there;
    widening the host lanes relieves it."""
    from repro.core.tlb_sim import SimConfig, TranslationSim
    from repro.core.workloads import build_workload, homogeneous_names

    names = homogeneous_names("dct", 4)
    traces, _ = build_workload(names, "mosaic", seed=0, n_access=n_access)
    rows = []
    res = {}
    for label, n_eng, host_lanes in (("1-engine", 1, 0),
                                     ("2-engines", 2, 0),
                                     ("2-engines-shared-host", 2, 1),
                                     ("2-engines-wide-host", 2, 2)):
        sim = TranslationSim(
            SimConfig(mode="mosaic", paging=True, dma_channels=1,
                      n_engines=n_eng, host_lanes=host_lanes), traces)
        sim.run()
        res[label] = (sim.link.contention_total(),
                      sim.link.host_contention_total())
        rows.append({"bench": "cluster-sim", "mode": label,
                     "n_engines": n_eng, "host_lanes": host_lanes,
                     "faults": sim.link.faults,
                     "link_contention": round(res[label][0], 1),
                     "host_contention": round(res[label][1], 1)})
    rows.append({"bench": "cluster-sim", "mode": "CLAIM",
                 "claim_cluster_links_cut_link_contention":
                     bool(res["2-engines"][0] < res["1-engine"][0]
                          and res["1-engine"][0] > 0),
                 "claim_cluster_host_lanes_relieve_shared_store":
                     bool(res["2-engines-shared-host"][1]
                          > res["2-engines-wide-host"][1])})
    return rows


# ------------------------------------------------------------ spill tier


def _grouped_prefix_reqs(cfg, *, n_groups=4, per_group=3, shared_tokens=40,
                         suffix_tokens=8, max_new=4, seed=0):
    """``n_groups`` distinct shared prefixes, ``per_group`` requests
    each.  Returned grouped so callers can wave-split: one request per
    group parks its prefix, the rest readmit against a warm index."""
    rng = np.random.default_rng(seed)
    groups, rid = [], 0
    for _ in range(n_groups):
        shared = rng.integers(0, cfg.vocab_size,
                              shared_tokens).astype(np.int32)
        group = []
        for _ in range(per_group):
            suf = rng.integers(0, cfg.vocab_size,
                               suffix_tokens).astype(np.int32)
            group.append(Request(rid=rid, tenant=rid % 3,
                                 prompt=np.concatenate([shared, suf]),
                                 max_new=max_new))
            rid += 1
        groups.append(group)
    return groups


def run_spill_cluster(spill: bool, *, capacity_frames: int = 3,
                      n_engines: int = 2, n_groups: int = 4,
                      per_group: int = 3, injector=None):
    """Two-wave grouped-prefix workload under a hard host-frame cap.

    Wave 1 (one request per group) parks every group's prefix; with
    4 groups x 5 pages in 4-page frames the parked set overflows
    ``capacity_frames``, so the LRU groups either spill to disk
    (``spill=True``) or are hard-evicted through the prefix index
    (``spill=False``).  Wave 2 readmits every group; per-engine
    ``admit_lat_us`` sample counts are snapshotted between the waves so
    the caller can take a wave-2-only p99.
    """
    from repro.serving.cluster import ServingCluster

    cfg = get_smoke_config("qwen2.5-3b")
    cluster = ServingCluster(cfg, geometry=GEO, n_engines=n_engines,
                             max_batch=4, max_seq=128, seed=0,
                             capacity_frames=capacity_frames, spill=spill,
                             decode_window_us=1000.0,
                             fault_injector=injector)
    groups = _grouped_prefix_reqs(cfg, n_groups=n_groups,
                                  per_group=per_group)
    wave1 = [g[0] for g in groups]
    wave2 = [r for g in groups for r in g[1:]]
    for r in wave1:
        cluster.submit(r)
    cluster.run_until_drained(max_steps=1000)
    starts = [len(e.stats.admit_lat_us) for e in cluster.engines]
    for r in wave2:
        cluster.submit(r)
    cluster.run_until_drained(max_steps=2000)
    assert all(r.done for r in wave1 + wave2), "spill workload not drained"
    cluster.check_invariants()
    wave2_lat = [x for e, s in zip(cluster.engines, starts)
                 for x in e.stats.admit_lat_us[s:]]
    return cluster, wave1 + wave2, wave2_lat


def spill_compare(n_engines: int = 2) -> List[Dict]:
    """Spill-to-disk vs hard-capped eviction under the same frame cap.

    Claims: (a) tokens byte-identical spill on/off (the disk tier is
    transparent memory management); (b) spill keeps the wave-2 prefix
    hit rate strictly higher — spilled frames promote back instead of
    being dropped; (c) wave-2 p99 admission latency (modeled: prefill
    compute at ``prefill_us_per_token`` + promote stalls) is strictly
    lower with spill — a ~200-600 us disk promote beats re-prefilling a
    48-token prompt.
    """
    rows = []
    outs, rates, p99s, clusters = {}, {}, {}, {}
    for mode, spill in (("spill", True), ("hard-cap", False)):
        cluster, reqs, wave2_lat = run_spill_cluster(
            spill, n_engines=n_engines)
        outs[mode] = {r.rid: tuple(r.out) for r in reqs}
        clusters[mode] = cluster
        cs = cluster.stats()
        t = cs.totals
        rates[mode] = cs.prefix_hit_rate()
        p99s[mode] = float(np.percentile(wave2_lat, 99)) \
            if wave2_lat else 0.0
        tier = cluster.tier
        rows.append({
            "bench": "spill", "mode": mode, "engines": n_engines,
            "tok_per_s_cpu": round(t.tok_per_s(), 1),
            "prefix_hits": t.prefix_hits,
            "prefix_misses": t.prefix_misses,
            "hit_rate": round(rates[mode], 3),
            "prefill_tokens": t.prefill_tokens,
            "spilled_frames": tier.stats["spilled_frames"],
            "promoted_frames": tier.stats["promoted_frames"],
            "hard_evicted_pages": tier.stats["hard_evicted_pages"],
            "promote_stall_us": round(t.promote_stall_us, 1),
            "spill_dma_jobs": (tier.wb_dma.stats["spill_jobs"]
                               if tier.spill_enabled else 0),
            "admit_p99_wave2_us": round(p99s[mode], 1),
            "host_frames_peak": tier.frames.stats["peak_frames"],
        })
    st_on = clusters["spill"].tier.stats
    identical = outs["spill"] == outs["hard-cap"]
    # The comparison is meaningful only if the cap actually bit on both
    # sides: frames went to disk with spill on, pages were dropped with
    # spill off.
    cap_bit = (st_on["spilled_frames"] > 0
               and st_on["promoted_frames"] > 0
               and clusters["hard-cap"].tier.stats["hard_evicted_pages"]
               > 0)
    rows.append({"bench": "spill", "mode": "CLAIM",
                 "claim_spill_tokens_identical": identical,
                 "claim_spill_higher_hit_rate":
                     bool(cap_bit and rates["spill"] > rates["hard-cap"]),
                 "claim_spill_lower_admit_p99":
                     bool(cap_bit and p99s["spill"] < p99s["hard-cap"])})
    assert identical, "disk spill tier changed model outputs!"
    return rows


def spill_backpressure_compare() -> List[Dict]:
    """Write-back saturation → refuse-park back-pressure.

    A 1-deep write-back queue over a deliberately slow disk (2 ms/page:
    one frame takes ~8 decode windows to persist) saturates while the
    first spill is still in flight, so later over-cap prefix parks are
    refused outright — the tier sheds cache-insert load instead of
    queueing unboundedly — and the queue depth never exceeds its bound.
    Refused parks only cost future hits; tokens are unaffected.
    """
    from repro.serving.cluster import ServingCluster

    cfg = get_smoke_config("qwen2.5-3b")
    cluster = ServingCluster(cfg, geometry=GEO, n_engines=2,
                             max_batch=4, max_seq=128, seed=0,
                             capacity_frames=2, wb_queue_frames=1,
                             disk_write_us_per_page=2000.0,
                             decode_window_us=1000.0)
    groups = _grouped_prefix_reqs(cfg, n_groups=5, per_group=1, seed=3)
    reqs = [g[0] for g in groups]
    for r in reqs:
        cluster.submit(r)
    cluster.run_until_drained(max_steps=2000)
    assert all(r.done for r in reqs), "backpressure workload not drained"
    cluster.tier.flush()        # persist the still-in-flight write-back
    cluster.check_invariants()
    t = cluster.stats().totals
    tier = cluster.tier
    rows = [{
        "bench": "spill-backpressure", "mode": "wb-queue-1",
        "parked_pages": t.prefix_parked_pages,
        "parks_refused": t.prefix_park_refused,
        "spilled_frames": tier.stats["spilled_frames"],
        "wb_peak_depth": tier.stats["wb_peak_depth"],
        "wb_queue_frames": tier.wb_queue_frames,
    }]
    rows.append({"bench": "spill-backpressure", "mode": "CLAIM",
                 "claim_spill_backpressure_refuses_parks":
                     bool(t.prefix_park_refused >= 1
                          and tier.stats["spilled_frames"] >= 1
                          and tier.stats["wb_peak_depth"]
                          <= tier.wb_queue_frames)})
    return rows


def spill_sim_compare(n_access: int = 2000,
                      hbm_pages: int = 192) -> List[Dict]:
    """Capacity writebacks hitting the disk in the TLB simulator.

    Same capped setting as ``duplex_sim_compare``, with the disk
    modeled: each writeback streams host→disk after its link transfer
    at ``disk_cycles_per_page`` (~an order of magnitude over the link's
    per-page cost), so evictions queue at a single disk lane; a second
    lane relieves the backlog.
    """
    from repro.core.tlb_sim import SimConfig, TranslationSim
    from repro.core.workloads import build_workload, homogeneous_names

    names = homogeneous_names("dct", 3)
    traces, _ = build_workload(names, "mosaic", seed=0, n_access=n_access)
    rows = []
    res = {}
    for disk_lanes in (1, 2):
        sim = TranslationSim(
            SimConfig(mode="mosaic", paging=True, dma_channels=1,
                      duplex=True, hbm_pages_per_app=hbm_pages,
                      disk_lanes=disk_lanes), traces)
        sim.run()
        res[disk_lanes] = sim.link.disk_contention_total()
        rows.append({
            "bench": "spill-sim", "disk_lanes": disk_lanes,
            "writebacks": sim.link.writebacks,
            "disk_writebacks": sim.link.disk_writebacks,
            "disk_busy_cycles": round(sim.link.disk_busy_cycles, 1),
            "disk_contention_cycles": round(res[disk_lanes], 1),
        })
    rows.append({"bench": "spill-sim", "disk_lanes": "CLAIM",
                 "claim_spill_disk_lanes_relieve_writeback":
                     bool(res[1] > 0 and res[2] < res[1])})
    return rows


# -------------------------------------------------------- fault tolerance


def _kill_unrecovered(cluster, idx: int) -> None:
    """Model an engine crash with NO failover (the baseline the recovery
    claim is measured against): the engine dies and takes its queued and
    in-flight work with it — those requests never complete.  The dead
    domain's host frames are still reclaimed so tier invariants hold."""
    victim = cluster.engines[idx]
    victim.alive = False
    victim.active.clear()
    victim.queue.clear()
    victim.preempted.clear()
    victim._held.clear()
    victim.states.clear()
    victim._saved_tokens.clear()
    if cluster.tier is not None:
        cluster.tier.reclaim_domain(victim.engine_id)


def run_crash_cluster(mode: str):
    """Deadline workload with replica 0 carrying most of the work.

    Replica 0 decodes two long requests, a premium request preempts one
    of them (leaving a host-side bundle), and a small burst lands on the
    idle replica 1.  ``mode``:

    * ``"fault-free"``  — no failure; reference tokens and SLO.
    * ``"recovery"``    — the injector kills engine 0 at router step 6
      (mid-decode, bundle parked): the router re-homes the preempted
      bundle to replica 1 with zero re-prefill and re-dispatches the
      in-flight/queued victims from the prompt.
    * ``"no-recovery"`` — the same crash point with failover disabled:
      the victim's requests die with the engine.
    """
    from repro.serving.cluster import ServingCluster
    from repro.serving.faults import FaultInjector, FaultPlan

    crash_step = 6
    inj = FaultInjector(FaultPlan(engine_crashes=((crash_step, 0),))) \
        if mode == "recovery" else None
    cfg = get_smoke_config("qwen2.5-3b")
    cluster = ServingCluster(cfg, geometry=GEO, n_engines=2, max_batch=2,
                             max_seq=128, seed=0, prefix_cache=False,
                             migrate=False, decode_window_us=1000.0,
                             fault_injector=inj)
    rng = np.random.default_rng(5)
    long_reqs = [Request(rid=i, tenant=0, priority=0,
                         prompt=rng.integers(0, cfg.vocab_size, 32)
                         .astype(np.int32), max_new=20,
                         deadline_us=120_000.0)
                 for i in range(2)]
    for r in long_reqs:
        cluster.submit(r, engine=0)
    for _ in range(2):
        cluster.step()
    premium = Request(rid=2, tenant=1, priority=2,
                      prompt=rng.integers(0, cfg.vocab_size, 24)
                      .astype(np.int32), max_new=6, deadline_us=40_000.0)
    cluster.submit(premium, engine=0)
    for _ in range(2):
        cluster.step()
    burst = [Request(rid=3 + i, tenant=2, priority=0,
                     prompt=rng.integers(0, cfg.vocab_size, 24)
                     .astype(np.int32), max_new=6, deadline_us=60_000.0)
             for i in range(2)]
    for r in burst:
        cluster.submit(r)
    reqs = long_reqs + [premium] + burst
    if mode == "no-recovery":
        for _ in range(2):          # reach the same crash point
            cluster.step()
        _kill_unrecovered(cluster, 0)
    cluster.run_until_drained(max_steps=1500)
    if mode != "no-recovery":
        assert all(r.done for r in reqs), f"{mode}: workload not drained"
    cluster.check_invariants()
    return cluster, reqs


def faults_crash_compare() -> List[Dict]:
    """Engine-crash recovery vs a no-failover baseline (DESIGN.md §12).

    Claims: (a) after the crash the recovered run's tokens are
    byte-identical to the fault-free run's for *every* request — the
    preempted bundle resumes on the survivor with zero re-prefill, the
    in-flight/queued victims replay deterministically from the prompt;
    (b) recovery's deadline-met fraction (over all submitted
    deadline-carrying requests; never-completed counts as a miss) is
    strictly above the no-failover baseline's.
    """
    rows = []
    outs, met, clusters = {}, {}, {}
    for mode in ("fault-free", "recovery", "no-recovery"):
        cluster, reqs = run_crash_cluster(mode)
        outs[mode] = {r.rid: tuple(r.out) for r in reqs if r.done}
        clusters[mode] = cluster
        t = cluster.stats().totals
        rs = cluster.router.stats
        n_dl = sum(1 for q in reqs if q.deadline_us is not None)
        met[mode] = sum(t.deadline_hits.values()) / max(n_dl, 1)
        rows.append({
            "bench": "faults-crash", "mode": mode,
            "engines": len(cluster.engines),
            "tok_per_s_cpu": round(t.tok_per_s(), 1),
            "completed": sum(1 for q in reqs if q.done),
            "requests": len(reqs),
            "deadline_met_frac": round(met[mode], 3),
            "crashes": rs.crashes,
            "recovered_bundles": rs.recovered_bundles,
            "recovered_requeued": rs.recovered_requeued,
            "reclaimed_frames": cluster.tier.stats["reclaimed_frames"],
        })
    rec = clusters["recovery"].router.stats
    # The scenario must actually bite: one crash, at least one zero-
    # re-prefill bundle re-homed, at least one victim re-dispatched.
    crash_bit = (rec.crashes == 1 and rec.recovered_bundles >= 1
                 and rec.recovered_requeued >= 1)
    identical = outs["recovery"] == outs["fault-free"]
    rows.append({"bench": "faults-crash", "mode": "CLAIM",
                 "claim_faults_crash_tokens_identical":
                     bool(crash_bit and identical),
                 "claim_faults_recovery_higher_slo":
                     bool(crash_bit
                          and met["recovery"] > met["no-recovery"])})
    assert identical, "crash recovery changed model outputs!"
    return rows


def faults_spill_compare() -> List[Dict]:
    """Spill-store integrity under injected disk faults (DESIGN.md §12).

    The ``spill_compare`` workload (grouped prefixes overflowing the
    frame cap, spill on) re-run under four fault plans against a clean
    reference:

    * ``corrupt``   — every spilled frame gets a seeded bit flip on
      disk.  Claims: the blake2b checksum catches **100 %** of corrupt
      reads (no corrupted frame is ever decoded from: zero successful
      reads), every caught frame is quarantined, and tokens still match
      the clean run — quarantined prefixes are re-derived by a full
      prefill, never served from bad bytes.
    * ``degrade``   — every disk write fails (transient, unbounded).
      Bounded retries with exponential backoff are charged to the
      modeled clock; once the error rate crosses the threshold the tier
      degrades to the hard-cap (spill-off) path.  Claims: the tier
      degraded, retries/backoff were exercised, and **zero requests
      dropped** — tokens identical to the clean run.
    * ``dma-stall`` (x2, same seed) — every 3rd DMA job stalls 500 µs.
      Claims: stalls fired, tokens are unchanged (timing-only fault),
      and two identically-seeded runs produce identical injector stats
      (the fault schedule is reproducible).
    """
    from repro.serving.faults import FaultInjector, FaultPlan

    plans = {
        "clean": None,
        "corrupt": FaultPlan(corrupt_write_rate=1.0),
        "degrade": FaultPlan(disk_write_error_rate=1.0,
                             max_transient_failures=10 ** 6),
        "dma-stall": FaultPlan(dma_stall_every=3, dma_stall_us=500.0),
        "dma-stall-b": FaultPlan(dma_stall_every=3, dma_stall_us=500.0),
    }
    rows, outs, clusters, injs = [], {}, {}, {}
    for mode, plan in plans.items():
        inj = FaultInjector(plan) if plan is not None else None
        cluster, reqs, _ = run_spill_cluster(True, injector=inj)
        outs[mode] = {r.rid: tuple(r.out) for r in reqs}
        clusters[mode], injs[mode] = cluster, inj
        tier = cluster.tier
        ss = tier.spill_store.stats if tier.spill_store is not None else {}
        rows.append({
            "bench": "faults-spill", "mode": mode,
            "spilled_frames": tier.stats["spilled_frames"],
            "promoted_frames": tier.stats["promoted_frames"],
            "frames_quarantined": tier.stats["frames_quarantined"],
            "checksum_failures": ss.get("checksum_failures", 0),
            "frames_read_ok": ss.get("frames_read", 0),
            "disk_errors": tier.stats["disk_errors"],
            "disk_retries": tier.stats["disk_retries"],
            "retry_backoff_us": round(tier.stats["retry_backoff_us"], 1),
            "degraded": tier.stats["degraded"],
            "lost_restarts": cluster.stats().totals.lost_restarts,
            "dma_stalls": inj.stats["dma_stalls"] if inj else 0,
            "injected_stall_us":
                round(inj.stats["dma_stall_us"], 1) if inj else 0.0,
        })
    # Clean reference must exercise the disk at all for the injected
    # plans to mean anything.
    clean_bit = (clusters["clean"].tier.stats["spilled_frames"] > 0
                 and clusters["clean"].tier.stats["promoted_frames"] > 0)
    ssc = clusters["corrupt"].tier.spill_store.stats
    tc = clusters["corrupt"].tier.stats
    detected = (clean_bit and ssc["checksum_failures"] >= 1
                and ssc["frames_read"] == 0          # 100%: none decoded
                and tc["frames_quarantined"] >= 1
                and injs["corrupt"].stats["corrupted_frames"] >= 1)
    corrupt_identical = outs["corrupt"] == outs["clean"]
    td = clusters["degrade"].tier
    degrade_ok = (clean_bit and bool(td.degraded)
                  and td.stats["disk_retries"] >= 1
                  and td.stats["retry_backoff_us"] > 0.0
                  and outs["degrade"] == outs["clean"])
    ia, ib = injs["dma-stall"], injs["dma-stall-b"]
    dma_ok = (ia.stats["dma_stalls"] >= 1
              and outs["dma-stall"] == outs["clean"]
              and outs["dma-stall"] == outs["dma-stall-b"]
              and ia.stats == ib.stats)
    rows.append({"bench": "faults-spill", "mode": "CLAIM",
                 "claim_faults_corruption_detected": bool(detected),
                 "claim_faults_corruption_tokens_identical":
                     bool(clean_bit and corrupt_identical),
                 "claim_faults_degrade_zero_drops": bool(degrade_ok),
                 "claim_faults_dma_stall_timing_only": bool(dma_ok)})
    assert corrupt_identical, "spill corruption leaked into outputs!"
    assert outs["degrade"] == outs["clean"], \
        "degraded tier changed model outputs!"
    return rows


# ------------------------------------------- fused gather-attend decode


def fused_decode_compare(factor: float = 2.0,
                         n_requests: int = 8) -> List[Dict]:
    """Sync vs async vs fused fault-in on the same oversubscribed trace.

    The fused path (``fault_mode="fused"``) removes the step-granularity
    DMA barrier: instead of waiting for every missing page before the
    decode launches, it hands the attention kernel a per-page readiness
    mask plus staging-buffer slots and lets the kernel consume arriving
    pages in place.  Only the transfer tail past the decode window is
    exposed, so at the starved 2 µs window its exposed µs must sit
    strictly below the async pipeline's (which stalls per page before
    launch).  Tokens stay byte-identical across all three modes — the
    staged bytes are exactly what the scatter would have written.

    The hidden-fraction claim is calibrated at the default 8-request
    trace: bigger traces shift exposure into single resume transfers
    many times the window (a 20 µs DMA exposes ≥18 µs under *any*
    2 µs-window scheme), so async and fused converge toward the same
    floor and the fraction measures trace shape, not the mechanism.
    The strictly-below-async claim holds at every size.
    """
    configs = (("sync", "sync", None),
               ("async", "async", 1000.0),
               ("async-tight", "async", 2.0),
               ("fused", "fused", 1000.0),
               ("fused-tight", "fused", 2.0))
    rows = []
    outs, stats = {}, {}
    for mode, fault_mode, window in configs:
        eng, reqs = run_oversubscribed(
            "mosaic", factor=factor, n_requests=n_requests,
            fault_mode=fault_mode, decode_window_us=window)
        outs[mode] = {r.rid: tuple(r.out) for r in reqs}
        stats[mode] = eng.stats
        s = eng.stats
        rows.append({
            "bench": "fused-decode", "mode": mode, "factor": factor,
            "tok_per_s_cpu": round(s.tok_per_s(), 1),
            "faults": s.faults, "dma_count": s.fault_dmas,
            "transfer_us": round(s.transfer_us, 1),
            "exposed_us": round(s.fault_exposed_us, 1),
            "hidden_us": round(s.fault_hidden_us, 1),
            "fused_ready_pages": s.fused_ready_pages,
            "fused_drained_pages": s.fused_drained_pages,
            "fused_tail_us": round(s.fused_tail_us, 1),
        })
    identical = all(o == outs["sync"] for o in outs.values())
    assert identical, "fused fault-in changed tokens!"
    sync_exp = max(stats["sync"].fault_exposed_us, 1e-9)
    frac_tight = 1.0 - stats["fused-tight"].fault_exposed_us / sync_exp
    below_async = (stats["fused-tight"].fault_exposed_us
                   < stats["async-tight"].fault_exposed_us)
    drained = (stats["fused"].fused_drained_pages
               + stats["fused-tight"].fused_drained_pages)
    rows.append({"bench": "fused-decode", "mode": "CHECK", "factor": factor,
                 "hidden_fraction_fused_tight": round(frac_tight, 3),
                 "fused_tight_exposed_us":
                     round(stats["fused-tight"].fault_exposed_us, 1),
                 "async_tight_exposed_us":
                     round(stats["async-tight"].fault_exposed_us, 1),
                 "outputs_identical": identical})
    rows.append({"bench": "fused-decode", "mode": "CLAIM", "factor": factor,
                 "claim_fused_tokens_identical": bool(identical),
                 "claim_fused_tight_exposed_below_async": bool(below_async),
                 "claim_fused_hides_over_089": bool(frac_tight > 0.89),
                 "claim_fused_drains_in_kernel": bool(drained > 0),
                 "hidden_fraction_fused_tight": round(frac_tight, 3)})
    assert below_async, (
        f"fused tight exposed {stats['fused-tight'].fault_exposed_us:.1f}us "
        f"not below async {stats['async-tight'].fault_exposed_us:.1f}us")
    return rows


def fused_kernel_compare(B: int = 4, nblk: int = 8, reps: int = 3) -> List[Dict]:
    """Readiness-masked kernel vs gather-then-attend on one synthetic batch.

    All-resident (every slot -1) the fused kernel must be *bitwise*
    identical to the baseline page-granularity kernel — the masked loads
    all select the pool and the late accumulator never initializes, so
    the flush emits the ready scratch untouched.  With half the pages
    staged it must match scatter-then-attend to float32 round-off (the
    two-accumulator combine is a fixed-order reassociation, and pallas
    interpret mode jits the kernel while the scatter path runs the same
    ops under a separate trace).  Tokens/s rows are CPU wall-clock on
    the interpret-mode kernel — relative only.
    """
    import time

    import jax.numpy as jnp

    from repro.kernels.paged_attention import (fused_paged_attention_kernel,
                                               paged_attention_kernel)

    n_kv, g, dh, ptok = 2, 2, 16, 8
    NP = B * nblk + 4
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, n_kv * g, dh), np.float32))
    pool_k = jnp.asarray(rng.standard_normal((NP, ptok, n_kv, dh), np.float32))
    pool_v = jnp.asarray(rng.standard_normal((NP, ptok, n_kv, dh), np.float32))
    tables = jnp.asarray(
        rng.permutation(NP)[:B * nblk].reshape(B, nblk).astype(np.int32))
    ntok = jnp.full((B, nblk), ptok, jnp.int32)
    scale = 1.0 / float(np.sqrt(dh))

    base = paged_attention_kernel(q, pool_k, pool_v, tables, ntok,
                                  granularity="page", scale=scale,
                                  interpret=True)

    # All resident: every slot -1, stage pools untouched.
    no_slots = jnp.full((B, nblk), -1, jnp.int32)
    stage_k = pool_k[:4]
    stage_v = pool_v[:4]
    allready = fused_paged_attention_kernel(
        q, pool_k, pool_v, stage_k, stage_v, tables, no_slots, ntok,
        scale=scale, interpret=True)
    bitwise = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(allready, base))

    # Half the pages staged: their pool bytes are garbage, the staging
    # buffer holds the truth.  Gather-then-attend scatters first.
    late = np.zeros((B, nblk), bool)
    late[:, 1::2] = True
    NS = int(late.sum())
    slots_np = np.full((B, nblk), -1, np.int32)
    slots_np[late] = np.arange(NS, dtype=np.int32)
    tbl_np = np.asarray(tables)
    sk = np.asarray(pool_k)[tbl_np[late]]
    sv = np.asarray(pool_v)[tbl_np[late]]
    dirty_k = np.asarray(pool_k).copy()
    dirty_v = np.asarray(pool_v).copy()
    dirty_k[tbl_np[late]] = rng.standard_normal(sk.shape).astype(np.float32)
    dirty_v[tbl_np[late]] = rng.standard_normal(sv.shape).astype(np.float32)
    slots = jnp.asarray(slots_np)
    fused = fused_paged_attention_kernel(
        q, jnp.asarray(dirty_k), jnp.asarray(dirty_v),
        jnp.asarray(sk), jnp.asarray(sv), tables, slots, ntok, scale=scale,
        interpret=True)
    partial_ok = all(
        np.allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)
        for a, b in zip(fused, base))

    def _time(fn):
        fn()                        # warm the jit/trace caches
        t0 = time.perf_counter()
        for _ in range(reps):
            r = fn()
            [np.asarray(x) for x in r]
        return (time.perf_counter() - t0) / reps

    def _gather_then_attend():
        gk = np.asarray(dirty_k).copy()
        gv = np.asarray(dirty_v).copy()
        gk[tbl_np[late]] = sk
        gv[tbl_np[late]] = sv
        return paged_attention_kernel(q, jnp.asarray(gk), jnp.asarray(gv),
                                      tables, ntok,
                                      granularity="page", scale=scale,
                                      interpret=True)

    t_fused = _time(lambda: fused_paged_attention_kernel(
        q, jnp.asarray(dirty_k), jnp.asarray(dirty_v),
        jnp.asarray(sk), jnp.asarray(sv), tables, slots, ntok, scale=scale,
        interpret=True))
    t_gather = _time(_gather_then_attend)
    toks = B * nblk * ptok
    rows = [
        {"bench": "fused-kernel", "mode": "fused", "batch": B,
         "blocks": nblk, "staged_pages": NS,
         "tok_per_s_cpu": round(toks / max(t_fused, 1e-9), 1)},
        {"bench": "fused-kernel", "mode": "gather-then-attend", "batch": B,
         "blocks": nblk, "staged_pages": NS,
         "tok_per_s_cpu": round(toks / max(t_gather, 1e-9), 1)},
        {"bench": "fused-kernel", "mode": "CLAIM",
         "claim_fused_allready_bitwise": bool(bitwise),
         "claim_fused_partial_matches_gather": bool(partial_ok)},
    ]
    assert bitwise, "all-resident fused kernel is not bitwise identical"
    assert partial_ok, "partially-staged fused kernel diverged from gather"
    return rows


# ------------------------------------------- translation (radix walker)


def translation_radix_compare(n_access: int = 2000) -> List[Dict]:
    """Contiguity ⇒ cheap translation, measured (DESIGN.md §15).

    The same trace geometry is allocated by the mosaic manager
    (contiguity-preserving CoCoA) and the gpu-mmu baseline (interleaved
    per-buffer allocation), then run under the radix walker with
    subregion-coalesced TLB entries whose coverage is derived from each
    allocator's *actual* frame map.  Claims:

    * mosaic pays fewer walks and fewer total walk cycles than the
      scattered baseline (one coalesced entry covers a contiguous run);
    * coalescing itself is the mechanism: span-1 entries (per-page)
      erase mosaic's advantage walk-for-walk;
    * per-level walk caches cut DRAM accesses per walk;
    * ``translation="flat"`` and radix with PWCs off + span 1 agree
      bitwise (per-app cycles), so every pre-§15 claim is preserved.
    """
    from repro.core.tlb_sim import SimConfig, TranslationSim
    from repro.core.workloads import build_workload, homogeneous_names

    names = homogeneous_names("bfs", 2)
    rows = []
    st = {}
    for label, kind, cfg_kw in (
            ("mosaic-radix", "mosaic", {}),
            ("gpu-mmu-radix", "gpu-mmu", {}),
            ("mosaic-span1", "mosaic", {"coalesce_span": 1}),
            ("gpu-mmu-nopwc", "gpu-mmu", {"pwc_entries": 0})):
        traces, _ = build_workload(names, kind, seed=0, n_access=n_access)
        sim = TranslationSim(
            SimConfig(translation="radix", paging=False, **cfg_kw), traces)
        res = sim.run()
        st[label] = {
            "walks": sim.total_walks(),
            "walk_cycles": sim.total_walk_cycles(),
            "dram": sim.walk_dram_accesses(),
            "queue": sim.walker_queue_cycles(),
            "pwc": sim.pwc_hit_rate(),
            "ipc": float(sum(r.ipc for r in res)),
        }
        rows.append({
            "bench": "translation", "mode": label,
            "walks": st[label]["walks"],
            "walk_cycles": round(st[label]["walk_cycles"], 1),
            "dram_accesses": st[label]["dram"],
            "walker_queue_cycles": round(st[label]["queue"], 1),
            "pwc_hit": round(st[label]["pwc"], 3),
            "l1_hit": round(sim.l1_hit_rate(), 3),
            "ipc_sum": round(st[label]["ipc"], 4),
        })

    # Flat/radix parity: the degenerate radix config must reproduce the
    # flat walker's timings bitwise (mode="base" exercises the flat
    # base-page path; large arrays zeroed so entry budgets match).
    parity_kw = dict(mode="base", paging=False,
                     l1_large_entries=0, l2_large_entries=0)
    tf, _ = build_workload(names, "gpu-mmu", seed=0, n_access=n_access)
    sim_f = TranslationSim(SimConfig(translation="flat", **parity_kw), tf)
    tr, _ = build_workload(names, "gpu-mmu", seed=0, n_access=n_access)
    sim_r = TranslationSim(
        SimConfig(translation="radix", pwc_entries=0, coalesce_span=1,
                  **parity_kw), tr)
    rf, rr = sim_f.run(), sim_r.run()
    parity = (all(f.cycles == r.cycles and f.retired == r.retired
                  for f, r in zip(rf, rr))
              and sim_f.walker.walks == sim_r.total_walks())
    rows.append({
        "bench": "translation", "mode": "flat-parity",
        "flat_walks": sim_f.walker.walks,
        "radix_walks": sim_r.total_walks(),
        "flat_cycles": round(float(sum(f.cycles for f in rf)), 1),
        "radix_cycles": round(float(sum(r.cycles for r in rr)), 1),
    })

    rows.append({
        "bench": "translation", "mode": "CLAIM",
        "claim_translation_mosaic_fewer_walks":
            bool(st["mosaic-radix"]["walks"]
                 < st["gpu-mmu-radix"]["walks"]),
        "claim_translation_mosaic_cheaper_walk_cycles":
            bool(st["mosaic-radix"]["walk_cycles"]
                 < st["gpu-mmu-radix"]["walk_cycles"]),
        "claim_translation_coalescing_cuts_walks":
            bool(st["mosaic-radix"]["walks"]
                 < st["mosaic-span1"]["walks"]),
        "claim_translation_pwc_cuts_dram_accesses":
            bool(st["gpu-mmu-radix"]["dram"]
                 < st["gpu-mmu-nopwc"]["dram"]),
        "claim_translation_flat_radix_parity": bool(parity),
    })
    assert parity, "flat/radix parity broke — pre-§15 claims at risk"
    return rows


def run_translation_cluster(mode: str):
    """Walker-contention routing scenario (DESIGN.md §15).

    Engine 0 is pinned four long-context requests, engine 1 four
    short-context ones with *identical* decode footprints (same
    ``max_new``, same arrival).  The meter runs with a deliberately
    small TLB (4/8 coalesced entries) so engine 0's big KV tables
    capacity-thrash its radix walker every step — sustained walker
    queueing — while engine 1's tables fit.  An unpinned probe wave
    then arrives with ``max_new=1``: ``ceil(remaining/max_batch)``
    stays under the critical path, so every pre-§15 cost term ties
    exactly, and the dispatch is decided purely by the tie-break.
    Without translation awareness that is the engine index (probes
    pile onto the walker-saturated engine 0); with it, the walker
    backlog term routes them to engine 1.  ``mode``: "aware",
    "unaware", or "off" (meters off — the pre-§15 router verbatim).
    """
    from repro.serving.cluster import ServingCluster

    cfg = get_smoke_config("qwen2.5-3b")
    cluster = ServingCluster(
        cfg, geometry=GEO, n_engines=2, max_batch=8, max_seq=192,
        manager_kind="gpu-mmu", seed=0, prefix_cache=False,
        migrate=False, router_steal_queued=False,
        decode_window_us=1000.0,
        translation="off" if mode == "off" else "radix",
        router_translation_aware=(mode != "unaware"),
        translation_kw={"l1_entries": 4, "l2_entries": 8})
    rng = np.random.default_rng(11)

    def _req(rid, tokens, max_new, tenant=0):
        return Request(rid=rid, tenant=tenant,
                       prompt=rng.integers(0, cfg.vocab_size, tokens)
                       .astype(np.int32), max_new=max_new)

    heavy = [_req(i, 160, 8, tenant=0) for i in range(4)]
    light = [_req(10 + i, 8, 8, tenant=1) for i in range(4)]
    for r in heavy:
        cluster.submit(r, engine=0)
    for r in light:
        cluster.submit(r, engine=1)
    for _ in range(2):       # book walker time before the probes arrive
        cluster.step()
    probes = [_req(100 + i, 16, 1, tenant=2) for i in range(4)]
    for r in probes:
        cluster.submit(r)
    cluster.run_until_drained(max_steps=600)
    reqs = heavy + light + probes
    assert all(r.done for r in reqs), "translation bench not drained"
    cluster.check_invariants()
    return cluster, reqs, probes


def translation_router_compare() -> List[Dict]:
    """Translation-aware routing A/B (DESIGN.md §15).

    Claims: (a) tokens byte-identical across aware / unaware / off —
    the walker term moves *placement*, never what decode computes;
    (b) awareness routes the probe wave away from the walker-saturated
    engine; (c) cluster-wide walker-queue interference drops.
    """
    rows = []
    outs, probe_split, queue_cycles = {}, {}, {}
    for mode in ("off", "unaware", "aware"):
        cluster, reqs, probes = run_translation_cluster(mode)
        outs[mode] = {r.rid: tuple(r.out) for r in reqs}
        t = cluster.stats().totals
        on1 = sum(1 for r in probes
                  if cluster.router._owner.get(r.rid) == 1)
        probe_split[mode] = on1
        queue_cycles[mode] = t.translation_queue_cycles
        rows.append({
            "bench": "translation-router", "mode": mode,
            "tok_per_s_cpu": round(t.tok_per_s(), 1),
            "probes_to_engine1": on1,
            "translation_lookups": t.translation_lookups,
            "translation_walks": t.translation_walks,
            "translation_queue_cycles":
                round(t.translation_queue_cycles, 1),
            "translation_us": round(t.translation_us, 1),
            "dispatched": "/".join(
                str(cluster.router.stats.dispatched.get(i, 0))
                for i in range(2)),
        })
    identical = outs["off"] == outs["unaware"] == outs["aware"]
    rows.append({
        "bench": "translation-router", "mode": "CLAIM",
        "claim_translation_tokens_identical": bool(identical),
        "claim_translation_aware_routes_off_hot_walker":
            bool(probe_split["aware"] > probe_split["unaware"]),
        "claim_translation_aware_cuts_queue_cycles":
            bool(queue_cycles["aware"] < queue_cycles["unaware"]),
    })
    assert identical, "translation metering changed model outputs!"
    return rows
