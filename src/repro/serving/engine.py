"""Multi-tenant continuous-batching serving engine on the Mosaic pool.

Lifecycle per request: admit → (en-masse) prefill → join the decode batch →
complete → deallocate (whole frames return to CoCoA thanks to the soft
guarantee; CAC compacts any splintered leftovers and the engine executes
the copy plan with the ``page_compact`` kernel between steps).

This is the paper's multi-application GPU setting transplanted: tenants
share one physical pool; the manager flag flips between ``mosaic`` and the
``gpu-mmu`` baseline so benchmarks can measure both (Figs. 5/6 analogue:
same workload, different manager).

Host tier (DESIGN.md §6): the pool may be *oversubscribed* — sized below
the workload's peak KV working set.  Each step ``touch()``es the pages its
packed tables will read and batch-faults the missing ones in from the
:class:`~repro.serving.host_tier.HostPageStore` as one gather-transfer
(contiguous runs merge into single DMAs — Mosaic's contiguity pays on the
I/O bus too).  When an allocation hits ``OutOfMemory`` even after CAC
compaction, the engine preempts the cheapest-to-evict active request
(cost-aware score: resident pages × priority × remaining tokens) —
evicting its frames to the host store at base-page granularity — instead
of failing, and resumes it later via demand fault-in; a resumed request
produces exactly the tokens it would have produced unpreempted.

Async fault-in (DESIGN.md §7): with ``fault_mode="async"`` (the default)
each step runs a two-stage pipeline — drain the prefetches that completed
during the previous decode into the double-buffered staging region, fault
only the remaining misses synchronously (*exposed* µs), then issue the
predicted next-step touches to the :class:`~repro.serving.dma.
AsyncDMAEngine` so their transfers run on DMA channels *while* this
step's decode computes (*hidden* µs).  ``fault_mode="sync"`` keeps PR 1's
blocking path; both modes produce byte-identical tokens because the
prefetch machinery never alters allocation or scheduling, only when
transfers are modeled to happen.

Fused gather-attend decode (DESIGN.md §13): ``fault_mode="fused"`` goes
one step further and removes the pre-decode DMA barrier entirely.  The
step pipeline becomes admit → start-decode-on-resident →
drain-within-kernel: fault-in resolves this step's misses to *sources*
(staged payloads, in-flight prefetch jobs, fresh demand jobs) without a
single ``dma.wait``, decode launches immediately with a per-page
readiness mask (``PageCtx.slots``) that lets attention read late
arrivals straight from the staging pools, and the collected jobs settle
against the *end* of the decode window — only transfer tails that
outlive the window are exposed.  Tokens stay byte-identical to
sync/async because the accumulation order never changes; only each
page's load source (pool vs. staging) differs, and the staged bytes
equal what the scatter would have written.

Prefix-cache reuse (DESIGN.md §8): finished prompts park their full
pages' KV in the :class:`~repro.serving.host_tier.PrefixIndex`, keyed by
chained per-page content hash.  An admission whose prompt shares a
cached page-aligned prefix skips decode for those tokens: the pages
fault in from the host tier through the async DMA pipeline *at
admission time* (merged DMAs — they were allocated en masse, so they are
contiguous) and only the suffix is prefilled, its queries attending over
the cached KV.  Tokens are byte-identical with the cache on or off
(suffix prefill reproduces full prefill bitwise; dense-transformer
families only).  The DMA timeline is full-duplex: preemption eviction
gathers and prefix parking ride the channels' "out" lanes, visible in
the per-direction stats without delaying inbound fault-ins.  Resume
scheduling is SLO-aware: within a priority tier, preempted requests
resume tightest-deadline-first and the deadline pressure widens the
resume-prefetch window (``Prefetcher.plan_depth``).

Cluster tier (DESIGN.md §10): an engine can be one replica of a
:class:`~repro.serving.cluster.ServingCluster` — it then holds a
domain-bound view of the shared host store (``host=``), a cluster-wide
prefix index (``prefix_index=``), and an ``engine_id`` naming its
frame-lease protection domain.  ``export_preempted``/``import_preempted``
hand a fully-swapped-out request to another replica (work-stealing
migration, driven by the :class:`~repro.serving.router.RequestRouter`)
with zero re-prefill.  Completions with a deadline record per-priority-
tier hit/miss counters (``EngineStats.deadline_*``; ``summary()`` prints
SLO attainment).  Non-dense model families never park into (or match
from) a prefix index — suffix prefill could not replay their KV — and
count the skips in ``prefix_park_skipped`` instead.

The engine is deliberately host-driven: page tables are packed on host per
step (Mosaic's runtime half), while the device step (prefill/decode +
pool writes) is a single jitted call (the hardware half).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, PoolGeometry
from repro.core.cocoa import OutOfMemory
from repro.core.demand_paging import LinkModel
from repro.kernels import ops as kops
from repro.models.common import cast
from repro.models.lm import LM
from repro.serving.dma import (AsyncDMAEngine, DMAJob, Key, Prefetcher,
                               StagingBuffer)
from repro.serving.host_tier import HostPageStore, PrefixIndex
from repro.serving.kv_cache import ShardedKVCache
from repro.serving.trace import WAIT_SAMPLES, Span, listen, spanned


@functools.lru_cache(maxsize=None)
def _model_fns(cfg: ModelConfig):
    """Jitted (init, decode_step, prefill) of ``cfg``'s model, shared by
    every engine of the process so replicas compile each shape once.
    ``init`` yields the weights straight in ``cfg.dtype``: no float32
    copy of the tree is ever resident on the device."""
    lm = LM(cfg)
    dtype = jnp.dtype(cfg.dtype)
    return (jax.jit(lambda key: cast(lm.init(key), dtype)),
            jax.jit(lm.decode_step),
            jax.jit(lm.prefill, static_argnames=("prefix_len",)))


@dataclasses.dataclass
class Request:
    rid: int
    tenant: int
    prompt: np.ndarray           # int32 [T]
    max_new: int
    priority: int = 0            # higher = more important (preempt lowest)
    # SLO deadline on the engine's modeled µs clock (DESIGN.md §8):
    # among same-priority resume candidates, tighter slack resumes (and
    # prefetches) first.  None = best-effort, FIFO within its tier.
    deadline_us: Optional[float] = None
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    preemptions: int = 0
    # perf_counter_ns stamps behind the measured waits: set by submit()
    # (cleared by the first prefill) and by _preempt() (cleared by the
    # resume).
    submit_ns: Optional[int] = dataclasses.field(
        default=None, repr=False, compare=False)
    preempt_ns: Optional[int] = dataclasses.field(
        default=None, repr=False, compare=False)


def _wait_ring() -> Deque[int]:
    return deque(maxlen=WAIT_SAMPLES)


@dataclasses.dataclass
class EngineStats:
    prefill_tokens: int = 0
    decode_steps: int = 0
    decode_tokens: int = 0
    compaction_copies: int = 0
    wall_s: float = 0.0          # engine.step spans' total (s)
    coalesced_sum: float = 0.0   # running sum of per-step coalesced fraction
    occupancy_sum: float = 0.0
    # Host-tier demand paging (DESIGN.md §6).
    faults: int = 0              # base pages faulted in
    fault_dmas: int = 0          # DMA descriptors (contiguous runs)
    fault_steps: int = 0         # engine steps that faulted at all
    bytes_in: int = 0
    transfer_us: float = 0.0
    swaps_out: int = 0           # whole-request preemptions
    swaps_in: int = 0            # whole-request resumes
    # Admissions the fit test refused without evicting anyone: the
    # candidate could not fit even with every eligible victim gone.
    evictions_declined: int = 0
    # Async fault-in pipeline (DESIGN.md §7).
    fault_exposed_us: float = 0.0   # transfer µs the engine stalled on
    fault_hidden_us: float = 0.0    # transfer µs overlapped with decode
    prefetch_hits: int = 0          # faults served from staging/in-flight
    prefetch_misses: int = 0        # demand faults the prefetcher missed
    prefetch_wasted: int = 0        # prefetched pages never consumed
    # Full-duplex outbound DMA (DESIGN.md §8): eviction gathers + parking.
    evict_pages: int = 0            # pages gathered device→host on channels
    evict_dmas: int = 0             # outbound DMA descriptors
    bytes_out: int = 0
    evict_us: float = 0.0           # outbound transfer µs on the timeline
    # Prefix-cache reuse (DESIGN.md §8).
    prefix_hits: int = 0            # admissions that matched a cached prefix
    prefix_misses: int = 0          # cache-enabled admissions with no match
    prefix_reused_tokens: int = 0   # prompt tokens NOT re-prefilled
    prefix_parked_pages: int = 0    # pages parked into the index
    prefix_fault_us: float = 0.0    # modeled µs to fault reused prefixes in
    admit_hits: int = 0             # admissions via the suffix-prefill path
    admit_colds: int = 0            # admissions via the full-prefill path
    admit_hit_us: float = 0.0       # engine.prefill µs of cache hits
    admit_cold_us: float = 0.0      # engine.prefill µs of cold admissions
    # Non-dense fallback (DESIGN.md §10): parks skipped because the model
    # family cannot replay cached KV (MoE routing / MLA latents / ssm
    # state) — counted instead of silently caching unreplayable pages.
    prefix_park_skipped: int = 0
    # Disk spill tier (DESIGN.md §11): parks refused by host-tier
    # back-pressure (write-back buffer saturated), admissions that had
    # to promote spilled frames, and the modeled disk-read stall µs.
    prefix_park_refused: int = 0
    promotions: int = 0
    promote_stall_us: float = 0.0
    # Per-admission modeled latency samples (µs): suffix/full prefill
    # compute at prefill_us_per_token plus any promote stall — the
    # distribution behind the spill bench's p99 claim.
    admit_lat_us: List[float] = dataclasses.field(default_factory=list)
    # Cross-engine migration (DESIGN.md §10): preempted requests handed
    # off through the shared host tier, never re-prefilled.
    migrations_out: int = 0
    migrations_in: int = 0
    # Failure recovery (DESIGN.md §12): requests restarted from the
    # prompt after a spill quarantine destroyed their swapped-out
    # payloads, and cache-hit admissions that fell back to full prefill
    # because the matched prefix payloads were quarantined mid-admission.
    lost_restarts: int = 0
    prefix_rederives: int = 0
    # Deadline accounting per priority tier (ROADMAP follow-up): a
    # request with a deadline counts as a hit when it completes with
    # ``clock_us <= deadline_us`` on the engine's modeled clock.
    deadline_hits: Dict[int, int] = dataclasses.field(default_factory=dict)
    deadline_misses: Dict[int, int] = dataclasses.field(default_factory=dict)
    # Fused gather-attend decode (DESIGN.md §13): pages the kernel
    # consumed straight from staging — already landed when the step's
    # decode window opened (*ready*) vs. arriving inside the window
    # (*drained* in-kernel) — and the µs the engine stalled on transfer
    # tails that outlived their window.
    fused_ready_pages: int = 0
    fused_drained_pages: int = 0
    fused_tail_us: float = 0.0
    # Proactive pre-staging of queued work (DESIGN.md §14): pages the
    # router faulted toward this engine before the owning request's
    # admission step.  *hit* — admission (or fault-in) found the page
    # staged/in-flight and skipped its own transfer; *wasted* — staged
    # but never consumed (prefix re-matched differently, request
    # retired first); *cancelled* — dropped by a steal/crash retarget,
    # with the un-elapsed lane time refunded (``prestage_refund_us``).
    prestaged_pages: int = 0
    prestage_hits: int = 0
    prestage_wasted: int = 0
    prestage_cancelled: int = 0
    prestage_refund_us: float = 0.0
    # Translation meter (DESIGN.md §15): per-step KV page translations
    # through the coalesced-TLB + radix-walker model.  Observational —
    # decode timing and tokens are identical with the meter on or off —
    # but the walker backlog is the router's translation-interference
    # term, and translation_us is the modeled µs the lookups would cost.
    translation_lookups: int = 0
    translation_tlb_hits: int = 0
    translation_walks: int = 0
    translation_walk_cycles: float = 0.0
    translation_queue_cycles: float = 0.0
    translation_us: float = 0.0
    # Spans of the engine's host work (serving/trace.py), by name: count,
    # total ns, self ns (total less its child spans), and the backend
    # compiles made while it was the innermost open span.
    span_n: Dict[str, int] = dataclasses.field(default_factory=dict)
    span_ns: Dict[str, int] = dataclasses.field(default_factory=dict)
    span_self_ns: Dict[str, int] = dataclasses.field(default_factory=dict)
    compiles: Dict[str, int] = dataclasses.field(default_factory=dict)
    # Measured waits (ns), the newest WAIT_SAMPLES of each: submit() to
    # the start of the request's first prefill, and _preempt() to the
    # successful _resume() of the same request.
    queue_wait_ns: Deque[int] = dataclasses.field(default_factory=_wait_ring)
    resume_wait_ns: Deque[int] = dataclasses.field(
        default_factory=_wait_ring)

    def note_deadline(self, priority: int, hit: bool) -> None:
        d = self.deadline_hits if hit else self.deadline_misses
        d[priority] = d.get(priority, 0) + 1

    def slo_attainment(self, priority: Optional[int] = None
                       ) -> Optional[float]:
        """Fraction of deadline-carrying completions that met their
        deadline — overall, or for one priority tier.  None when no
        deadline-carrying request has completed (not 1.0: 'no SLOs set'
        must be distinguishable from 'all SLOs met')."""
        if priority is None:
            hits = sum(self.deadline_hits.values())
            total = hits + sum(self.deadline_misses.values())
        else:
            hits = self.deadline_hits.get(priority, 0)
            total = hits + self.deadline_misses.get(priority, 0)
        return None if total == 0 else hits / total

    @property
    def coalesced_mean(self) -> float:
        return self.coalesced_sum / max(self.decode_steps, 1)

    @property
    def occupancy_mean(self) -> float:
        return self.occupancy_sum / max(self.decode_steps, 1)

    def admit_hit_mean_us(self) -> float:
        return self.admit_hit_us / max(self.admit_hits, 1)

    def admit_cold_mean_us(self) -> float:
        return self.admit_cold_us / max(self.admit_colds, 1)

    def admit_p99_us(self, start: int = 0) -> float:
        """p99 of the modeled per-admission latencies (µs), optionally
        over the samples from index ``start`` on (benches slice off a
        warm-up wave).  0.0 when no samples."""
        samples = self.admit_lat_us[start:]
        if not samples:
            return 0.0
        return float(np.percentile(np.asarray(samples), 99))

    def tok_per_s(self) -> float:
        # A zero-step engine (or mocked clock) must report 0, not explode.
        if self.wall_s <= 0.0:
            return 0.0
        return (self.prefill_tokens + self.decode_tokens) / self.wall_s

    def summary(self) -> str:
        """One-line human summary: throughput, the exposed/hidden fault
        split, the prefetch hit/miss/wasted counts, duplex outbound
        traffic and swap/prefix-reuse totals."""
        line = (
            f"{self.tok_per_s():.1f} tok/s | "
            f"{self.prefill_tokens} prefill + {self.decode_tokens} decode "
            f"tok in {self.decode_steps} steps | "
            f"faults {self.faults} in {self.fault_dmas} DMAs "
            f"({self.bytes_in / 1024:.0f} KiB, "
            f"{self.fault_hidden_us:.0f}us hidden / "
            f"{self.fault_exposed_us:.0f}us exposed) | "
            f"prefetch {self.prefetch_hits}/{self.prefetch_misses}/"
            f"{self.prefetch_wasted} hit/miss/wasted | "
            f"out {self.evict_pages} pages in {self.evict_dmas} DMAs "
            f"({self.bytes_out / 1024:.0f} KiB) | "
            f"swaps {self.swaps_out}/{self.swaps_in} "
            f"({self.evictions_declined} declined)")
        if self.prefix_hits or self.prefix_misses:
            line += (f" | prefix {self.prefix_hits}/{self.prefix_misses} "
                     f"hit/miss ({self.prefix_reused_tokens} tok reused)")
        if self.prefix_park_skipped:
            line += f" | parks skipped {self.prefix_park_skipped} (non-dense)"
        if self.prefix_park_refused:
            line += (f" | parks refused {self.prefix_park_refused} "
                     f"(wb back-pressure)")
        if self.promotions:
            line += (f" | promotes {self.promotions} "
                     f"({self.promote_stall_us:.0f}us stall)")
        if self.migrations_out or self.migrations_in:
            line += (f" | migrated {self.migrations_out} out / "
                     f"{self.migrations_in} in")
        if self.lost_restarts or self.prefix_rederives:
            line += (f" | quarantine: {self.lost_restarts} restarts, "
                     f"{self.prefix_rederives} prefix re-derives")
        if self.fused_ready_pages or self.fused_drained_pages:
            line += (f" | fused {self.fused_ready_pages} ready + "
                     f"{self.fused_drained_pages} drained in-kernel "
                     f"({self.fused_tail_us:.0f}us tail)")
        if self.prestaged_pages:
            line += (f" | prestage {self.prestaged_pages} pages "
                     f"({self.prestage_hits}/{self.prestage_wasted}/"
                     f"{self.prestage_cancelled} hit/wasted/cancelled)")
        if self.translation_lookups:
            line += (f" | translation {self.translation_lookups} lookups, "
                     f"{self.translation_walks} walks "
                     f"({self.translation_us:.0f}us, queue "
                     f"{self.translation_queue_cycles:.0f} cyc)")
        att = self.slo_attainment()
        if att is not None:
            tiers = sorted(set(self.deadline_hits) | set(self.deadline_misses),
                           reverse=True)
            per = ", ".join(
                f"t{t} {self.deadline_hits.get(t, 0)}/"
                f"{self.deadline_hits.get(t, 0) + self.deadline_misses.get(t, 0)}"
                for t in tiers)
            line += f" | SLO {att:.1%} ({per})"
        return line


class ServingEngine:
    def __init__(self, cfg: ModelConfig, *, geometry: PoolGeometry,
                 max_batch: int, max_seq: int, manager_kind: str = "mosaic",
                 n_shards: int = 1, params=None, seed: int = 0,
                 use_pallas: bool = False, oversubscription: float = 1.0,
                 link: Optional[LinkModel] = None,
                 fault_mode: str = "async", dma_channels: int = 2,
                 prefetch_depth: int = 2, victim_policy: str = "cost",
                 decode_window_us: Optional[float] = None,
                 prefill_us_per_token: float = 50.0,
                 prefix_cache: bool = True,
                 prefix_capacity_pages: int = 4096,
                 duplex: bool = True,
                 slo_urgency_us: float = 1000.0,
                 host: Optional[HostPageStore] = None,
                 prefix_index: Optional[PrefixIndex] = None,
                 engine_id: int = 0,
                 injector=None,
                 translation: str = "off",
                 translation_kw: Optional[dict] = None):
        # ValueError, not assert: configuration validation must survive
        # ``python -O`` (asserts compile away under optimization).
        if fault_mode not in ("async", "sync", "fused"):
            raise ValueError(
                f"fault_mode must be 'async', 'sync' or 'fused', "
                f"got {fault_mode!r}")
        if fault_mode == "fused" and cfg.mla is not None:
            # The fused path stages dense (k, v) page payloads into the
            # attention kernel; MLA's latent pools take a different
            # decode path that cannot consume them.
            raise ValueError("fault_mode='fused' supports dense-attention "
                             "families only (not MLA)")
        if victim_policy not in ("cost", "priority"):
            raise ValueError(
                f"victim_policy must be 'cost' or 'priority', "
                f"got {victim_policy!r}")
        if translation not in ("off", "flat", "radix"):
            raise ValueError(
                f"translation must be 'off', 'flat' or 'radix', "
                f"got {translation!r}")
        self.cfg = cfg
        # Replica identity within a cluster (DESIGN.md §10): the host-tier
        # frame-lease protection domain and the reporting label.
        self.engine_id = engine_id
        # Replica placement: engine i keeps its weights, pools and step
        # inputs on device i (mod the device count); a lone engine uses
        # device 0.
        self.device = jax.devices()[engine_id % jax.device_count()]
        # Failure model (DESIGN.md §12): False after an injected crash —
        # the router stops dispatching here and recovers the workload.
        self.alive = True
        self.injector = injector
        self.fault_mode = fault_mode
        self.victim_policy = victim_policy
        # Full-duplex outbound modeling (DESIGN.md §8): eviction gathers
        # and prefix parking ride the DMA channels' "out" lanes.  Only
        # the async pipeline has a channel timeline to ride.
        self.duplex = duplex and fault_mode in ("async", "fused")
        # Deadline slack below which a resume candidate counts as urgent
        # for SLO-aware prefetch-depth planning.
        self.slo_urgency_us = slo_urgency_us
        # Modeled compute window per decode step for the DMA timeline.
        # None = measured decode wall time; on CPU that includes jit
        # compilation (seconds), which dwarfs the µs-scale transfers —
        # set an explicit window to model a real accelerator's step time
        # and exercise partial overlap deterministically.
        self.decode_window_us = decode_window_us
        # Modeled prefill compute cost per prompt token (µs) — the basis
        # of the per-admission latency samples (admit_lat_us): a cache
        # hit pays only its suffix (+ any spill-promote stall), a cold
        # admission the full prompt.  Deliberately on the same modeled
        # timeline as decode_window_us, not wall time: CPU jit wall time
        # would drown the µs-scale effects the benches measure.
        self.prefill_us_per_token = prefill_us_per_token
        self.lm = LM(cfg)
        self.geo = geometry
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.use_pallas = use_pallas
        pages_per_seq = (max_seq + geometry.page_tokens - 1) \
            // geometry.page_tokens
        self.mpps = int(np.ceil(pages_per_seq / n_shards
                                / geometry.frame_pages)
                        ) * geometry.frame_pages
        # oversubscription > 1 shrinks HBM below the sized-for-peak working
        # set; the host tier absorbs the overflow (DESIGN.md §6).
        per_shard = int(geometry.pages_for(max_seq, max_batch) / n_shards
                        / max(oversubscription, 1e-9))
        per_shard = max(per_shard, self.mpps)  # ≥ one max-length sequence
        per_shard = ((per_shard + geometry.frame_pages - 1)
                     // geometry.frame_pages) * geometry.frame_pages
        probe = self.lm.pool_shapes(1, geometry.page_tokens)
        if probe:
            # True KV bytes of one base page across all layers (k + v).
            page_bytes = sum(
                int(np.prod(s.shape[2:])) * s.shape[0]
                * np.dtype(s.dtype).itemsize for s in probe)
        else:
            page_bytes = 0      # attention-free: nominal paper default
        self.page_bytes = page_bytes
        self.link = link or LinkModel()
        self.cache = ShardedKVCache(geometry, per_shard, n_shards,
                                    manager_kind, link=self.link,
                                    page_bytes=page_bytes)
        # ``host`` may be a cluster-shared store view (DESIGN.md §10);
        # standalone engines own a private store as before.
        self.host = host if host is not None else HostPageStore()
        # Content-hash prefix cache (DESIGN.md §8).  Suffix-only prefill
        # needs full-sequence attention over cached KV pages, which only
        # the dense-transformer family supports bitwise (MoE capacity
        # routing is batch-shape-dependent; ssm/hybrid carry recurrent
        # state; encdec cross-attends; MLA caches latents).
        self.prefix_supported = (cfg.family == "dense" and cfg.mla is None
                                 and bool(page_bytes))
        self.prefix: Optional[PrefixIndex] = None
        if prefix_index is not None:
            # Cluster-shared index: keep the reference even when this
            # replica's model family cannot replay cached KV — the
            # match/park paths skip and count instead of caching
            # unreplayable pages (the MoE/MLA fallback, DESIGN.md §10).
            self.prefix = prefix_index if prefix_cache else None
        elif prefix_cache and self.prefix_supported:
            self.prefix = PrefixIndex(self.host, geometry.page_tokens,
                                      capacity_pages=prefix_capacity_pages)
        # Weights live in the model's dtype, so the per-call cast inside
        # the model is a no-op.
        init, self._decode_jit, self._prefill_jit = _model_fns(cfg)
        if params is None:
            params = init(self._dev(jax.random.PRNGKey(seed)))
        self.params = self._dev(cast(params, jnp.dtype(cfg.dtype)))
        shapes = self.lm.pool_shapes(per_shard * n_shards,
                                     geometry.page_tokens)
        self.pools = (tuple(self._dev(np.zeros(s.shape, s.dtype))
                            for s in shapes) if shapes else None)
        self.states: Dict[int, dict] = {}
        self.queue: Deque[Request] = deque()
        self.preempted: Deque[Request] = deque()
        self._held: List[Request] = []
        self._saved_tokens: Dict[int, int] = {}
        self.active: List[Request] = []
        # rids migrated away to another engine (DESIGN.md §10): their
        # in-flight prefetch payloads must settle as waste here, never
        # re-stage — the destination engine owns the host copies now.
        self._foreign: set = set()
        self._stalled_steps = 0      # consecutive no-decode steps
        self.stats = EngineStats()
        listen()
        # Async fault-in pipeline (DESIGN.md §7): DMA channel timeline +
        # double-buffered staging + next-step touch predictor.  The clock
        # is modeled µs: advanced by measured decode wall time (compute
        # the transfers hide behind) and by exposed fault stalls.
        self.dma = AsyncDMAEngine(self.link, n_channels=dma_channels,
                                  duplex=duplex, injector=injector)
        self.staging = StagingBuffer()
        self.prefetch = Prefetcher(depth=prefetch_depth)
        # Keys pre-staged toward this engine for still-queued requests
        # (DESIGN.md §14), mapped to the source page's owner id (prefix
        # owners are minted once and never reused, so a matching owner
        # proves the staged bytes are the ones admission would fetch).
        # Consumed → prestage_hits, invalidated at retire/export →
        # prestage_wasted, retargeted by steal/crash → cancelled.
        self._prestage_keys: Dict[Key, int] = {}
        # Translation meter (DESIGN.md §15): the decode loop feeds it the
        # KV page tables each step's batch reads; subregion span defaults
        # to the allocator's frame size — exactly the contiguity unit
        # CoCoA preserves, so an unsplintered frame is one TLB entry.
        self.translation = translation
        self.translation_meter = None
        if translation != "off":
            from repro.core.ptw import TranslationMeter
            self.translation_meter = TranslationMeter(
                translation, span=max(1, geometry.frame_pages),
                **(translation_kw or {}))
        self._clock_us = 0.0
        # Fused decode step state (DESIGN.md §13): DMA jobs whose pages
        # this step's kernel consumes (settled at the decode-window end,
        # not before decode), the staged ((shard, ppn), payload,
        # arrive_us) entries awaiting the post-decode pool scatter, and
        # the window-open timestamp splitting ready vs drained pages.
        self._fused_jobs: List[DMAJob] = []
        self._fused_staged: List[tuple] = []
        self._fused_t0 = 0.0

    def _dev(self, tree):
        """Commit host data (or another device's arrays) to this
        engine's device."""
        return jax.device_put(tree, self.device)

    # ------------------------------------------------------------- admission

    def submit(self, req: Request):
        req.submit_ns = time.perf_counter_ns()
        self.queue.append(req)

    @spanned("engine.admit")
    def _admit(self):
        # One admission order across resumes and new arrivals: highest
        # priority first; within a tier, tightest SLO deadline first
        # (deadline-free requests rank last and stay FIFO — max() is
        # stable), and resumes beat arrivals (they are older and already
        # hold host payloads + decode state).  This keeps a premium
        # arrival from being head-of-line blocked behind an unadmittable
        # best-effort request — in either pool.
        def rank(r: Request):
            return (r.priority, -self._slack_or_inf(r))

        skipped: set = set()     # failed this round; don't block the rest
        while True:
            cand = max((r for r in self.preempted
                        if r.rid not in skipped),
                       key=rank, default=None)
            queued = max((r for r in self.queue if r.rid not in skipped),
                         key=rank, default=None)
            resume = cand is not None and (
                queued is None or cand.priority >= queued.priority)
            if not resume:
                cand = queued
            if cand is None:
                break
            if len(self.active) >= self.max_batch:
                # Batch slots are a resource too: a premium candidate
                # displaces a strictly-lower-priority active request (the
                # strictness makes displacement chains terminate).  With a
                # full batch and no displaceable victim, no lower-priority
                # candidate can enter either — stop the round.
                # Nor does it displace anyone for a candidate that memory
                # could not hold with every eligible victim gone.
                victim = self._pick_victim(below_priority=cand.priority)
                if victim is None:
                    break
                # A resume whose payloads were lost restarts from its
                # prompt (_resume), so that is all it allocates.
                need = (self._saved_tokens[cand.rid]
                        if resume and not self.host.is_lost(cand.rid)
                        else self._prompt_tokens(cand))
                if not self._could_fit(cand, need,
                                       below_priority=cand.priority):
                    self.stats.evictions_declined += 1
                    skipped.add(cand.rid)
                    continue
                self._preempt(victim)
            ok = self._resume(cand) if resume else self._admit_one(cand)
            if not ok:
                # Memory can't fit this candidate right now; a smaller or
                # lower-priority one may still fill the idle capacity.
                # The fit test evicts nobody for a candidate that could
                # not fit with every victim gone, so a victim preempted
                # for it in vain (the test is necessary, not sufficient)
                # is rare; it resumes right here.
                skipped.add(cand.rid)
                continue
            (self.preempted if resume else self.queue).remove(cand)
            self.active.append(cand)
        if not self.active and (self.queue or self.preempted):
            raise RuntimeError(
                "pool cannot hold a single request: shrink max_seq or grow "
                "the pool (oversubscription too aggressive)")

    # --------------------------------------------------- preemption / resume

    def _victim_score(self, r: Request) -> float:
        """Cost of evicting ``r``: resident pages (gather + fault-back
        traffic) × priority (importance) × remaining tokens (how long it
        still needs its memory — a nearly-done request vacates cheaply
        and re-finishes quickly).  Lower = better victim."""
        remaining = max(r.max_new - len(r.out), 1)
        return (float(self.cache.resident_page_count(r.rid))
                * (r.priority + 1) * remaining)

    def _eligible_victims(self, below_priority: Optional[int],
                          exclude: Tuple[int, ...]) -> List[Request]:
        """Active requests a victim may be picked from: below
        ``below_priority`` (when given) and not in ``exclude``."""
        return [r for r in self.active if r.rid not in exclude
                and (below_priority is None or r.priority < below_priority)]

    def _pick_victim(self, *, below_priority: Optional[int] = None,
                     exclude: Tuple[int, ...] = ()) -> Optional[Request]:
        """Cheapest-to-evict active request under the configured policy.

        ``victim_policy="cost"`` (default) minimizes the eviction score;
        ``"priority"`` keeps PR 1's lowest-priority-only rule.  Both
        respect ``below_priority`` (a candidate never displaces its own
        tier or above at admission) and tie-break youngest-first.
        """
        cands = self._eligible_victims(below_priority, exclude)
        if not cands:
            return None
        if self.victim_policy == "priority":
            return min(cands, key=lambda r: (r.priority, -r.rid))
        return min(cands,
                   key=lambda r: (self._victim_score(r), r.priority, -r.rid))

    def _alloc_with_preemption(self, req: Request, n_tokens: int, *,
                               below_priority: Optional[int]) -> bool:
        """Allocate with growth headroom, preempting victims as needed.

        Victims go one at a time, cheapest first, and the allocation is
        retried after each.  The growth guard is part of the loop: an
        allocation that would leave no room for one decode step of the
        batch evicts another victim too, so no victim is stranded by a
        post-hoc guard failure.  Before the first eviction the fit test
        (:meth:`_could_fit`) asks whether ``req`` could be placed at all
        with every eligible victim gone; if not, nobody is evicted
        (``evictions_declined``) — each eviction would pay a device→host
        copy for an admission that fails anyway, and the victims would
        resume in the same round.  Returns False (leaving ``req``
        unallocated) when the test fails or no victim remains.
        """
        tested = False
        while True:
            try:
                self.cache.allocate(req.rid, n_tokens)
            except OutOfMemory:
                pass
            else:
                if self._growth_guard_ok(req):
                    return True
            # Roll back the partial allocation, or the one starved of
            # growth headroom (resume↔preempt livelock otherwise), and
            # evict one more victim before re-placing.
            self.cache.free(req.rid)
            victim = self._pick_victim(below_priority=below_priority,
                                       exclude=(req.rid,))
            if victim is None:
                return False
            if not tested:
                tested = True
                if not self._could_fit(req, n_tokens,
                                       below_priority=below_priority):
                    self.stats.evictions_declined += 1
                    return False
            self._preempt(victim)

    def _could_fit(self, req: Request, n_tokens: int, *,
                   below_priority: Optional[int]) -> bool:
        """The fit test: could ``req`` take ``n_tokens`` and pass the
        growth guard if every eligible victim left?  A necessary condition
        for :meth:`_alloc_with_preemption` to end in admission, so it
        never refuses one the loop would complete: the memory bound
        assumes the victims' pages freed and compaction at its best (each
        manager's :meth:`capacity_if_released`), and evicting every
        victim leaves the most free pages for the fewest staying rows."""
        victims = self._eligible_victims(below_priority, (req.rid,))
        gone = [v.rid for v in victims]
        if not self.cache.fits_if_released(n_tokens, gone):
            return False
        staying = len(self.active) - len(victims)
        return staying == 0 or (self.cache.free_page_count(gone)
                                - sum(self.cache.shard_pages(n_tokens))
                                >= staying + 2)

    def _gather_pages(self, entries: List[Tuple[int, int, int]]
                      ) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Device→host gather of [(shard, vpn, ppn)] pool pages as one
        batched launch; returns per-page (k_page, v_page) payloads."""
        if not entries or self.pools is None:
            return []
        with Span(self.stats, "tier.gather", pages=len(entries)):
            pps = self.cache.pages_per_shard
            gidx = self._dev(np.asarray(
                [s * pps + ppn for s, _v, ppn in entries], np.int32))
            k, v = self.pools
            kp = jax.vmap(lambda pool: kops.page_gather(
                pool, gidx, use_pallas=self.use_pallas))(k)
            vp = jax.vmap(lambda pool: kops.page_gather(
                pool, gidx, use_pallas=self.use_pallas))(v)
            # The wait for the gathers and the device→host copy.
            with Span(self.stats, "tier.to_host"):
                kp, vp = np.asarray(kp), np.asarray(vp)  # [L, n, ptok, kv, dh]
            return [(kp[:, i], vp[:, i]) for i in range(len(entries))]

    def _enqueue_outbound(self, keys: List[Tuple[int, int, int]],
                          entries: List[Tuple[int, int, int]],
                          payloads: List[Tuple[np.ndarray, np.ndarray]],
                          kind: str) -> None:
        """Account a device→host gather on the DMA channels' "out" lanes
        (full-duplex, DESIGN.md §8).  The host copy is synchronous in the
        model (write-back buffering), so the engine never stalls on these
        jobs — they occupy the outbound timeline, contend with other
        outbound traffic, and settle as hidden µs at the next drain."""
        if not self.duplex or not entries:
            return
        by_shard: Dict[int, List[int]] = {}
        for i, (s, _vpn, _ppn) in enumerate(entries):
            by_shard.setdefault(s, []).append(i)
        for s, idxs in sorted(by_shard.items()):
            job = self.dma.enqueue(
                [keys[i] for i in idxs],
                [entries[i][2] for i in idxs],
                self.cache.mgrs[s].residency.page_bytes,
                [payloads[i] for i in idxs],
                self._clock_us, kind=kind, direction="out")
            self.stats.evict_pages += len(job.keys)
            self.stats.evict_dmas += job.dma_count
            self.stats.bytes_out += job.nbytes
            self.stats.evict_us += job.transfer_us

    def _preempt(self, victim: Request) -> None:
        """Swap a request out: frames → host store at base-page granularity,
        decode state retained host-side, pages freed for other tenants."""
        rid = victim.rid
        with Span(self.stats, "mm.preempt", rid=rid):
            # Pending compaction plans rewrote tables already; land the
            # payload copies before gathering through those tables.
            self._run_compaction()
            pages = self.cache.mapped_pages(rid)     # [(shard, vpn, ppn)]
            # A just-resumed victim may still hold non-resident pages whose
            # payloads never left the host store — gather only resident
            # ones (the rest keep their existing host copies).
            resident = [
                (s, vpn, ppn) for s, vpn, ppn in pages
                if self.cache.mgrs[s].residency.resident[ppn]
            ]
            payloads = self._gather_pages(resident)
            for (s, vpn, _ppn), (kp, vp) in zip(resident, payloads):
                self.host.put(rid, s, vpn, kp, vp)
            # The gather itself is outbound DMA traffic: it rides the
            # channels' "out" lanes (hidden behind compute on a full-duplex
            # link; contending with fault-ins when half-duplex).
            self._enqueue_outbound(
                [(rid, s, vpn) for s, vpn, _p in resident],
                resident, payloads, kind="evict")
            self.cache.evict_pages(resident)
            self._saved_tokens[rid] = self.cache.seq_tokens[rid]
            self.cache.free(rid)
            self.active.remove(victim)
            victim.preemptions += 1
            self.preempted.append(victim)
            self.host.note_swap_out()
            self.stats.swaps_out += 1
        victim.preempt_ns = time.perf_counter_ns()

    def preempt(self, rid: int, *, hold: bool = False) -> bool:
        """Proactively swap an active request out (external-scheduler hook,
        cf. proactive memory scheduling).  It resumes automatically when
        capacity allows, unless ``hold`` is set — a held request stays
        swapped out until :meth:`release`.  Returns False if ``rid`` is not
        active."""
        for r in self.active:
            if r.rid == rid:
                self._preempt(r)
                if hold:
                    self.preempted.remove(r)
                    self._held.append(r)
                return True
        return False

    def release(self, rid: int) -> bool:
        """Make a held request eligible for resume again."""
        for r in self._held:
            if r.rid == rid:
                self._held.remove(r)
                self.preempted.append(r)
                return True
        return False

    # ------------------------------------------------- cross-engine handoff

    def export_preempted(self, rid: int) -> Optional[dict]:
        """Detach a preempted request for migration to another engine
        (DESIGN.md §10).  The request must be fully swapped out (it is:
        preemption gathers every resident page to the host store), so
        the bundle is pure host-side state — the Request, its decode
        state, and its saved token count.  Its host-resident pages stay
        in the (shared) store; the cluster re-leases their frames to the
        destination domain.  Local staging/prefetch state for the rid is
        invalidated, and in-flight DMA payloads will settle as waste."""
        for r in self.preempted:
            if r.rid == rid:
                break
        else:
            return None
        self.preempted.remove(r)
        bundle = {"request": r, "state": self.states.pop(rid, None),
                  "saved_tokens": self._saved_tokens.pop(rid)}
        self._note_prestage_waste(rid)
        dropped = self.staging.invalidate_seq(rid)
        self.stats.prefetch_wasted += dropped
        self.prefetch.stats["wasted_pages"] += dropped
        self.prefetch.cancel_seq(rid)
        self._foreign.add(rid)
        self.stats.migrations_out += 1
        return bundle

    def import_preempted(self, bundle: dict) -> None:
        """Adopt a migrated request: it joins this engine's resume queue
        and faults its pages in from the (shared) host store through
        this engine's own DMA lanes — no device-to-device copy and no
        re-prefill, ever."""
        r = bundle["request"]
        self._foreign.discard(r.rid)
        self.preempted.append(r)
        if bundle["state"] is not None:
            self.states[r.rid] = self._dev(bundle["state"])
        self._saved_tokens[r.rid] = bundle["saved_tokens"]
        self.stats.migrations_in += 1

    # ------------------------------------------------ proactive pre-staging
    # (DESIGN.md §14) The router calls these for *queued*, never-admitted
    # requests: once a target engine is picked, the request's prefix-index
    # hits and resume pages start faulting toward this engine's staging
    # buffers over the regular prefetch DMA lanes, so the later admission
    # step finds the transfers already in flight.  Strictly timing-only:
    # the probe is read-only (``peek_match``), allocation and scheduling
    # are untouched, and the staged payloads are byte-identical to what
    # admission would have fetched — tokens cannot change.

    def prestage_queued(self, req: Request) -> int:
        """Fault ``req``'s known-reusable pages toward staging before its
        admission step; returns the number of pages issued.

        Two sources: host copies the rid already owns (a re-queued /
        crash-requeued request resuming from the host tier) and
        prefix-index hits, staged under the exact ``(rid, shard, vpn)``
        keys :meth:`_prefill_suffix` will enqueue, so admission dedups
        against them.  Spilled frames are promoted here — moving the
        disk read off the admission critical path is the point — but the
        promote stall is *not* charged to this engine's clock: it is
        background work on the tier's disk lanes.
        """
        if self.fault_mode not in ("async", "fused") or not self.alive:
            return 0
        tier = getattr(self.host, "tier", None)
        keys: List[Key] = []
        srcs: List[Key] = []
        for key in self.host.seq_pages(req.rid):
            if self.staging.contains(key) or key in self.prefetch.in_flight:
                continue
            keys.append(key)
            srcs.append(key)
        prefix_pages = []
        if self.prefix is not None and self.prefix_supported:
            ptok = self.geo.page_tokens
            n, pages = self.prefix.peek_match(req.prompt)
            n = min(n, (len(req.prompt) - 1) // ptok)
            for pg in pages[:n]:
                key = (req.rid, pg.shard, pg.vpn)
                if self.staging.contains(key) \
                        or key in self.prefetch.in_flight:
                    continue
                keys.append(key)
                srcs.append((pg.owner, pg.shard, pg.vpn))
                prefix_pages.append((key, pg))
        if not keys:
            return 0
        # Promote any spilled source frames now (tier-modeled disk time,
        # off the admission path); a quarantine may destroy sources —
        # drop those from the batch rather than staging garbage.
        self.host.ensure_resident(srcs, now_us=self._clock_us)
        live = [(k, s) for k, s in zip(keys, srcs) if self.host.has(*s)]
        if not live:
            return 0
        keys = [k for k, _ in live]
        payloads = [self.host.peek(*s) for _, s in live]
        if any(p is None for p in payloads):
            return 0
        job = self.dma.enqueue(keys, list(range(len(keys))),
                               self.page_bytes, payloads, self._clock_us,
                               kind="prefetch")
        self._account_prefetch(job)
        for (k, s) in live:
            self._prestage_keys[k] = s[0]       # source owner fingerprint
        self.stats.prestaged_pages += len(keys)
        return len(keys)

    def cancel_prestage(self, rid: int) -> float:
        """Cancel ``rid``'s pre-staged pages (a steal or a crash
        retargeted the request before admission).  In-flight jobs whose
        pages are all pre-stage work for this rid are cancelled with a
        lane-time refund for the un-elapsed transfer remainder; payloads
        already staged are dropped.  Returns the refunded µs."""
        refunded = 0.0
        jobs: Dict[int, "DMAJob"] = {}
        for key, job in list(self.prefetch.in_flight.items()):
            if key[0] == rid and key in self._prestage_keys:
                jobs[job.job_id] = job
        for job in jobs.values():
            if not all(k[0] == rid and k in self._prestage_keys
                       for k in job.keys):
                continue            # mixed job: let it settle normally
            refunded += self.dma.cancel(job, self._clock_us)
            self.prefetch.forget(job.keys)
            self.stats.prestage_cancelled += len(job.keys)
            for k in job.keys:
                self._prestage_keys.pop(k, None)
        mine = [k for k in self._prestage_keys if k[0] == rid]
        if mine:
            for k in mine:
                self._prestage_keys.pop(k, None)
            self.stats.prestage_cancelled += \
                self.staging.invalidate_seq(rid)
        self.prefetch.cancel_seq(rid)
        self.stats.prestage_refund_us += refunded
        self.stats.transfer_us = max(0.0, self.stats.transfer_us - refunded)
        return refunded

    def _note_prestage_hit(self, key: Key) -> None:
        if self._prestage_keys.pop(key, None) is not None:
            self.stats.prestage_hits += 1

    def _note_prestage_waste(self, rid: int) -> None:
        """Account pre-staged pages the request never consumed (counted
        at the same invalidation points as prefetch waste)."""
        stale = [k for k in self._prestage_keys if k[0] == rid]
        for k in stale:
            self._prestage_keys.pop(k, None)
            self.stats.prestage_wasted += 1

    def _growth_guard_ok(self, req: Request) -> bool:
        """Admitting ``req`` must leave room for ≥ one decode step of the
        whole batch, or the newcomer would be preempted again before
        producing a token (resume↔preempt livelock)."""
        if not self.active:
            return True          # a sole request always fits (pool ≥ mpps)
        return self.cache.free_page_count() >= len(self.active) + 2

    def _resume(self, req: Request) -> bool:
        """Re-map a preempted request; payloads fault in on next touch.

        If the tier quarantined a spill frame holding this request's
        swapped-out payloads (DESIGN.md §12), the saved state is
        unusable — restart from the prompt instead.  The deterministic
        decoder makes the replay byte-identical to an unfaulted run."""
        with Span(self.stats, "mm.resume", rid=req.rid):
            if self.host.take_lost(req.rid):
                self._forget_request(req)
                self.stats.lost_restarts += 1
                return self._admit_one(req)
            tokens = self._saved_tokens[req.rid]
            if not self._alloc_with_preemption(req, tokens,
                                               below_priority=req.priority):
                return False
            # Allocation under pressure may have planned compaction:
            # execute the copies before anything reads the rewritten tables.
            self._run_compaction()
            self.cache.demote_host_backed(req.rid, self.host)
            del self._saved_tokens[req.rid]
            self.host.note_swap_in()
            self.stats.swaps_in += 1
            if req.preempt_ns is not None:
                self.stats.resume_wait_ns.append(
                    time.perf_counter_ns() - req.preempt_ns)
                req.preempt_ns = None
            return True

    def _forget_request(self, r: Request) -> None:
        """Erase every trace of a request whose saved payloads were lost
        to a spill quarantine (§12) so it can restart from the prompt:
        device pages, decode state, host copies, staged and in-flight
        prefetches, saved token count, and any tokens already emitted."""
        self.cache.free(r.rid)
        self.states.pop(r.rid, None)
        self.host.drop_seq(r.rid)
        self.host.take_lost(r.rid)   # clear a flag re-set during the drop
        self._note_prestage_waste(r.rid)
        dropped = self.staging.invalidate_seq(r.rid)
        self.stats.prefetch_wasted += dropped
        self.prefetch.stats["wasted_pages"] += dropped
        self.prefetch.cancel_seq(r.rid)
        self._saved_tokens.pop(r.rid, None)
        r.preempt_ns = None
        r.out.clear()
        r.done = False

    def _restart_lost(self, rids: set) -> None:
        """Pull active requests whose payloads a quarantine destroyed
        out of the batch and re-queue them from the prompt (head of the
        queue: they are the oldest work).  Deterministic decode makes
        the replay byte-identical to an unfaulted run."""
        for r in [r for r in self.active if r.rid in rids]:
            self.active.remove(r)
            self._forget_request(r)
            self.queue.appendleft(r)
            self.stats.lost_restarts += 1

    def _prompt_tokens(self, req: Request) -> int:
        """Tokens a fresh admission of ``req`` allocates: its prompt,
        behind a VLM's frontend tokens."""
        n_prefix = (self.cfg.frontend_tokens
                    if self.cfg.family == "vlm" else 0)
        return n_prefix + len(req.prompt)

    def _admit_one(self, req: Request) -> bool:
        if not self._alloc_with_preemption(req, self._prompt_tokens(req),
                                           below_priority=req.priority):
            return False
        self._prefill(req)
        return True

    # --------------------------------------------------- demand fault-in

    @spanned("tier.fault_in")
    def _fault_in(self, seqs: List[int]) -> set:
        """touch() this step's pages; fault the missing ones in (blocking
        under ``fault_mode="sync"``, staged/overlapped under ``"async"``).
        Returns the rids whose payloads were lost to a spill quarantine
        during the promote (§12) — their entries were skipped and the
        caller must restart them."""
        if self.fault_mode == "sync":
            return self._fault_in_sync(seqs)
        if self.fault_mode == "fused":
            return self._fault_in_fused(seqs)
        return self._fault_in_async(seqs)

    @staticmethod
    def _drop_lost_entries(missing: Dict, lost: set) -> Dict:
        """Remove a lost rid's entries from a missing-pages map — their
        host payloads no longer exist, so they must not be popped."""
        if not lost:
            return missing
        return {s: kept for s, entries in missing.items()
                if (kept := [e for e in entries if e[1] not in lost])}

    def _promote_missing(self, missing: Dict) -> set:
        """Before popping payloads, promote any spilled frames the step's
        misses live in (DESIGN.md §11) — the modeled disk-read stall is
        exposed time, charged to the clock like a demand fault.

        Returns the rids whose payloads the promote *destroyed* (frame
        quarantine after corruption or a permanent disk error, §12):
        the caller must skip their entries and restart them."""
        keys = [(owner, s, vpn) for s, entries in missing.items()
                for _ppn, owner, vpn in entries]
        promote_us = self.host.ensure_resident(keys, now_us=self._clock_us)
        if promote_us:
            self._clock_us += promote_us
            self.stats.promote_stall_us += promote_us
            self.stats.promotions += 1
        return {k[0] for k in keys
                if k[0] >= 0 and self.host.take_lost(k[0])}

    def _scatter_pages(self, gidx: List[int],
                       payloads: List[Tuple[np.ndarray, np.ndarray]]
                       ) -> None:
        """Land faulted payloads in the device pools (one batched launch)."""
        if self.pools is None or not gidx:
            return
        idx = self._dev(np.asarray(gidx, np.int32))
        kp = self._dev(np.stack([p[0] for p in payloads], axis=1))
        vp = self._dev(np.stack([p[1] for p in payloads], axis=1))
        k, v = self.pools
        k = jax.vmap(lambda pool, pages: kops.page_scatter(
            pool, idx, pages, use_pallas=self.use_pallas))(k, kp)
        v = jax.vmap(lambda pool, pages: kops.page_scatter(
            pool, idx, pages, use_pallas=self.use_pallas))(v, vp)
        self.pools = (k, v)

    def _fault_in_sync(self, seqs: List[int]) -> set:
        """PR 1's blocking path: the whole batch stalls on the transfer,
        so every µs is exposed."""
        missing = self.cache.missing_pages(seqs)
        if not missing:
            return set()
        lost = self._promote_missing(missing)
        missing = self._drop_lost_entries(missing, lost)
        if not missing:
            return lost
        pps = self.cache.pages_per_shard
        gidx: List[int] = []
        payloads: List[Tuple[np.ndarray, np.ndarray]] = []
        step_us = 0.0
        for s, entries in missing.items():
            batch = self.cache.mgrs[s].residency.fault_in(
                [ppn for ppn, _o, _v in entries])
            self.stats.faults += len(batch.ppns)
            self.stats.fault_dmas += batch.dma_count
            self.stats.bytes_in += batch.nbytes
            self.stats.transfer_us += batch.transfer_us
            self.stats.fault_exposed_us += batch.transfer_us
            step_us += batch.transfer_us
            for ppn, owner, vpn in entries:
                gidx.append(s * pps + ppn)
                payloads.append(self.host.pop(owner, s, vpn))
        self.stats.fault_steps += 1
        self._clock_us += step_us       # the whole transfer stalls the step
        self._scatter_pages(gidx, payloads)
        return lost

    def _fault_in_async(self, seqs: List[int]) -> set:
        """Stage 1 of the pipeline: serve this step's misses from the
        staging region (hidden), stall on in-flight prefetches (partially
        hidden), and demand-fault only the never-predicted remainder
        (fully exposed, and queued behind in-flight prefetch DMAs —
        shared-channel contention is part of the model)."""
        missing = self.cache.missing_pages(seqs)
        if not missing:
            return set()
        lost = self._promote_missing(missing)
        missing = self._drop_lost_entries(missing, lost)
        pps = self.cache.pages_per_shard
        now = self._clock_us
        gidx: List[int] = []
        payloads: List[Tuple[np.ndarray, np.ndarray]] = []
        waited: Dict[Tuple[int, int, int],
                     Tuple[np.ndarray, np.ndarray]] = {}
        for s, entries in sorted(missing.items()):
            demand: List[Tuple[int, int, int]] = []
            for ppn, owner, vpn in entries:
                key = (owner, s, vpn)
                payload = waited.pop(key, None)
                if payload is None:
                    payload = self.staging.consume(key)
                if payload is None and key in self.prefetch.in_flight:
                    # Partially-hidden: the transfer started during the
                    # previous decode; stall only for the remainder.
                    job = self.prefetch.in_flight[key]
                    now = self.dma.wait(job, now)
                    self.prefetch.forget(job.keys)
                    for k2, p2 in zip(job.keys, job.payloads):
                        waited[k2] = p2
                    payload = waited.pop(key)
                if payload is None:
                    demand.append((ppn, owner, vpn))
                    continue
                # Prefetch hit: payload already on device (staging);
                # scatter it to its mapped frame and retire the host copy.
                self.cache.mgrs[s].residency.mark_resident([ppn])
                self.host.pop(owner, s, vpn)
                self.stats.faults += 1
                self.stats.prefetch_hits += 1
                self._note_prestage_hit(key)
                self.prefetch.stats["hits"] += 1
                gidx.append(s * pps + ppn)
                payloads.append(payload)
            if demand:
                batch = self.cache.mgrs[s].residency.fault_in(
                    [ppn for ppn, _o, _v in demand])
                dpay = [self.host.pop(owner, s, vpn)
                        for _ppn, owner, vpn in demand]
                job = self.dma.enqueue(
                    [(owner, s, vpn) for _p, owner, vpn in demand],
                    [ppn for ppn, _o, _v in demand],
                    self.cache.mgrs[s].residency.page_bytes, dpay,
                    now, kind="demand")
                now = self.dma.wait(job, now)
                self.stats.faults += len(demand)
                self.stats.fault_dmas += job.dma_count
                self.stats.bytes_in += job.nbytes
                self.stats.transfer_us += job.transfer_us
                self.stats.prefetch_misses += len(demand)
                self.prefetch.stats["misses"] += len(demand)
                for (ppn, _o, _v), p in zip(demand, dpay):
                    gidx.append(s * pps + ppn)
                    payloads.append(p)
        # Leftover payloads of a waited multi-page job: keep for later
        # steps (their keys weren't in this step's touch set); a key
        # whose owner retired (or migrated away) mid-flight is wasted
        # transfer.
        for key, payload in waited.items():
            if self.host.has(*key) and key[0] not in self._foreign:
                self.staging.stage(key, payload)
            else:
                self.prefetch.stats["wasted_pages"] += 1
                self.stats.prefetch_wasted += 1
        self.stats.fault_steps += 1
        # Engine-level exposed = the step's stall (includes channel-queue
        # wait); the DMA engine keeps the strict per-transfer split.
        self.stats.fault_exposed_us += now - self._clock_us
        self.stats.fault_hidden_us = self.dma.stats["hidden_us"]
        self._clock_us = now
        self._scatter_pages(gidx, payloads)
        return lost

    # ------------------------------------------------ fused decode path

    def _write_page_set(self, seqs: List[int]) -> set:
        """(shard, ppn) of each sequence's current write page: the page
        ``write_kv`` lands the new token in.  A staged write page must be
        merged into the pool *before* decode — attention would otherwise
        read the pre-write staged bytes and miss the new token."""
        ftok = self.geo.frame_pages * self.geo.page_tokens
        out = set()
        for seq in seqs:
            pos = self.cache.seq_tokens[seq] - 1
            s = self.cache._shard_of_frame(pos // ftok)
            table = self.cache.mgrs[s].tables[seq]
            out.add((s, table.ppn[len(table.ppn) - 1]))
        return out

    def _fault_in_fused(self, seqs: List[int]) -> set:
        """Fused gather-attend path (DESIGN.md §13): no pre-decode DMA
        barrier.  This step's misses are resolved to *sources* — staged
        payloads (consumed in-kernel from the staging region), in-flight
        prefetch jobs, and freshly enqueued demand jobs — but the engine
        never calls ``dma.wait`` here.  Decode launches immediately with
        a per-page readiness mask; the collected jobs settle at the end
        of the decode window (:meth:`_settle_fused`), exposing only the
        transfer tail that outlives the window."""
        self._fused_jobs = []
        self._fused_staged = []
        self._fused_t0 = self._clock_us
        missing = self.cache.missing_pages(seqs)
        if not missing:
            return set()
        lost = self._promote_missing(missing)       # disk stall stays exposed
        missing = self._drop_lost_entries(missing, lost)
        self._fused_t0 = self._clock_us
        now = self._clock_us
        pps = self.cache.pages_per_shard
        write_pages = self._write_page_set(
            [s for s in seqs if s in self.cache.seq_tokens])
        jobs: Dict[int, DMAJob] = {}
        waited: Dict[Tuple[int, int, int],
                     Tuple[np.ndarray, np.ndarray]] = {}
        arrive: Dict[Tuple[int, int, int], float] = {}
        for s, entries in sorted(missing.items()):
            demand: List[Tuple[int, int, int]] = []
            for ppn, owner, vpn in entries:
                key = (owner, s, vpn)
                when = now          # staging hits: landed before this step
                payload = waited.pop(key, None)
                if payload is not None:
                    when = arrive.get(key, now)
                if payload is None:
                    payload = self.staging.consume(key)
                if payload is None and key in self.prefetch.in_flight:
                    # In flight: consume in-kernel, do NOT stall — record
                    # the page's modeled arrival on the µs timeline.
                    job = self.prefetch.in_flight[key]
                    jobs[job.job_id] = job
                    self.prefetch.forget(job.keys)
                    for i2, (k2, p2) in enumerate(
                            zip(job.keys, job.payloads)):
                        waited[k2] = p2
                        arrive[k2] = job.page_done_us(i2)
                    payload = waited.pop(key)
                    when = arrive[key]
                if payload is None:
                    demand.append((ppn, owner, vpn))
                    continue
                self.cache.mgrs[s].residency.mark_resident([ppn])
                self.host.pop(owner, s, vpn)
                self.stats.faults += 1
                self.stats.prefetch_hits += 1
                self._note_prestage_hit(key)
                self.prefetch.stats["hits"] += 1
                self._fused_staged.append(((s, ppn), payload, when))
            if demand:
                self.cache.mgrs[s].residency.fault_in(
                    [ppn for ppn, _o, _v in demand])
                dpay = [self.host.pop(owner, s, vpn)
                        for _ppn, owner, vpn in demand]
                job = self.dma.enqueue(
                    [(owner, s, vpn) for _p, owner, vpn in demand],
                    [ppn for ppn, _o, _v in demand],
                    self.cache.mgrs[s].residency.page_bytes, dpay,
                    now, kind="demand")
                jobs[job.job_id] = job
                self.stats.faults += len(demand)
                self.stats.fault_dmas += job.dma_count
                self.stats.bytes_in += job.nbytes
                self.stats.transfer_us += job.transfer_us
                self.stats.prefetch_misses += len(demand)
                self.prefetch.stats["misses"] += len(demand)
                for i2, ((ppn, _o, _v), p) in enumerate(zip(demand, dpay)):
                    self._fused_staged.append(
                        ((s, ppn), p, job.page_done_us(i2)))
        for key, payload in waited.items():
            if self.host.has(*key) and key[0] not in self._foreign:
                self.staging.stage(key, payload)
            else:
                self.prefetch.stats["wasted_pages"] += 1
                self.stats.prefetch_wasted += 1
        self._fused_jobs = sorted(jobs.values(), key=lambda j: j.job_id)
        # The write page is merged at consumption time (it is mutated by
        # this step's token write); everything else stays in staging for
        # the kernel.  Its job still settles at the window end.
        pre = [(sp, pl) for sp, pl, _t in self._fused_staged
               if sp in write_pages]
        if pre:
            self._scatter_pages([s * pps + p for (s, p), _pl in pre],
                                [pl for _sp, pl in pre])
            self._fused_staged = [e for e in self._fused_staged
                                  if e[0] not in write_pages]
        self.stats.fault_steps += 1
        return lost

    def _attach_staging(self, ctx):
        """Expose this step's staged arrivals to the decode kernel
        (DESIGN.md §13): a dense step-local stage pool [L, NS, ptok,
        n_kv, dh{,_v}] plus a slot table mirroring ``ctx.tables``
        (-1 = pool-resident).  NS is padded to a power of two to bound
        jit retraces across steps with different arrival counts."""
        if not self._fused_staged or self.pools is None:
            return ctx
        pps = self.cache.pages_per_shard
        gid = {s * pps + ppn: i
               for i, ((s, ppn), _pl, _t) in enumerate(self._fused_staged)}
        tables = ctx.tables
        slots = np.full(tables.shape, -1, np.int32)
        for g, i in gid.items():
            slots[tables == g] = i
        kp = np.stack([pl[0] for _sp, pl, _t in self._fused_staged], axis=1)
        vp = np.stack([pl[1] for _sp, pl, _t in self._fused_staged], axis=1)
        ns = 1 << (kp.shape[1] - 1).bit_length()
        if ns > kp.shape[1]:
            kp = np.concatenate(
                [kp, np.zeros((kp.shape[0], ns - kp.shape[1],
                               *kp.shape[2:]), kp.dtype)], axis=1)
            vp = np.concatenate(
                [vp, np.zeros((vp.shape[0], ns - vp.shape[1],
                               *vp.shape[2:]), vp.dtype)], axis=1)
        return dataclasses.replace(ctx, slots=slots, stage_k=kp, stage_v=vp)

    def _settle_fused(self) -> None:
        """Post-decode sync point (DESIGN.md §13): the kernel drained
        every staged page during the decode window, so the collected
        jobs settle against the window *end* — transfer µs inside the
        window are hidden, only tails past it are exposed.  The staged
        payloads are then scattered so the device pool is authoritative
        again before parking/preemption gathers run (same data-only
        timing model as every `_scatter_pages` landing)."""
        t_end = self._clock_us
        now = t_end
        for job in self._fused_jobs:
            now = max(now, self.dma.wait(job, t_end))
        if self._fused_jobs:
            self.stats.fault_exposed_us += now - t_end
            self.stats.fused_tail_us += now - t_end
            self._clock_us = now
        self.stats.fault_hidden_us = self.dma.stats["hidden_us"]
        self._fused_jobs = []
        if self._fused_staged:
            t0 = self._fused_t0
            ready = sum(1 for _sp, _pl, t in self._fused_staged if t <= t0)
            self.stats.fused_ready_pages += ready
            self.stats.fused_drained_pages += \
                len(self._fused_staged) - ready
            pps = self.cache.pages_per_shard
            self._scatter_pages(
                [s * pps + p for (s, p), _pl, _t in self._fused_staged],
                [pl for _sp, pl, _t in self._fused_staged])
            self._fused_staged = []

    # --------------------------------------------- async prefetch pipeline

    @spanned("tier.prefetch")
    def _drain_prefetches(self) -> None:
        """Step start: publish the transfers that completed during the
        previous decode into the staging front buffer (double-buffer
        swap; see StagingBuffer ownership rules)."""
        for job in self.dma.drain(self._clock_us):
            if job.direction == "out":
                continue    # outbound gathers: settled by drain, no staging
            self.prefetch.forget(job.keys)
            for key, payload in zip(job.keys, job.payloads):
                # Pre-staged keys (DESIGN.md §14) stage under the rid
                # *before* admission registers the host copy, so they
                # pass on _prestage_keys membership, not host.has.
                if (self.host.has(*key) or key in self._prestage_keys) \
                        and key[0] not in self._foreign:
                    self.staging.stage(key, payload)
                else:   # owner retired/migrated while the DMA was in flight
                    self.prefetch.stats["wasted_pages"] += 1
                    self.stats.prefetch_wasted += 1
        self.staging.swap()
        self.stats.fault_hidden_us = self.dma.stats["hidden_us"]

    def _slack(self, r: Request) -> Optional[float]:
        """Deadline slack on the modeled clock (None = no deadline)."""
        if r.deadline_us is None:
            return None
        return r.deadline_us - self._clock_us

    def _slack_or_inf(self, r: Request) -> float:
        s = self._slack(r)
        return float("inf") if s is None else s

    def _resume_candidates(self) -> List[Request]:
        """Resume candidates in the order _admit will consider them:
        highest priority first; within a tier tightest deadline slack
        first, deadline-free requests FIFO last (stable sort)."""
        return sorted(self.preempted,
                      key=lambda r: (-r.priority, self._slack_or_inf(r)))

    def _resume_order(self) -> List[int]:
        return [r.rid for r in self._resume_candidates()]

    @spanned("tier.prefetch")
    def _issue_prefetch(self) -> None:
        """Step end (just before decode): issue the predicted next-step
        touches to the DMA channels so they transfer while we compute.
        The resume-prefetch window is SLO-aware (DESIGN.md §8): the
        deadline pressure of the resume queue widens ``Prefetcher.depth``
        so urgent resumes have their pages staged in time."""
        resume = self._resume_candidates()
        depth = self.prefetch.plan_depth(
            [self._slack(r) for r in resume], self.slo_urgency_us)
        preds = self.prefetch.predict(
            self.cache, self.host, [r.rid for r in self.active],
            [r.rid for r in resume], depth=depth)
        by_shard: Dict[int, List[Tuple[Tuple[int, int, int], int]]] = {}
        by_seq: Dict[int, List[Tuple[int, int, int]]] = {}
        for key, ppn in preds:
            if self.staging.contains(key) or key in self.prefetch.in_flight:
                continue        # already staged or on a channel
            if not self.host.has(*key):
                continue
            if ppn is not None:
                by_shard.setdefault(key[1], []).append((key, ppn))
            else:
                by_seq.setdefault(key[0], []).append(key)
        page_bytes = self.page_bytes or self.cache.mgrs[0].residency.page_bytes
        jobs = []
        for s, group in sorted(by_shard.items()):
            # Mapped targets: real ppns drive the contiguous-run cost.
            jobs.append(self.dma.enqueue(
                [k for k, _p in group], [p for _k, p in group], page_bytes,
                [self.host.peek(*k) for k, _p in group],
                self._clock_us, kind="prefetch"))
        for rid, keys in sorted(by_seq.items()):
            # Resume candidates have no frames yet: the transfer gathers
            # into contiguous staging slots, so it merges into one DMA.
            jobs.append(self.dma.enqueue(
                keys, list(range(len(keys))), page_bytes,
                [self.host.peek(*k) for k in keys],
                self._clock_us, kind="prefetch"))
        for job in jobs:
            self._account_prefetch(job)

    def _account_prefetch(self, job) -> None:
        """Register an issued inbound prefetch job: in-flight tracking +
        the engine-side transfer accounting (one site for both the
        per-step predictor and admission-time prefix prefetches)."""
        for key in job.keys:
            self.prefetch.in_flight[key] = job
        self.prefetch.stats["issued_pages"] += len(job.keys)
        self.stats.fault_dmas += job.dma_count
        self.stats.bytes_in += job.nbytes
        self.stats.transfer_us += job.transfer_us

    def _match_prefix(self, req: Request):
        """Longest cached page-aligned prefix usable for this admission.

        Capped one page short of the prompt when the whole prompt is
        cached: the engine always prefills ≥ 1 real token, so the first
        output token comes from live computation (byte-identical to the
        cache-off run by construction — suffix prefill reproduces full
        prefill bitwise; see tests/test_prefix_cache.py).

        A shared (cluster) index attached to a non-dense replica never
        matches: this engine could not replay the cached KV."""
        if self.prefix is None or not self.prefix_supported:
            return None
        ptok = self.geo.page_tokens
        T = len(req.prompt)
        n, pages = self.prefix.match(req.prompt)
        n = min(n, (T - 1) // ptok)
        if n <= 0:
            self.stats.prefix_misses += 1
            return None
        self.stats.prefix_hits += 1
        return pages[:n]

    def _prefill(self, req: Request):
        """Run prefill for an already-allocated request (see _admit_one):
        suffix-only when a cached prefix matches, full otherwise.  Each
        admission also records a *modeled* latency sample (admit_lat_us):
        tokens actually prefilled × prefill_us_per_token, plus the disk
        promote stall a cache hit paid to bring spilled prefix frames
        back (DESIGN.md §11) — the wall-µs counters stay, but on CPU
        they measure jit time, not the serving effect."""
        T = len(req.prompt)
        with Span(self.stats, "engine.prefill", rid=req.rid) as sp:
            if req.submit_ns is not None:
                # A first admission: its wait in the queue ends here.
                self.stats.queue_wait_ns.append(sp.t0 - req.submit_ns)
                req.submit_ns = None
            match = self._match_prefix(req)
            promote_us = self._prefill_suffix(req, match) if match else None
            if promote_us is None:
                if match:
                    # The matched payloads were quarantined mid-admission
                    # (§12): fall back to full prefill — the prefix will be
                    # re-derived (re-parked) when this request completes.
                    self.stats.prefix_rederives += 1
                self._prefill_full(req)
            sp.set(hit=promote_us is not None)
        if promote_us is not None:
            self.stats.admit_hits += 1
            self.stats.admit_hit_us += sp.ns / 1e3
            model_us = (T - len(match) * self.geo.page_tokens) \
                * self.prefill_us_per_token + promote_us
        else:
            self.stats.admit_colds += 1
            self.stats.admit_cold_us += sp.ns / 1e3
            model_us = T * self.prefill_us_per_token
        self.stats.admit_lat_us.append(model_us)

    def _prefill_suffix(self, req: Request, pages) -> Optional[float]:
        """Cache-hit admission (DESIGN.md §8): restore the matched prefix
        pages through the host tier instead of recomputing them, and
        forward only the suffix (queries attend over the cached KV).

        The matched pages' payloads are (1) registered in the host store
        under this request — the index's own copies stay, shared and
        unpopped — (2) their freshly-allocated frames demoted to
        non-resident, and (3) prefetched through the DMA pipeline *now*,
        at admission, so the transfer overlaps whatever runs before the
        first decode step touches them.

        Promote-on-admission (DESIGN.md §11): matched pages whose frames
        were spilled to disk are promoted back *before* the payload
        reads, and the modeled disk stall — returned to the caller —
        advances the engine clock and the admission latency sample.
        Spill on/off changes only this timing, never the payload bytes,
        so tokens stay byte-identical.

        Returns None — *before* any request-visible side effect — when
        the promote quarantined a matched page's payload (§12): the
        caller falls back to full prefill and re-derives the prefix."""
        ptok = self.geo.page_tokens
        T = len(req.prompt)
        P = len(pages) * ptok
        self._run_compaction()
        promote_us = self.host.ensure_resident(
            [(pg.owner, pg.shard, pg.vpn) for pg in pages],
            now_us=self._clock_us)
        if any(not self.host.has(pg.owner, pg.shard, pg.vpn)
               for pg in pages):
            return None
        if promote_us:
            self._clock_us += promote_us
            self.stats.promote_stall_us += promote_us
            self.stats.promotions += 1
        payloads = [self.prefix.payload(pg) for pg in pages]
        locs = [(pg.shard, pg.vpn) for pg in pages]
        for (s, vpn), (kp, vp) in zip(locs, payloads):
            self.host.put(req.rid, s, vpn, kp, vp, kind="reuse")
        entries = self.cache.demote_prefix_pages(req.rid, locs)
        self.prefix.stats["reused_tokens"] += P
        # [L, B=1, P, kv, dh] stacked prefix KV for the layer scan.
        pk = np.stack([p[0] for p in payloads], axis=1)
        pv = np.stack([p[1] for p in payloads], axis=1)
        L = pk.shape[0]
        pk = pk.reshape(L, 1, P, *pk.shape[3:])
        pv = pv.reshape(L, 1, P, *pv.shape[3:])
        Tpad = ((T + ptok - 1) // ptok) * ptok
        tokens = np.full((1, Tpad - P), 0, np.int32)
        tokens[0, :T - P] = req.prompt[P:]
        ctx = self._ctx_global(self.cache.pack_ctx([req.rid], self.mpps))
        logits, self.pools, state = self._prefill_jit(
            self.params, self._dev({"tokens": tokens}), self.pools,
            self._dev(ctx),
            last_pos=self._dev(np.asarray([T - 1 - P], np.int32)),
            prefix_kv=self._dev((pk, pv)), prefix_len=P)
        self.states[req.rid] = state
        with Span(self.stats, "device.wait"):
            req.out.append(int(jnp.argmax(logits[0])))
        self.stats.prefill_tokens += T - P      # compute actually done
        self.stats.prefix_reused_tokens += P
        # Admission-time fault-in through the async pipeline: the first
        # decode step that touches these pages finds them in flight (or
        # already staged) instead of paying a cold demand fault.
        if self.fault_mode in ("async", "fused"):
            # Pre-staged keys whose source owner no longer matches are
            # stale — the index churned between the router's probe and
            # this admission and re-parked different bytes at the same
            # (shard, vpn).  Cancel the whole rid's pre-stage before the
            # dedup pass: byte identity beats saving a transfer.
            if any(self._prestage_keys.get((req.rid, s, vpn),
                                           pages[i].owner) != pages[i].owner
                   for i, (s, vpn, _ppn) in enumerate(entries)):
                self.cancel_prestage(req.rid)
            by_shard: Dict[int, List[int]] = {}
            for i, (s, vpn, _ppn) in enumerate(entries):
                key = (req.rid, s, vpn)
                if key in self._prestage_keys and (
                        self.staging.contains(key)
                        or key in self.prefetch.in_flight):
                    # Pre-staged toward this engine while the request
                    # was still queued (DESIGN.md §14): the identical
                    # payload is already staged or in flight under this
                    # exact key — issuing (and charging) the transfer
                    # again would double-book the lane.
                    self._note_prestage_hit(key)
                    continue
                by_shard.setdefault(s, []).append(i)
            for s, idxs in sorted(by_shard.items()):
                job = self.dma.enqueue(
                    [(req.rid, entries[i][0], entries[i][1]) for i in idxs],
                    [entries[i][2] for i in idxs],
                    self.cache.mgrs[s].residency.page_bytes,
                    [payloads[i] for i in idxs],
                    self._clock_us, kind="prefetch")
                self._account_prefetch(job)
                self.stats.prefix_fault_us += job.transfer_us
        return promote_us

    @spanned("tier.park")
    def _park_prefix(self, req: Request) -> None:
        """Completion hook (DESIGN.md §8): park the finished request's
        full prompt pages in the prefix index so future admissions
        sharing the prefix fault them in instead of re-decoding.

        Only the chain suffix the index is missing is parked (chained
        hashes dedupe shared prefixes for free).  Payloads come from the
        device pool (resident pages — one batched gather that rides the
        outbound DMA lanes) or from the request's own host copies (pages
        still swapped out); a page with neither truncates the chain,
        keeping the index prefix-closed.

        Non-dense fallback (DESIGN.md §10): a model family whose KV a
        suffix prefill cannot replay bitwise (MoE capacity routing, MLA
        latents, recurrent state) must not park — a cluster-shared index
        would hand those pages to dense replicas as unreplayable KV.
        The park is skipped and counted instead."""
        if self.prefix is None:
            return
        if not self.prefix_supported or self.pools is None:
            self.stats.prefix_park_skipped += 1
            return
        if not self.host.park_allowed():
            # §11 back-pressure: the host tier's write-back buffer is
            # saturated — parking more cold pages would queue unbounded
            # dirty data in front of the disk.  Refuse (and count) the
            # park; the prefix is simply not cached this time, which is
            # always token-safe.
            self.stats.prefix_park_refused += 1
            return
        hashes = self.prefix.chain_hashes(req.prompt)
        start = self.prefix.missing_from(hashes)
        if start >= len(hashes):
            return
        # Pending compaction plans rewrote tables; land the copies before
        # gathering through them (same rule as _preempt).
        self._run_compaction()
        rid = req.rid
        to_park: List[Tuple[int, int, int, Optional[Tuple]]] = []
        gather_entries: List[Tuple[int, int, int]] = []
        for gp in range(start, len(hashes)):
            s, vpn = self.cache.locate_page(gp)
            mgr = self.cache.mgrs[s]
            if rid not in mgr.tables or vpn >= len(mgr.tables[rid].ppn):
                break
            ppn = mgr.tables[rid].ppn[vpn]
            if ppn >= 0 and mgr.residency.resident[ppn]:
                gather_entries.append((s, vpn, ppn))
                to_park.append((gp, s, vpn, None))
            elif self.host.has(rid, s, vpn):
                to_park.append((gp, s, vpn, self.host.peek(rid, s, vpn)))
            else:
                break
        if not to_park:
            return
        gathered = self._gather_pages(gather_entries)
        git = iter(gathered)
        out_keys: List[Tuple[int, int, int]] = []
        parent = hashes[start - 1] if start else None
        for gp, s, vpn, payload in to_park:
            from_device = payload is None
            if from_device:
                payload = next(git)
            page = self.prefix.park(hashes[gp], parent, gp, s, vpn,
                                    *payload)
            if from_device:
                out_keys.append((page.owner, s, vpn))
            parent = hashes[gp]
        self.stats.prefix_parked_pages += len(to_park)
        # The device gather is outbound traffic on the duplex channels.
        self._enqueue_outbound(out_keys, gather_entries, gathered,
                               kind="park")

    def _prefill_full(self, req: Request):
        """Cold admission: full-prompt forward (PR 2's only path)."""
        ptok = self.geo.page_tokens
        T = len(req.prompt)
        Tpad = ((T + ptok - 1) // ptok) * ptok
        # Allocation under memory pressure may have compacted: the tables
        # already point at the new locations, so the data copies must land
        # BEFORE the device reads them (and before the pages freed by
        # compaction are overwritten by this prefill).
        self._run_compaction()
        ctx = self._ctx_global(self.cache.pack_ctx([req.rid], self.mpps))
        tokens = np.full((1, Tpad), 0, np.int32)
        tokens[0, :T] = req.prompt
        batch = {"tokens": tokens}
        if self.cfg.family == "vlm":
            batch["patch_embeds"] = np.zeros(
                (1, self.cfg.frontend_tokens, self.cfg.d_model), np.float32)
        if self.cfg.family == "encdec":
            batch["src_embeds"] = np.zeros(
                (1, self.cfg.encdec.source_len, self.cfg.d_model),
                np.float32)
        logits, self.pools, state = self._prefill_jit(
            self.params, self._dev(batch), self.pools, self._dev(ctx),
            last_pos=self._dev(np.asarray([T - 1], np.int32)))
        self.states[req.rid] = state
        with Span(self.stats, "device.wait"):
            nxt = int(jnp.argmax(logits[0]))
        req.out.append(nxt)
        # tokens beyond T within the padded page are unused; tracked length
        # stays T (+1 for the decode append below).
        self.stats.prefill_tokens += T

    # ------------------------------------------------------------- pools

    # Pools are global arrays addressed by global page id =
    # shard * pages_per_shard + local id; pack_ctx returns local ids, so we
    # offset per shard here (host-side, before the step's device put).
    def _ctx_global(self, ctx):
        """Convert per-shard local page ids to global pool ids."""
        off = np.arange(self.cache.S) * self.cache.pages_per_shard
        tables = np.where(ctx.tables >= 0, ctx.tables + off[None, :, None],
                          -1).astype(np.int32)
        wpage = np.where(ctx.wpage >= 0, ctx.wpage + off[None, :],
                         -1).astype(np.int32)
        return dataclasses.replace(ctx, tables=tables, wpage=wpage)

    # ------------------------------------------------------------- stepping

    @spanned("mm.grow")
    def _append_with_preemption(self) -> List[Request]:
        """Grow active requests by one token slot, highest priority first.

        Under pool pressure a request may displace peers of its own tier or
        below (never a higher-priority one); with no displaceable victim it
        *stalls* — keeps its pages but sits this step out.  If nobody can
        grow, the lowest-priority request is forcibly swapped out so the
        rest make progress next step.  Returns this step's decode batch.
        """
        order = sorted(self.active, key=lambda r: -r.priority)  # stable
        appended: List[Request] = []
        for r in order:
            if r not in self.active:
                continue            # preempted as someone else's victim
            while r in self.active:
                try:
                    self.cache.append(r.rid, 1)
                    appended.append(r)
                    break
                except OutOfMemory:
                    victim = self._pick_victim(
                        below_priority=r.priority + 1,
                        exclude=tuple(a.rid for a in appended) + (r.rid,))
                    if victim is None:
                        break       # stall: retry next step
                    self._preempt(victim)
        if not appended and self.active:
            victim = self._pick_victim()
            if victim is not None and len(self.active) > 1:
                self._preempt(victim)
        return [r for r in self.active if r in appended]

    def step(self):
        """One engine iteration as a two-stage pipeline: drain completed
        prefetches → admit → fault remaining misses (exposed) → decode
        while the next step's prefetch is in flight → retire.  The whole
        iteration is the ``engine.step`` span; ``wall_s`` is their total."""
        if not self.alive:
            return False        # a crashed engine does no work (§12)
        st = self.stats
        with Span(st, "engine.step", step=st.span_n.get("engine.step", 0)):
            busy = self._step()
        st.wall_s = st.span_ns["engine.step"] / 1e9
        return busy

    def _step(self) -> bool:
        # Advance the host tier's write-back pipeline to the engine clock
        # (DESIGN.md §11): frames whose spill completed during previous
        # steps persist now, freeing write-back queue slots before this
        # step's admissions and parks consult park_allowed().
        self.host.pump(self._clock_us)
        if self.fault_mode in ("async", "fused"):
            # Stage 0: publish transfers that finished during the last
            # decode (double-buffer swap) so admission's resumes and this
            # step's fault-in see them as hits.
            self._drain_prefetches()
        self._admit()
        if not self.active:
            return False
        # Append this step's token slot, then pack tables.
        runnable = self._append_with_preemption()
        if not runnable:
            # An occasional all-stalled step is normal under pressure
            # (capacity frees as others complete), but a *permanent* stall
            # means some request can never grow — fail loudly rather than
            # spinning run_until_drained to its step cap.
            self._stalled_steps += 1
            if self._stalled_steps > 64:
                raise OutOfMemory(
                    f"engine stalled {self._stalled_steps} consecutive "
                    f"steps: active requests "
                    f"{sorted(r.rid for r in self.active)} cannot grow "
                    f"(pool too small or fragmentation unrecoverable)")
            # Stalled steps still did real work (admission attempts, forced
            # preemption gathers) — their engine.step spans stay in the
            # tok/s denominator.
            return bool(self.active or self.queue or self.preempted)
        self._stalled_steps = 0
        seqs = [r.rid for r in runnable]
        # Appends under pressure may compact; execute the copy plan before
        # the decode step consumes the updated tables (ordering matters:
        # tables are rewritten at plan time, payloads move here).
        self._run_compaction()
        # touch() the pages this step's packed tables will read and
        # batch-fault the missing ones in from the host tier.
        lost = self._fault_in(seqs)
        if lost:
            # A spill quarantine destroyed some batch members' payloads
            # mid-promote (§12): restart them from the prompt and decode
            # the survivors this step.
            self._restart_lost(lost)
            runnable = [r for r in runnable if r.rid not in lost]
            seqs = [r.rid for r in runnable]
            if not runnable:
                if self.fault_mode == "fused":
                    # No decode window opens: settle collected jobs and
                    # land staged payloads at the current clock.
                    self._settle_fused()
                return bool(self.active or self.queue or self.preempted)
        # Decode always runs max_batch rows; idle rows read no page and
        # write nowhere.  One compiled step, and each row's numerics stay
        # independent of which requests share the step: on the TPU a
        # matmul's rounding can depend on its row count, which would let
        # the memory manager's scheduling change the tokens.
        rows = self.max_batch
        with Span(self.stats, "engine.pack"):
            ctx = self._ctx_global(self.cache.pack_ctx(seqs, self.mpps,
                                                       rows=rows))
            if self.fault_mode == "fused":
                # Start-decode-on-resident: hand the kernel this step's
                # staged arrivals + readiness mask instead of stalling for
                # them (DESIGN.md §13).
                ctx = self._attach_staging(ctx)
            toks = np.zeros(rows, np.int32)
            pos = np.zeros(rows, np.int32)
            for i, r in enumerate(runnable):
                toks[i] = r.out[-1]
                pos[i] = self.cache.seq_tokens[r.rid] - 1
            state = self._stack_states(seqs, rows)
        if self.fault_mode in ("async", "fused"):
            # Stage 2: predicted next-step touches ride the DMA channels
            # while the decode below computes — their µs become hidden.
            self._issue_prefetch()
        t_dec = time.perf_counter()
        with Span(self.stats, "model.decode"):
            logits, self.pools, state = self._decode_jit(
                self.params, self._dev(toks), self._dev(pos), self.pools,
                self._dev(ctx), state)
        with Span(self.stats, "device.wait"):
            nxt = np.asarray(jnp.argmax(logits, axis=-1))
        # The decode step is the compute window in-flight DMAs hide in:
        # modeled width if configured, else measured wall time.
        self._clock_us += (self.decode_window_us
                           if self.decode_window_us is not None
                           else (time.perf_counter() - t_dec) * 1e6)
        if self.fault_mode == "fused":
            # Drain-within-kernel settled: jobs consumed this step charge
            # only the tail past the window, and the staged payloads are
            # scattered so the pool is authoritative before the parking
            # gathers in the retire loop below (DESIGN.md §13).
            self._settle_fused()
        # The decode window may have carried queued write-backs past
        # their disk-ready time: persist them before the completion
        # parks below ask park_allowed().
        self.host.pump(self._clock_us)
        self._meter_translation(runnable)
        self._unstack_states(seqs, state)
        done_now = []
        for i, r in enumerate(runnable):
            r.out.append(int(nxt[i]))
            self.stats.decode_tokens += 1
            if len(r.out) >= r.max_new \
                    or self.cache.seq_tokens[r.rid] >= self.max_seq - 1:
                r.done = True
                done_now.append(r)
                if r.deadline_us is not None:
                    # SLO attainment on the modeled clock, per priority
                    # tier (DESIGN.md §10).
                    self.stats.note_deadline(
                        r.priority, self._clock_us <= r.deadline_us)
        with Span(self.stats, "engine.retire"):
            for r in done_now:
                self._retire(r)
        # Execute any CAC compaction plans on-device.
        self._run_compaction()
        st = self.cache.stats()
        self.stats.coalesced_sum += st.get("coalesced_fraction", 0.0)
        self.stats.occupancy_sum += st.get("occupancy", 0.0)
        self.stats.decode_steps += 1
        return True

    def _retire(self, r: Request) -> None:
        # Park the finished prompt's pages in the prefix cache before
        # the frames are freed / host copies dropped (DESIGN.md §8).
        self._park_prefix(r)
        if self.translation_meter is not None:
            # Address space retires with the sequence: its coalesced
            # entries and in-flight MSHR keys go with it.
            for s in range(self.cache.S):
                self.translation_meter.drop_space((r.rid, s))
        self.active.remove(r)
        self.cache.free(r.rid)
        self.states.pop(r.rid, None)
        self.host.drop_seq(r.rid)
        self._note_prestage_waste(r.rid)
        dropped = self.staging.invalidate_seq(r.rid)
        self.stats.prefetch_wasted += dropped
        self.prefetch.stats["wasted_pages"] += dropped
        self.prefetch.cancel_seq(r.rid)
        self._saved_tokens.pop(r.rid, None)

    def _meter_translation(self, runnable) -> None:
        """Run this step's packed KV page touches through the translation
        meter (DESIGN.md §15).  Each (seq, shard) pair is a distinct
        address space; latency is charged to the request's tenant.  Pure
        observation — decode results and the engine clock are untouched."""
        if self.translation_meter is None:
            return
        tables = []
        for r in runnable:
            for s, m in enumerate(self.cache.mgrs):
                t = m.tables.get(r.rid)
                if t is not None:
                    tables.append(((r.rid, s), r.tenant, t.ppn))
        d = self.translation_meter.step_access(self._clock_us, tables)
        st = self.stats
        st.translation_lookups += int(d["lookups"])
        st.translation_tlb_hits += int(d["tlb_hits"])
        st.translation_walks += int(d["walks"])
        st.translation_walk_cycles += d["walk_cycles"]
        st.translation_queue_cycles += d["queue_cycles"]
        st.translation_us += self.translation_meter.cycles_us(
            d["latency_cycles"])

    def translation_backlog_us(self) -> float:
        """Booked walker time beyond the engine clock, in modeled µs —
        the translation-interference term the router's dispatch cost
        charges.  0.0 when the meter is off (router claims unchanged)."""
        if self.translation_meter is None:
            return 0.0
        return self.translation_meter.backlog_us(self._clock_us)

    def _run_compaction(self):
        ops = self.cache.drain_copy_ops()
        if ops and self.translation_meter is not None:
            # CAC remapped pages: splinter exactly the touched subregions
            # out of the TLB (the selective shootdown the coalesced-entry
            # model requires).  rmap already points at the destination.
            for s, op in ops:
                owner_vpn = self.cache.mgrs[s].rmap.get(op.dst_ppn)
                if owner_vpn is not None:
                    self.translation_meter.splinter(
                        (owner_vpn[0], s), owner_vpn[1])
        if not ops or self.pools is None:
            return
        with Span(self.stats, "mm.compact"):
            pps = self.cache.pages_per_shard
            src = self._dev(np.asarray(
                [s * pps + op.src_ppn for s, op in ops], np.int32))
            dst = self._dev(np.asarray(
                [s * pps + op.dst_ppn for s, op in ops], np.int32))
            k, v = self.pools
            # pools are stacked [L, NP, ...]: compact every layer's pool.
            k = jax.vmap(lambda pool: kops.page_compact(
                pool, src, dst, use_pallas=self.use_pallas))(k)
            v = jax.vmap(lambda pool: kops.page_compact(
                pool, src, dst, use_pallas=self.use_pallas))(v)
            self.pools = (k, v)
        self.stats.compaction_copies += len(ops)

    # ------------------------------------------------------------- states

    def _stack_states(self, seqs, rows):
        """Per-request decode states stacked into ``rows`` batch rows
        (zeros for the idle ones)."""
        if not self.states:
            return {}
        out = {}
        for k in self.states[seqs[0]].keys():
            parts = [self._state_of(s)[k] for s in seqs]
            parts += [jnp.zeros_like(parts[0])] * (rows - len(seqs))
            out[k] = jnp.concatenate(
                parts,
                axis=1 if k in ("ssm", "conv", "cross_k", "cross_v") else 0)
        return out

    def _state_of(self, seq):
        return self.states[seq]

    def _unstack_states(self, seqs, stacked):
        if not stacked:
            return
        for k, v in stacked.items():
            ax = 1 if k in ("ssm", "conv", "cross_k", "cross_v") else 0
            # One row per request (prefill runs B=1); idle rows dropped.
            parts = jnp.split(v, v.shape[ax], axis=ax)
            for s, part in zip(seqs, parts):
                self.states[s][k] = part

    # ------------------------------------------------------------- run

    def run_until_drained(self, max_steps: int = 10_000):
        steps = 0
        while (self.queue or self.active or self.preempted) \
                and steps < max_steps:
            self.step()
            steps += 1
        if self.fault_mode in ("async", "fused") and not (
                self.queue or self.active or self.preempted):
            # Settle transfers still riding the channels so the reported
            # hidden/exposed/wasted split covers every issued byte (a
            # prefetch issued on the final step would otherwise stay
            # unaccounted while its µs sit in transfer_us).
            self._clock_us = max(self._clock_us, self.dma.busy_until())
            self._drain_prefetches()
        self.host.pump(self._clock_us)
        return steps
