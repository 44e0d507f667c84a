"""Fused gather-attend decode over partially-resident KV (DESIGN.md §13).

Covers the readiness-masked attention paths at every layer: the pallas
kernel's two-accumulator flush (all-resident → bitwise-identical to the
baseline paged kernel; partial/all-late → matches the eager reference
and gather-then-attend to float32 round-off), the pure-JNP local path's
slot-select (bitwise-identical to the slot-free call when the staged
bytes equal the pool's), per-page DMA completion timestamps, staging
slot addressing, and the serving engine's three-mode token identity
with zero-resident resume steps and mid-run preemption.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_smoke_config
from repro.configs.base import PoolGeometry
from repro.core.demand_paging import LinkModel
from repro.kernels.paged_attention import (fused_paged_attention_kernel,
                                           paged_attention_kernel,
                                           readiness_meta)
from repro.kernels.ref import fused_gather_attend_ref
from repro.models.paged import paged_attention_local
from repro.serving.dma import AsyncDMAEngine, StagingBuffer
from repro.serving.engine import Request, ServingEngine

GEO = PoolGeometry(page_tokens=8, frame_pages=4, compact_threshold=0.4)

# Kernel-vs-anything comparisons are allclose, not bitwise: pallas
# interpret mode jits the whole kernel (XLA fuses elementwise chains)
# while the eager reference runs op-by-op, so identical math can differ
# in the last bits.  Bitwise asserts are reserved for same-trace pairs
# (fused kernel all-ready vs baseline kernel; engine tokens).
TOL = dict(rtol=1e-4, atol=1e-5)


def _case(B=3, nblk=4, n_kv=2, g=2, dh=8, ptok=8, seed=0):
    rng = np.random.default_rng(seed)
    NP = B * nblk + 3
    q = jnp.asarray(rng.standard_normal((B, n_kv * g, dh), np.float32))
    pk = jnp.asarray(rng.standard_normal((NP, ptok, n_kv, dh), np.float32))
    pv = jnp.asarray(rng.standard_normal((NP, ptok, n_kv, dh), np.float32))
    tables = jnp.asarray(
        rng.permutation(NP)[:B * nblk].reshape(B, nblk).astype(np.int32))
    ntok = jnp.asarray(
        rng.integers(1, ptok + 1, (B, nblk)).astype(np.int32))
    return q, pk, pv, tables, ntok, 1.0 / float(np.sqrt(dh))


def _stage_from_pool(pk, pv, tables, late):
    """Stage the `late` pages' true bytes; garbage their pool copies."""
    rng = np.random.default_rng(99)
    tbl = np.asarray(tables)
    sk = np.asarray(pk)[tbl[late]]
    sv = np.asarray(pv)[tbl[late]]
    dk, dv = np.asarray(pk).copy(), np.asarray(pv).copy()
    dk[tbl[late]] = rng.standard_normal(sk.shape).astype(np.float32)
    dv[tbl[late]] = rng.standard_normal(sv.shape).astype(np.float32)
    slots = np.full(tbl.shape, -1, np.int32)
    slots[late] = np.arange(int(late.sum()), dtype=np.int32)
    return (jnp.asarray(sk), jnp.asarray(sv), jnp.asarray(dk),
            jnp.asarray(dv), jnp.asarray(slots))


# ------------------------------------------------------------ kernel layer


def test_fused_kernel_all_ready_bitwise_vs_baseline():
    """Every slot -1: the late accumulator never initializes and the
    flush emits the ready scratch untouched — bitwise-identical to the
    baseline page-granularity kernel."""
    q, pk, pv, tables, ntok, scale = _case()
    base = paged_attention_kernel(q, pk, pv, tables, ntok,
                                  granularity="page", scale=scale,
                                  interpret=True)
    slots = jnp.full(tables.shape, -1, jnp.int32)
    fused = fused_paged_attention_kernel(
        q, pk, pv, pk[:2], pv[:2], tables, slots, ntok, scale=scale,
        interpret=True)
    for a, b in zip(fused, base):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_fused_kernel_partial_matches_ref_and_gather():
    """Mask flips mid-accumulation: alternating ready/late blocks must
    match both the eager reference and scatter-then-attend."""
    q, pk, pv, tables, ntok, scale = _case(seed=1)
    late = np.zeros(tables.shape, bool)
    late[:, 1::2] = True
    late[0, 0] = True                      # first block late on row 0
    sk, sv, dk, dv, slots = _stage_from_pool(pk, pv, tables, late)
    fused = fused_paged_attention_kernel(
        q, dk, dv, sk, sv, tables, slots, ntok, scale=scale, interpret=True)
    ref = fused_gather_attend_ref(q, dk, dv, sk, sv, tables, slots, ntok,
                                  scale=scale)
    base = paged_attention_kernel(q, pk, pv, tables, ntok,
                                  granularity="page", scale=scale,
                                  interpret=True)
    for f, r, b in zip(fused, ref, base):
        np.testing.assert_allclose(np.asarray(f), np.asarray(r), **TOL)
        np.testing.assert_allclose(np.asarray(f), np.asarray(b), **TOL)


def test_fused_kernel_zero_resident_row():
    """A row whose pages are ALL late (zero-resident decode step): only
    the late accumulator runs and the flush emits its scratch."""
    q, pk, pv, tables, ntok, scale = _case(seed=2)
    late = np.zeros(tables.shape, bool)
    late[0, :] = True                       # row 0 fully late
    late[2, -1] = True                      # row 2 a single straggler
    sk, sv, dk, dv, slots = _stage_from_pool(pk, pv, tables, late)
    fused = fused_paged_attention_kernel(
        q, dk, dv, sk, sv, tables, slots, ntok, scale=scale, interpret=True)
    base = paged_attention_kernel(q, pk, pv, tables, ntok,
                                  granularity="page", scale=scale,
                                  interpret=True)
    for f, b in zip(fused, base):
        np.testing.assert_allclose(np.asarray(f), np.asarray(b), **TOL)


def test_readiness_meta_edges():
    slots = jnp.asarray(np.array([[-1, -1, -1],     # all ready
                                  [0, 1, 2],        # all late
                                  [-1, 3, -1]],     # mixed
                                 np.int32))
    meta = np.asarray(readiness_meta(slots))
    np.testing.assert_array_equal(meta[0], [0, 0, -1])
    np.testing.assert_array_equal(meta[1], [3, -1, 0])
    np.testing.assert_array_equal(meta[2], [1, 0, 1])


# ------------------------------------------------------- local (JNP) layer


def test_local_slot_select_bitwise_when_staged_equals_pool():
    """The local path only swaps the load source per page; with staged
    bytes equal to the pool's, partial-resident and slot-free calls are
    byte-for-byte identical (this is what makes engine tokens identical
    across modes by construction)."""
    q, pk, pv, tables, ntok, scale = _case(seed=3)
    base = paged_attention_local(q, pk, pv, tables, ntok, scale=scale)

    late = np.zeros(tables.shape, bool)
    late[:, ::2] = True
    tbl = np.asarray(tables)
    sk = jnp.asarray(np.asarray(pk)[tbl[late]])
    sv = jnp.asarray(np.asarray(pv)[tbl[late]])
    slots = np.full(tbl.shape, -1, np.int32)
    slots[late] = np.arange(int(late.sum()), dtype=np.int32)
    fused = paged_attention_local(q, pk, pv, tables, ntok, scale=scale,
                                  stage_k=sk, stage_v=sv,
                                  slots=jnp.asarray(slots))
    for a, b in zip(fused, base):
        assert np.array_equal(np.asarray(a), np.asarray(b))

    # All-(-1) slots with stage pools attached: classic path, bitwise.
    allready = paged_attention_local(
        q, pk, pv, tables, ntok, scale=scale, stage_k=sk, stage_v=sv,
        slots=jnp.full(tbl.shape, -1, jnp.int32))
    for a, b in zip(allready, base):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------- DMA/staging


def _payload():
    return (np.zeros((1, 8, 1, 4), np.float32),
            np.zeros((1, 8, 1, 4), np.float32))


def test_dma_page_done_us_monotone_and_bounded():
    link = LinkModel(setup_us=10.0, bandwidth_GBps=10.0)
    dma = AsyncDMAEngine(link, n_channels=1)
    keys = [(0, 0, i) for i in range(4)]
    job = dma.enqueue(keys, list(range(4)), 1000,
                      [_payload()] * 4, now_us=50.0)
    times = [job.page_done_us(i) for i in range(4)]
    assert all(b > a for a, b in zip(times, times[1:]))
    assert times[0] > job.start_us
    assert times[-1] == pytest.approx(job.done_us)


def test_staging_slot_addressing():
    st = StagingBuffer()
    p = _payload()
    st.stage((1, 0, 0), p)
    st.stage((1, 0, 1), p)
    s0, s1 = st.slot_of((1, 0, 0)), st.slot_of((1, 0, 1))
    assert s0 is not None and s1 is not None and s0 != s1
    assert st.slot_of((9, 9, 9)) is None
    # Slot survives the double-buffer swap while the entry is retained.
    st.swap()
    assert st.slot_of((1, 0, 0)) == s0
    # Consume frees the slot; invalidation frees the rest.
    st.consume((1, 0, 0))
    assert st.slot_of((1, 0, 0)) is None
    st.invalidate_seq(1)
    assert st.slot_of((1, 0, 1)) is None


# ------------------------------------------------------------ engine layer


def _engine(mode, *, window=None, max_batch=6, seed=0, **kw):
    cfg = get_smoke_config("qwen2.5-3b")
    return cfg, ServingEngine(cfg, geometry=GEO, max_batch=max_batch,
                              max_seq=96, manager_kind="mosaic", seed=seed,
                              oversubscription=2.0, fault_mode=mode,
                              decode_window_us=window, **kw)


def _requests(cfg, n=8, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, tenant=i % 3,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(24, 56)))
                    .astype(np.int32),
                    max_new=int(rng.integers(24, 40))) for i in range(n)]


def test_fused_tokens_identical_and_tail_only_exposed():
    """2× oversubscribed, starved 2 µs window: fused tokens byte-equal
    sync and async, exposed µs at or below async's per-page stalls, and
    pages actually ride both fused buckets (ready + drained)."""
    outs, engines = {}, {}
    for mode, window in (("sync", None), ("async", 2.0), ("fused", 2.0)):
        cfg, eng = _engine(mode, window=window)
        reqs = _requests(cfg)
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained(max_steps=5000)
        assert all(r.done for r in reqs)
        eng.cache.check_invariants()
        outs[mode] = {r.rid: list(r.out) for r in reqs}
        engines[mode] = eng
    assert outs["fused"] == outs["sync"]
    assert outs["fused"] == outs["async"]
    f, a = engines["fused"].stats, engines["async"].stats
    assert f.faults > 0, "workload never faulted: test is vacuous"
    assert f.fault_exposed_us <= a.fault_exposed_us
    assert f.fault_exposed_us == pytest.approx(f.fused_tail_us)
    assert f.fused_ready_pages + f.fused_drained_pages > 0
    assert "fused" in f.summary()


def test_fused_zero_resident_resume_step():
    """Hold a request swapped out, churn until its pages are cold, then
    release: its first fused decode step starts with every page missing
    (all faulted in-kernel), and tokens still match the sync run."""
    outs, drained = {}, {}
    for mode, window in (("sync", None), ("fused", 2.0)):
        cfg, eng = _engine(mode, window=window, max_batch=3, seed=0)
        rng = np.random.default_rng(3)
        spec = [(64, 16), (40, 28), (40, 28)]
        reqs = [Request(rid=i, tenant=i,
                        prompt=rng.integers(0, cfg.vocab_size, T)
                        .astype(np.int32), max_new=mn)
                for i, (T, mn) in enumerate(spec)]
        for r in reqs:
            eng.submit(r)
        for _ in range(2):
            eng.step()
        assert eng.preempt(0, hold=True)
        for _ in range(6):
            eng.step()
        eng.release(0)
        eng.run_until_drained(max_steps=2000)
        assert all(r.done for r in reqs)
        eng.cache.check_invariants()
        outs[mode] = {r.rid: list(r.out) for r in reqs}
        drained[mode] = eng.stats
    assert outs["fused"] == outs["sync"]
    s = drained["fused"]
    assert s.faults > 0
    assert s.fused_ready_pages + s.fused_drained_pages > 0


def test_fused_midrun_preemption_keeps_tokens():
    """Preempt a live request mid-run under fused mode (its in-flight
    staged pages must settle without corrupting anyone) and resume:
    tokens match the sync run of the same trace."""
    outs = {}
    for mode, window in (("sync", None), ("fused", 2.0)):
        cfg, eng = _engine(mode, window=window)
        reqs = _requests(cfg, n=6, seed=4)
        for r in reqs:
            eng.submit(r)
        for _ in range(4):
            eng.step()
        victim = next(r.rid for r in reqs if not r.done)
        eng.preempt(victim)                 # straight to resume queue
        eng.run_until_drained(max_steps=5000)
        assert all(r.done for r in reqs)
        eng.cache.check_invariants()
        assert eng.host.request_pages() == 0
        outs[mode] = {r.rid: list(r.out) for r in reqs}
    assert outs["fused"] == outs["sync"]


def test_fused_rejects_mla_families():
    cfg = get_smoke_config("deepseek-v2-lite-16b")
    with pytest.raises(ValueError, match="dense-attention"):
        ServingEngine(cfg, geometry=GEO, max_batch=2, max_seq=64,
                      manager_kind="mosaic", seed=0, fault_mode="fused")
