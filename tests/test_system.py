"""System tests for the Mosaic core: allocator invariants, coalescing,
compaction, demand paging, and the kernel-facing packed views.

Property tests (hypothesis) drive random alloc/append/free/dealloc
interleavings through both managers and assert the module-documented
invariants after every operation:

  I1..I5  physical pool invariants (pagepool.check_invariants)
  I6      soft guarantee: a frame only ever holds one owner's pages
  I7      coalesced bit => vframe is full + physically contiguous + aligned
  I8      rmap is exactly the set of mapped pages
  I9      CAC plans never move a page across protection domains and the
          copy batch is hole-free from the kernel's perspective
"""

import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis")
from hypothesis import HealthCheck, example, given, settings
from hypothesis import strategies as st

from repro.core.baseline_mmu import BaselineMMU
from repro.core.cocoa import OutOfMemory
from repro.core.manager import MosaicManager
from repro.core.pagepool import PoolConfig
from repro.core.demand_paging import LinkModel, ResidencyTracker

FP = 4          # frame_pages (small so property tests hit edge cases fast)
PTOK = 8        # tokens per page


def make_mgr(kind="mosaic", num_pages=16 * FP, compact_threshold=0.5):
    cfg = PoolConfig(num_pages=num_pages, frame_pages=FP, page_tokens=PTOK,
                     compact_threshold=compact_threshold)
    return MosaicManager(cfg) if kind == "mosaic" else BaselineMMU(cfg)


# ---------------------------------------------------------------- property


ops_strategy = st.lists(
    st.one_of(
        st.tuples(st.just("alloc"), st.integers(0, 3),
                  st.integers(1, 6 * PTOK)),
        st.tuples(st.just("append"), st.integers(0, 3), st.integers(1, 12)),
        st.tuples(st.just("free_tail"), st.integers(0, 3),
                  st.integers(1, 4)),
        st.tuples(st.just("dealloc"), st.integers(0, 3), st.just(0)),
        st.tuples(st.just("compact"), st.integers(0, 3), st.just(0)),
    ),
    min_size=1, max_size=40,
)


def _apply_ops(mgr, ops):
    """Drive a manager through an op sequence; returns #completed ops."""
    done = 0
    for op, owner, n in ops:
        try:
            if op == "alloc":
                mgr.allocate_tokens(owner, n)
            elif op == "append":
                mgr.append_tokens(owner, n)
            elif op == "free_tail":
                if owner in mgr.tables:
                    mapped = mgr.tables[owner].mapped_vpns()
                    if mapped:
                        mgr.free_pages(owner, mapped[-min(n, len(mapped)):])
            elif op == "dealloc":
                if owner in mgr.tables:
                    mgr.deallocate(owner)
            elif op == "compact":
                mgr.compact(owner)
        except OutOfMemory:
            pass  # pool pressure is a legal outcome, not a bug
        mgr.check_invariants()
        done += 1
    return done


@settings(max_examples=60, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=ops_strategy)
def test_mosaic_invariants_under_random_ops(ops):
    mgr = make_mgr("mosaic")
    _apply_ops(mgr, ops)
    # Teardown returns every page: pool must drain to empty.
    for owner in list(mgr.owners()):
        mgr.deallocate(owner)
    mgr.check_invariants()
    assert mgr.pool.occupancy() == 0.0
    assert mgr.pool.num_free_frames == mgr.config.num_frames
    assert not mgr.rmap


@settings(max_examples=40, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=ops_strategy)
def test_baseline_invariants_under_random_ops(ops):
    mgr = make_mgr("gpu-mmu")
    _apply_ops(mgr, ops)
    for owner in list(mgr.owners()):
        mgr.deallocate(owner)
    mgr.check_invariants()
    assert mgr.pool.occupancy() == 0.0


@settings(max_examples=40, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=ops_strategy)
# An en-masse allocation that runs out of memory part-way must leave no
# half-mapped pages behind (found by this test's search).
@example(ops=[("alloc", 0, 1)] * 8 + [("alloc", 0, 41)]
         + [("alloc", 0, 48)] * 3 + [("alloc", 1, 33)])
def test_cac_plans_stay_in_domain_and_disjoint(ops):
    """I9: every CAC copy batch has src∩dst=∅ and stays within one owner.

    Disjointness is what lets the page_compact kernel execute the whole
    batch as one launch with no ordering hazards (see kernels/page_compact).
    """
    mgr = make_mgr("mosaic", num_pages=8 * FP)
    for op, owner, n in ops:
        try:
            if op == "alloc":
                mgr.allocate_tokens(owner, n)
            elif op == "append":
                mgr.append_tokens(owner, n)
            elif op == "free_tail" and owner in mgr.tables:
                mapped = mgr.tables[owner].mapped_vpns()
                if mapped:
                    mgr.free_pages(owner, mapped[-min(n, len(mapped)):])
            elif op == "dealloc" and owner in mgr.tables:
                mgr.deallocate(owner)
            elif op == "compact":
                mgr.compact(owner)
        except OutOfMemory:
            pass
        batch = mgr.drain_copy_ops()
        srcs = [c.src_ppn for c in batch]
        dsts = [c.dst_ppn for c in batch]
        assert len(set(srcs)) == len(srcs), "page copied out twice"
        assert len(set(dsts)) == len(dsts), "two copies into one slot"
        assert not set(srcs) & set(dsts), "chained copy in one batch"
        mgr.check_invariants()


# ---------------------------------------------------------------- CoCoA


def test_en_masse_allocation_coalesces_immediately():
    """Paper's key observation: en-masse allocation => whole frames =>
    immediate zero-copy coalescing (steps 5-6 of Fig. 4)."""
    mgr = make_mgr()
    mgr.allocate_tokens(0, 3 * FP * PTOK)   # exactly 3 frames of tokens
    t = mgr.table(0)
    assert t.num_pages == 3 * FP
    assert all(t.coalesced[:3])
    assert mgr.pool.coalesced_fraction() == 1.0
    assert mgr.pool.stats["coalesce_ops"] == 3
    # and the migrations required for it: zero.
    assert mgr.pool.stats["compaction_copies"] == 0


def test_soft_guarantee_across_owners():
    mgr = make_mgr()
    for owner in range(4):
        mgr.allocate_tokens(owner, int(2.5 * FP * PTOK))
    pool = mgr.pool
    for owner, table in mgr.tables.items():
        frames = {pool.frame_of(p) for p in table.ppn if p >= 0}
        for f in frames:
            assert pool.frame_owner[f] == owner


def test_append_growth_coalesces_at_frame_boundary():
    """Decode growth fills the active frame slot-by-slot; the frame is
    promoted exactly when its last slot fills (in-place, no copies)."""
    mgr = make_mgr()
    for _ in range((FP - 1) * PTOK):          # fills pages 0..FP-2
        mgr.append_tokens(0, 1)
    assert not mgr.table(0).coalesced[0]
    mgr.append_tokens(0, 1)   # first token of the frame's last page
    assert mgr.table(0).coalesced[0]
    assert mgr.pool.stats["compaction_copies"] == 0


def test_baseline_interleaving_denies_coalescing():
    """Fig. 2: round-robin en-masse allocation through the frame-blind
    baseline interleaves owners within frames -> ~no coalescing chances."""
    mosaic, base = make_mgr("mosaic"), make_mgr("gpu-mmu")
    # Interleave odd-sized buffers (not frame multiples) across 3 owners.
    for rep in range(3):
        for owner in range(3):
            for m in (mosaic, base):
                m.allocate_tokens(owner, 3 * PTOK + owner)
    assert base.multi_owner_frames() > 0
    assert base.coalesce_opportunities == 0
    # Mosaic, same workload: most pages sit in coalesced frames.
    assert mosaic.pool.coalesced_fraction() > 0.5
    packed = mosaic.pack(mosaic.owners(), max_pages=4 * FP)
    assert (packed["coalesced"] == 1).any()


def test_oom_triggers_compaction_then_succeeds():
    """Paper steps 9-10: compaction frees frames for future allocations."""
    mgr = make_mgr(num_pages=4 * FP, compact_threshold=0.4)
    # Two owners, each holding a sliver of two frames (fragmented).
    for owner in (0, 1):
        mgr.allocate_tokens(owner, FP * PTOK + PTOK)    # frame + 1 page
    for owner in (0, 1):
        mapped = mgr.tables[owner].mapped_vpns()
        mgr.free_pages(owner, mapped[1:FP])             # fragment frame 0
    # All 4 frames are owned; a 1-frame en-masse alloc must compact first.
    vpns = mgr.allocate_tokens(2, FP * PTOK)
    assert len(vpns) == FP
    mgr.check_invariants()


# ---------------------------------------------------------------- CAC + kernel


def test_compaction_preserves_payload_through_kernel():
    """End-to-end CAC: plan on host, execute with the page_compact kernel,
    then verify every owner's virtual view of the data is unchanged."""
    import jax.numpy as jnp
    from repro.kernels.page_compact import page_compact

    mgr = make_mgr(num_pages=8 * FP, compact_threshold=0.4)
    rng = np.random.default_rng(3)
    pool_arr = rng.normal(size=(8 * FP, PTOK, 2, 4)).astype(np.float32)

    mgr.allocate_tokens(0, 4 * FP * PTOK)
    # Virtual content: page payload == pool content at its ppn at t0.
    view0 = {v: pool_arr[p].copy()
             for v, p in enumerate(mgr.table(0).ppn)}
    # Fragment: free most of vframes 1 and 2.
    dropped = list(range(FP + 1, 3 * FP - 1))
    mgr.free_pages(0, dropped)
    for v in dropped:
        del view0[v]
    plan_ops = mgr.drain_copy_ops()
    if not plan_ops:   # fragmentation below threshold — force it
        mgr.compact(0)
        plan_ops = mgr.drain_copy_ops()
    assert plan_ops, "expected a compaction plan"
    src = jnp.asarray([c.src_ppn for c in plan_ops], jnp.int32)
    dst = jnp.asarray([c.dst_ppn for c in plan_ops], jnp.int32)
    out = np.asarray(page_compact(jnp.asarray(pool_arr), src, dst,
                                  interpret=True))
    # The virtual view through the updated table must be unchanged.
    t = mgr.table(0)
    for v, payload in view0.items():
        np.testing.assert_array_equal(out[t.ppn[v]], payload)
    mgr.check_invariants()


def test_compaction_frees_frames():
    mgr = make_mgr(num_pages=6 * FP, compact_threshold=0.4)
    mgr.allocate_tokens(0, 4 * FP * PTOK)
    free_before = mgr.pool.num_free_frames
    # Leave one live page in each of vframes 0..3 -> 4 fragmented frames.
    drop = [v for v in range(4 * FP) if v % FP != 0]
    mgr.free_pages(0, drop)
    assert mgr.pool.num_free_frames >= free_before + 3


# ---------------------------------------------------------------- packing


def test_pack_batch_tables_layout():
    mgr = make_mgr()
    mgr.allocate_tokens(0, FP * PTOK)        # coalesced frame
    mgr.allocate_tokens(1, 2 * PTOK)         # partial frame (splintered)
    packed = mgr.pack([0, 1], max_pages=2 * FP)
    assert packed["page_tables"].shape == (2, 2 * FP)
    assert packed["frame_tables"].shape == (2, 2)
    assert packed["coalesced"][0, 0] == 1
    assert packed["coalesced"][1, 0] == 0
    assert packed["seq_pages"][0] == FP
    assert packed["seq_pages"][1] == 2
    assert packed["seq_tokens"][0] == FP * PTOK
    # Frame table entry must point at the physical frame of the vframe.
    pf = packed["frame_tables"][0, 0]
    assert pf >= 0
    base = mgr.table(0).ppn[0]
    assert pf == base // FP


# ---------------------------------------------------------------- paging


def test_residency_tracker_accounting():
    link = LinkModel(setup_us=10.0, bandwidth_GBps=10.0)
    tr = ResidencyTracker(num_pages=64, page_bytes=4096, link=link)
    batch = tr.fault_in([1, 2, 3])
    assert batch.nbytes == 3 * 4096
    assert tr.stats["faults"] == 3 and tr.stats["fault_batches"] == 1
    # Second touch: resident, no fault.
    batch = tr.fault_in([1, 2, 3])
    assert not batch.ppns and tr.stats["faults"] == 3
    assert tr.touch([3, 4]) == [4]
    assert tr.evict([2]) == 1
    assert tr.touch([2]) == [2]
    # transfer model: setup + bytes/bw
    assert link.transfer_us(10_000) == pytest.approx(10.0 + 1.0)


def test_memory_bloat_metric():
    """Large-page-only designs bloat; filling the frame removes the bloat."""
    mgr = make_mgr(num_pages=16 * FP)
    mgr.allocate_tokens(0, 1)                 # 1 page in a FP-page frame
    assert mgr.pool.memory_bloat() == FP      # worst case: whole frame held
    mgr.allocate_tokens(0, (FP - 1) * PTOK)   # fill the frame
    assert mgr.pool.memory_bloat() == 1.0
