"""The serving engine's spans and counters (serving/trace.py).

One oversubscribed smoke engine, with a shared prompt prefix so that
admissions hit the prefix cache, runs to the end under a CPU profiler
trace while a test observer notes each span's parent.  Its counters, the
observed spans and the profiler's host events must agree.
"""

import glob
import os
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.configs.base import PoolGeometry
from repro.core.compaction import CopyOp
from repro.serving import trace
from repro.serving.engine import EngineStats, Request, ServingEngine

GEO = PoolGeometry(page_tokens=8, frame_pages=4, compact_threshold=0.4)

# Each span of the engine and the spans it may open under.
PARENTS = {
    "engine.step": {None},
    "engine.admit": {"engine.step"},
    "engine.prefill": {"engine.admit", "mm.resume"},
    "device.wait": {"engine.step", "engine.prefill"},
    "model.decode": {"engine.step"},
    "engine.pack": {"engine.step"},
    "mm.grow": {"engine.step"},
    "mm.preempt": {"engine.admit", "mm.grow", "mm.resume"},
    "mm.resume": {"engine.admit"},
    "mm.compact": {"engine.step", "engine.prefill", "mm.preempt",
                   "mm.resume", "tier.park"},
    "tier.fault_in": {"engine.step"},
    "tier.prefetch": {"engine.step"},
    "tier.gather": {"mm.preempt", "tier.park"},
    "tier.to_host": {"tier.gather"},
    "tier.park": {"engine.retire"},
    "engine.retire": {"engine.step"},
}
WITH_RID = ("engine.prefill", "mm.preempt", "mm.resume")


def _requests(cfg, n=10):
    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    return [Request(rid=i, tenant=0, priority=i % 3,
                    prompt=np.concatenate([shared, rng.integers(
                        0, cfg.vocab_size, int(rng.integers(8, 40)))
                        .astype(np.int32)]),
                    max_new=int(rng.integers(16, 40)))
            for i in range(n)]


class Observer:
    """Every closed span as (name, parent name, ns)."""

    def __init__(self, monkeypatch):
        self.closed = []
        parent = {}
        enter, exit_ = trace.Span.__enter__, trace.Span.__exit__

        def on_enter(sp):
            stack = trace._local.stack
            parent[id(sp)] = stack[-1].name if stack else None
            return enter(sp)

        def on_exit(sp, *exc):
            out = exit_(sp, *exc)
            self.closed.append((sp.name, parent.pop(id(sp)), sp.ns))
            return out

        monkeypatch.setattr(trace.Span, "__enter__", on_enter)
        monkeypatch.setattr(trace.Span, "__exit__", on_exit)


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """The engine run to the end, its requests, the observed spans, the
    counters at the trace's start, and the profiler's host events."""
    mp = pytest.MonkeyPatch()
    obs = Observer(mp)
    cfg = get_smoke_config("qwen2.5-3b")
    eng = ServingEngine(cfg, geometry=GEO, max_batch=3, max_seq=96,
                        manager_kind="mosaic", seed=0, oversubscription=2.0)
    # The smoke pool never fragments enough for CAC to plan a copy, so
    # each drain of the copy plan also hands back a page copied onto
    # itself: a real page_compact launch that changes no byte.
    drain = eng.cache.drain_copy_ops
    mp.setattr(eng.cache, "drain_copy_ops",
               lambda: drain() + [(0, CopyOp(0, 0))])
    reqs = _requests(cfg)
    for r in reqs:
        eng.submit(r)
    for _ in range(5):
        eng.step()
    before = (dict(eng.stats.span_n), dict(eng.stats.span_ns))
    log_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(log_dir)
    try:
        steps = 0
        while eng.queue or eng.active or eng.preempted:
            eng.step()
            steps += 1
            assert steps < 500
    finally:
        jax.profiler.stop_trace()
    mp.undo()
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(line.name, ev.start_ns, ev.duration_ns, ev.name,
                            dict(ev.stats))
                           for ev in line.events if ev.name in PARENTS]
    return eng, reqs, obs.closed, before, events


def test_every_span_is_recorded_under_its_parent(run):
    eng, reqs, closed, _before, _events = run
    assert all(r.done for r in reqs)
    assert eng.stats.swaps_out > 0 and eng.stats.prefix_hits > 0
    seen = {}
    for name, parent, _ns in closed:
        seen.setdefault(name, set()).add(parent)
    assert set(seen) == set(PARENTS)
    for name, parents in seen.items():
        assert parents <= PARENTS[name], (name, parents)
    assert set(eng.stats.span_n) == set(PARENTS)


def test_self_time_and_children_fit_inside_the_parent(run):
    eng, _reqs, closed, _before, _events = run
    st = eng.stats
    for name in PARENTS:
        mine = [ns for n, _p, ns in closed if n == name]
        assert st.span_n[name] == len(mine)
        assert st.span_ns[name] == sum(mine)
        children = sum(ns for _n, p, ns in closed if p == name)
        assert 0 <= st.span_self_ns[name] <= st.span_ns[name]
        assert st.span_ns[name] - st.span_self_ns[name] == children
    # Self times partition the outermost spans' time.
    assert sum(st.span_self_ns.values()) == st.span_ns["engine.step"]
    assert st.span_ns["tier.to_host"] <= st.span_ns["tier.gather"]


def test_wall_s_is_the_engine_step_total(run):
    eng = run[0]
    assert eng.stats.wall_s == eng.stats.span_ns["engine.step"] / 1e9
    assert eng.stats.tok_per_s() > 0
    # The admission counters are the prefill spans, split by path.
    assert eng.stats.admit_hits > 0 and eng.stats.admit_colds > 0
    assert eng.stats.admit_hit_us + eng.stats.admit_cold_us == \
        pytest.approx(eng.stats.span_ns["engine.prefill"] / 1e3)


def test_one_wait_sample_per_first_admission_and_per_resume(run):
    eng, reqs, _closed, _before, _events = run
    st = eng.stats
    assert st.lost_restarts == 0
    assert len(st.queue_wait_ns) == len(reqs)
    assert st.swaps_in > 0 and len(st.resume_wait_ns) == st.swaps_in
    assert st.span_n["mm.preempt"] == st.swaps_out
    assert min(st.queue_wait_ns) > 0 and min(st.resume_wait_ns) > 0
    # Every stamp was taken back.
    assert all(r.submit_ns is None and r.preempt_ns is None for r in reqs)


def test_profiler_trace_holds_the_program_spans(run):
    """Each span the engine closed while the profiler recorded is a host
    event of the same name, nested as observed, carrying its metadata,
    with durations that agree with the counters."""
    eng, _reqs, closed, (n0, ns0), events = run
    st = eng.stats
    by_name = {}
    for _line, _t, dur, name, _meta in events:
        n, tot = by_name.get(name, (0, 0))
        by_name[name] = (n + 1, tot + dur)
    assert set(by_name) == set(PARENTS)
    for name, (n, tot) in by_name.items():
        ours = st.span_ns[name] - ns0.get(name, 0)
        assert n == st.span_n[name] - n0.get(name, 0), name
        assert abs(tot - ours) <= 0.02 * ours + n * 5e5, (name, tot, ours)
    # Nesting, from the intervals of each thread's events.
    pairs = set()
    for line in {e[0] for e in events}:
        open_ = []
        for _l, t, dur, name, _m in sorted(
                (e for e in events if e[0] == line),
                key=lambda e: (e[1], -e[2])):
            while open_ and open_[-1][0] <= t:
                open_.pop()
            pairs.add((open_[-1][1] if open_ else None, name))
            open_.append((t + dur, name))
    observed = {(p, n) for n, p, _ns in closed}
    assert pairs <= observed
    assert {n for _p, n in pairs} == set(PARENTS)
    for _l, _t, _d, name, meta in events:
        if name in WITH_RID:
            assert "rid" in meta, (name, meta)
        if name == "engine.prefill":
            assert "hit" in meta
        if name == "engine.step":
            assert "step" in meta
        if name == "tier.gather":
            assert meta["pages"] > 0


def test_a_new_prefill_length_compiles_under_engine_prefill():
    cfg = get_smoke_config("qwen2.5-3b")
    # A pool size of its own, so no other engine compiled these shapes.
    eng = ServingEngine(cfg, geometry=GEO, max_batch=5, max_seq=136,
                        manager_kind="mosaic", seed=0, prefix_cache=False)
    rng = np.random.default_rng(1)
    eng.submit(Request(rid=0, tenant=0, max_new=4, prompt=rng.integers(
        0, cfg.vocab_size, 10).astype(np.int32)))
    eng.step()
    eng.step()
    before = dict(eng.stats.compiles)
    assert before.get("engine.prefill", 0) >= 1
    eng.submit(Request(rid=1, tenant=0, max_new=4, prompt=rng.integers(
        0, cfg.vocab_size, 37).astype(np.int32)))
    eng.step()
    assert eng.stats.compiles["engine.prefill"] \
        > before["engine.prefill"], eng.stats.compiles


def test_a_compile_is_counted_once_under_the_innermost_span():
    """However many engines registered the listener, one compile counts
    once, under the span open around it."""
    trace.listen()
    trace.listen()
    st = EngineStats()
    f = jax.jit(lambda x: x * 3 + 1)
    with trace.Span(st, "outer"):
        with trace.Span(st, "inner"):
            f(np.zeros(7, np.float32)).block_until_ready()
        f(np.zeros(7, np.float32)).block_until_ready()     # cached
    f(np.zeros(9, np.float32)).block_until_ready()         # no span open
    assert st.compiles == {"inner": 1}
    assert st.span_n == {"outer": 1, "inner": 1}


def test_a_span_costs_microseconds_with_no_profiler():
    st = EngineStats()
    n = 20_000
    t = time.perf_counter()
    for i in range(n):
        with trace.Span(st, "x", rid=i):
            pass
    per_us = (time.perf_counter() - t) / n * 1e6
    assert st.span_n["x"] == n
    # 2.5-4 µs on an idle CPU core; room for a loaded one.
    assert per_us < 50, per_us
