"""The readers of the program's spans and wait counters, rehearsed on the
CPU in smoke runs of each cell: what they read must hang together."""

import pytest

import devtrace
import harness
from test_bench_harness import (CELLS, _recorded_trace, _run,  # noqa: F401
                                interpret_movers, smoke_cell)


def _traced(name, monkeypatch):
    """A traced smoke run of ``name``: its metrics and the readers'
    context (which holds the window's end-to-end numbers too)."""
    monkeypatch.setattr(devtrace, "start", lambda log_dir: None)
    monkeypatch.setattr(devtrace, "stop_and_reduce", _recorded_trace)
    seen = {}
    read = harness.read_metric

    def read_and_keep(metric, ctx):
        seen["ctx"] = ctx
        return read(metric, ctx)

    monkeypatch.setattr(harness, "read_metric", read_and_keep)
    out = _run(smoke_cell(name), trace=True)
    return {k: v["value"] for k, v in out["metrics"].items()}, seen["ctx"]


@pytest.mark.parametrize("name", CELLS)
def test_program_span_readers_agree(name, monkeypatch):
    m, ctx = _traced(name, monkeypatch)
    st = ctx["stats"]
    assert 0 < m["fault_in_ms_per_step"] <= m["host_ms_per_step"]
    assert m["host_ms_per_step"] * st.span_n["engine.step"] \
        <= st.wall_s * 1e3
    if "preempt_ms_mean" in harness.load_cell(name).per_layer:
        assert st.swaps_out > 0 and st.swaps_in > 0
        assert m["preempt_ms_mean"] > 0 and m["resume_wait_p50_ms"] > 0
        assert len(st.resume_wait_ns) == st.swaps_in
    if "queue_wait_p50_ms" in m:
        assert 0 < m["queue_wait_p50_ms"] <= ctx["e2e"]["ttft_p50_ms"]
