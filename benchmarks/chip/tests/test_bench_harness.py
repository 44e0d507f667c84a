"""The harness rehearsed on the CPU at smoke width.

Each cell's machinery runs in-process with its configuration cut to a
smoke size, the Pallas page movers in interpret mode and a short window.
What only the chip gives (times, device trace, memory) is not read
here, except through a trace recorded on the chip and committed beside
this file.
"""

import functools
import gzip
import importlib
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

import devtrace
import harness
from repro.serving.engine import Request

CHIP = pathlib.Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
TRACE = CHIP / "tests" / "data" / "smoke_trace.xplane.pb.gz"
SEED = 2**31 + 977


def smoke_cell(name: str) -> harness.Cell:
    """The cell with its configuration cut by its family's ``smoke`` and
    its traffic and engine cut alike for every cell."""
    cell = harness.load_cell(name)
    cell.cfg = cell.family.smoke(cell.cfg)
    m = cell.mix
    m["engine"].update(max_batch=4, max_seq=512, page_tokens=16,
                       frame_pages=4)
    if "pool_pages" in m["engine"]:
        # Still oversubscribed: 16 pages for 4 clients of up to 11 pages.
        m["engine"].update(max_seq=256, pool_pages=16)
    m["prompt_tokens"].update(min=16, median=48, max=128)
    m["output_tokens"].update(min=4, median=12, max=48)
    if m["loop"] == "open":
        m["rate_rps"] = 3.0
    else:
        m["clients"] = 4
    m["warmup_seconds"] = 1
    cell.limits.update(sample_requests=4, min_compared_tokens=10)
    return cell


@pytest.fixture(autouse=True)
def interpret_movers(monkeypatch):
    from repro.kernels import ops as kops
    for name in harness.MOVERS:
        monkeypatch.setattr(kops, name, functools.partial(
            getattr(kops, name), interpret=True))


def _run(cell, trace=False, **kw):
    """A 4 s run of ``cell`` checked by the reference its configuration
    names."""
    ref = importlib.import_module(cell.cfg["reference"])
    return harness.run(cell, SEED, 4.0, trace, time.perf_counter(),
                       ref.score,
                       device_kind="TPU v5 lite" if trace else None, **kw)


@pytest.mark.parametrize("name", CELLS)
def test_run_reports_every_end_to_end_metric(name):
    out = _run(smoke_cell(name))
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == set(harness.load_cell(name).end_to_end)
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-2:] == ["check", "_info"]
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}


def test_a_window_with_no_request_due_reports_no_ttft():
    """A closed loop whose clients all sent before the window and none
    finished in it has no first token due: its TTFT is left out, not 0."""
    req = Request(rid=0, tenant=0, prompt=np.zeros(4, np.int32), max_new=9)
    req.out = [1, 2, 3]
    s = harness.Sent(plan=None, req=req, sched=-1.0, sent=-1.0,
                     times=[0.0, 1.0, 2.0])
    e = harness.end_to_end([s], 3.0)
    assert e["ttft_p50_ms"] is None and e["ttft_p90_ms"] is None
    assert e["tokens_per_s"] == pytest.approx(2 / 3.0)
    assert e["itl_p95_ms"] > 0


def test_a_family_of_new_files_alone_runs(tmp_path, monkeypatch):
    """A family module that exists only as a new file under the families
    directory, here the dense family under another name, runs a cell
    through ``harness.run``: a configuration names it and nothing else
    changes."""
    cell = smoke_cell("qwen2.5-3b.chat")
    (tmp_path / "standin.py").write_text(
        (harness.FAMILIES / "dense.py").read_text())
    monkeypatch.setattr(harness, "FAMILIES", tmp_path)
    cell.cfg["family"] = "standin"
    assert cell.family.__file__ == str(tmp_path / "standin.py")
    out = _run(cell)
    assert out["correct"], out["check"]
    assert out["check"]["compared_tokens"]["value"] \
        >= cell.limits["min_compared_tokens"]


def _recorded_trace(log_dir):
    return devtrace.reduce_bytes(gzip.decompress(TRACE.read_bytes()))


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reads_every_per_layer_metric(name, monkeypatch):
    """The per-layer readers, fed the counters of a smoke run and a trace
    recorded on the chip, give every metric the cell lists."""
    monkeypatch.setattr(devtrace, "start", lambda log_dir: None)
    monkeypatch.setattr(devtrace, "stop_and_reduce", _recorded_trace)
    cell = smoke_cell(name)
    out = _run(cell, trace=True)
    assert set(out["metrics"]) == set(cell.per_layer), out["metrics"]
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    for key in ("device_ops", "idle_gaps"):
        assert 0 < len(out["breakdown"][key]) <= 10
    # Shares of a peak come from a smoke run's calls over a recorded
    # trace's times, so only their presence and sign mean anything here.
    assert all(m["value"] >= 0 for m in out["metrics"].values())


def _token_altered(dec):
    """Decode's logits of row 0 flipped: the engine appends the token its
    own model ranks last."""
    def broken(*a, **kw):
        logits, pools, state = dec(*a, **kw)
        return logits.at[0].multiply(-1.0), pools, state
    return broken


def _state_unchanged(dec):
    """Decode hands back the KV pools it was given: the step's writes are
    lost, so later steps attend to stale pages."""
    def broken(params, toks, pos, pools, ctx, state):
        logits, _pools, state = dec(params, toks, pos, pools, ctx, state)
        return logits, pools, state
    return broken


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged],
                         ids=["token altered", "state unchanged"])
def test_a_broken_timed_path_is_not_correct(fault):
    def plant(eng):
        eng._decode_jit = fault(eng._decode_jit)

    out = _run(smoke_cell("qwen2.5-3b.chat"), on_engine=plant)
    assert not out["correct"]
    gap = out["check"]["widest_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_the_control_reads_wider_gaps_than_the_program():
    """The float8 control fails the run's own comparison.  Its error grows
    with depth: at the smoke cell's 2 layers its widest gap reads 0.2-0.5
    against the cell's limit of 0.3, at 8 layers 0.5-0.8 (at the cells'
    own sizes on the chip, 1.3-3.6)."""
    cell = smoke_cell("qwen2.5-3b.chat")
    cell.cfg["num_hidden_layers"] = 8
    out = _run(cell, control=True)
    c = out["check"]
    assert out["correct"], c
    assert out["control_correct"] is False, c
    assert c["control_widest_logit_gap"]["value"] \
        > 3 * c["widest_logit_gap"]["value"]


@pytest.mark.parametrize("where", ["checkout", "benchmark files only"])
def test_no_tpu_exits_nonzero_without_a_result(where, tmp_path):
    """With JAX held to the CPU: from the checkout's root, and from a
    directory holding only BENCHMARK.json and the benchmark's files."""
    cwd = ROOT
    if where != "checkout":
        cwd = tmp_path
        shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
        for p in SPEC["paths"]:
            shutil.copytree(ROOT / p, tmp_path / p,
                            ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, *SPEC["command"][1:], "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    if where == "checkout":
        assert "needs 1 TPU chip" in proc.stderr
    assert not any(ln.lstrip().startswith("{")
                   for ln in proc.stdout.splitlines())


def test_recorded_trace_reduces_to_programs_and_gaps():
    r = devtrace.reduce_bytes(gzip.decompress(TRACE.read_bytes()))
    assert 0 < r["busy_s"] < r["window_s"]
    assert {"decode", "prefill", "page_movers"} <= set(r["programs"])
    assert sum(v for _k, v in r["breakdown"]["idle_gaps"]) \
        <= r["window_s"] - r["busy_s"] + 1e-9
