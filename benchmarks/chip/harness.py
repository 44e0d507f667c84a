"""One benchmark cell: load, warm up, measure a window, check, report.

The cell's configuration, traffic mix, limits and per-layer readers are
files found by the names in ``BENCHMARK.json``, and what a model family
gives (sizes, ``ModelConfig``, weights, the work of each call) is a
module of ``families/`` named by the configuration's ``family`` key;
nothing here knows a particular cell or family.  The system under test
is ``ServingEngine`` driven through its entry, ``submit`` and ``step``,
with compiled Pallas page movers and the engine's defaults except for
what the mix's ``engine`` block sets.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

import cost
import devtrace
import traffic

CHIP = pathlib.Path(__file__).resolve().parent
ROOT = CHIP.parents[1]
FAMILIES = CHIP / "families"
sys.path.insert(0, str(ROOT / "src"))

from repro.configs.base import PoolGeometry  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.serving.engine import Request, ServingEngine  # noqa: E402

MOVERS = ("page_gather", "page_scatter", "page_compact")


# ------------------------------------------------------------ the cell


@dataclasses.dataclass
class Cell:
    name: str
    cfg: dict                 # the configuration file, as run
    mix: dict                 # the traffic file
    limits: dict              # the limits file
    end_to_end: List[str]
    per_layer: List[str]
    units: Dict[str, str]     # of every metric in BENCHMARK.json
    chips: int

    @property
    def family(self):
        return family_of(self.cfg)

    @property
    def dims(self):
        return self.family.dims(self.cfg)


@functools.lru_cache(maxsize=None)
def _module(path: pathlib.Path):
    """The module in ``path``, loaded once: a family's dims are the static
    argument of jitted calls, so their class must stay one class."""
    spec = importlib.util.spec_from_file_location(
        f"{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family_of(cfg: dict):
    """The module ``families/<family>.py`` of the configuration's
    ``family`` key, dense where it has none (``families/dense.py`` says
    what a family module gives)."""
    path = FAMILIES / f"{cfg.get('family', 'dense')}.py"
    if not path.is_file():
        raise SystemExit(f"no model family module {path}")
    return _module(path)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    w = next((w for w in spec["workloads"] if w["name"] == name), None)
    if w is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    c = next(c for c in spec["configs"] if c["name"] == w["config"])
    return Cell(
        name=name, cfg=json.loads((root / c["file"]).read_text()),
        mix=json.loads((CHIP / "traffic" / f"{w['traffic']}.json")
                       .read_text()),
        limits=json.loads((CHIP / "limits" / f"{name}.json").read_text()),
        end_to_end=[m["name"] for m in spec["end_to_end"]
                    if _applies(m, name)],
        per_layer=[m["name"] for m in spec["per_layer"]
                   if _applies(m, name)],
        units={m["name"]: m["unit"]
               for m in spec["end_to_end"] + spec["per_layer"]},
        chips=int(w["chips"]))


def served_model(cell: Cell, seed: int):
    """The program's ``ModelConfig`` for ``cell`` and its weights from
    ``seed``, as the cell's family makes them."""
    fam, dm = cell.family, cell.dims
    return (fam.model_config(cell.name, dm, cell.mix["engine"]["max_seq"]),
            fam.model_weights(seed, dm))


def engine_kwargs(mix: dict) -> dict:
    e = mix["engine"]
    geo = PoolGeometry(page_tokens=e["page_tokens"],
                       frame_pages=e["frame_pages"])
    kw = dict(geometry=geo, max_batch=e["max_batch"], max_seq=e["max_seq"],
              manager_kind="mosaic", use_pallas=True)
    if "pool_pages" in e:
        # The engine sizes its pool as pages_for(max_seq, max_batch) over
        # the oversubscription, so a pool of N pages is that ratio.
        kw["oversubscription"] = (geo.pages_for(e["max_seq"], e["max_batch"])
                                  / e["pool_pages"])
    if "prefix_capacity_pages" in e:
        kw["prefix_capacity_pages"] = e["prefix_capacity_pages"]
    return kw


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` is set), holding every compile
    however short or small, so that a second run of a cell compiles
    nothing.  Returns its directory."""
    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Compiles:
    """Backend compilations JAX reports, with their seconds."""

    def __init__(self):
        self.n, self.s = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.s += secs


# ------------------------------------------------------------ the window


@dataclasses.dataclass
class Sent:
    plan: traffic.Planned
    req: Request
    sched: float                       # when it was due, window seconds
    sent: float                        # when submit() returned
    times: List[float] = dataclasses.field(default_factory=list)


def drive(eng: ServingEngine, plan: traffic.Plan, mix: dict,
          seconds: float, rid0: int = 0,
          step_s: Optional[List[float]] = None) -> List[Sent]:
    """Offer ``plan`` to the engine for ``seconds`` and stamp every token
    with the host clock when ``step`` hands it back (each step's seconds
    go to ``step_s``)."""
    clock = time.perf_counter
    closed = mix["loop"] == "closed"
    clients = mix.get("clients", 0)
    sent: List[Sent] = []
    live: List[Sent] = []
    next_of = {}                       # closed loop: client -> plan index
    t0 = clock()

    def submit(p: traffic.Planned, sched: float) -> None:
        r = Request(rid=rid0 + p.idx, tenant=p.tenant, priority=p.priority,
                    prompt=p.prompt, max_new=p.max_new)
        with devtrace.span("submit"):
            eng.submit(r)
        s = Sent(p, r, sched, clock() - t0)
        sent.append(s)
        live.append(s)

    if closed:
        # Every client sends its first request before the window opens,
        # and the step that admits them all (one prefill each) runs then:
        # the window measures the loop running, not its start.
        for c in range(clients):
            submit(plan.upto(c), 0.0)
            next_of[c] = c + clients
        eng.step()
        t0 = clock()
        for s in sent:
            s.sched = s.sent = -1.0
            s.times = [0.0] * len(s.req.out)
    i = 0
    while True:
        now = clock() - t0
        if now >= seconds:
            break
        if not closed:
            while i < len(plan) and plan[i].at <= now:
                submit(plan[i], plan[i].at)
                i += 1
        if not (eng.queue or eng.active or eng.preempted):
            if closed or i >= len(plan):
                break
            time.sleep(max(0.0, min(plan[i].at, seconds) - now))
            continue
        with devtrace.span("step"):
            eng.step()
        t = clock() - t0
        if step_s is not None:
            step_s.append(t - now)
        for s in list(live):
            s.times.extend([t] * (len(s.req.out) - len(s.times)))
            if s.req.done:
                live.remove(s)
                if closed:
                    c = s.plan.idx % clients
                    if t < seconds:
                        submit(plan.upto(next_of[c]), t)
                        next_of[c] += clients
    return sent


def _pct(values, q, scale: float = 1.0) -> Optional[float]:
    """The q-th percentile times ``scale``; None for no values."""
    return scale * float(np.percentile(np.asarray(values), q)) \
        if values else None


def end_to_end(sent: List[Sent], seconds: float) -> Dict[str, float]:
    """Client-side latencies and rate of the window (seconds → ms)."""
    ttft, gaps, tokens = [], [], 0
    for s in sent:
        if s.sched >= seconds:
            continue
        inside = [t for t in s.times if 0.0 < t <= seconds]
        tokens += len(inside)
        if s.sched >= 0.0:
            # No first token by the window's end: missing, at its age then.
            ttft.append((inside[0] if inside else seconds) - s.sched)
        gaps.extend(np.diff([t for t in s.times if t <= seconds]).tolist())
        if inside and not s.req.done:
            gaps.append(seconds - inside[-1])
    return {"ttft_p50_ms": _pct(ttft, 50, 1e3),
            "ttft_p90_ms": _pct(ttft, 90, 1e3),
            "itl_p95_ms": _pct(gaps, 95, 1e3),
            "tokens_per_s": tokens / seconds,
            "_requests": len(ttft), "_gaps": len(gaps)}


# ------------------------------------------------------------ warm-up


def warm_up(eng: ServingEngine, cell: Cell, seed: int) -> None:
    """Compile every shape the window uses: prefill at each padded prompt
    length of the mix, decode at max_batch, the page movers at each page
    count up to a sequence's pages (on the device, as the engine calls
    them, with nothing sent to the host), and a few seconds of the mix
    itself under a seed of its own (left unfinished: the engine is thrown
    away)."""
    mix, ptok = cell.mix, eng.geo.page_tokens
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    rng = np.random.default_rng(seed ^ 0x5EED)
    lengths = [min(max(lo, n * ptok), hi)
               for n in range(-(-lo // ptok), -(-hi // ptok) + 1)]
    for i, n in enumerate(lengths):
        eng.submit(Request(rid=(1 << 29) + i, tenant=0, prompt=rng.integers(
            0, cell.dims.vocab, n).astype(np.int32), max_new=2))
    eng.run_until_drained()
    if eng.pools is not None:
        for n in range(1, eng.mpps + 1):
            warm_movers(eng, n)
    plan = traffic.plan(mix, seed ^ 0xA5A5A5, cell.dims.vocab,
                        mix["warmup_seconds"])
    drive(eng, plan, mix, mix["warmup_seconds"], rid0=1 << 30)


def warm_movers(eng: ServingEngine, n: int) -> None:
    """Gather ``n`` pages of each pool and scatter them back in place, by
    the calls ``ServingEngine._gather_pages`` and ``_scatter_pages`` make,
    on device arrays alone."""
    idx = eng._dev(np.arange(n, dtype=np.int32))
    for pool in eng.pools:
        pages = jax.vmap(lambda p: kops.page_gather(
            p, idx, use_pallas=eng.use_pallas))(pool)
        out = jax.vmap(lambda p, pg: kops.page_scatter(
            p, idx, pg, use_pallas=eng.use_pallas))(pool, pages)
        # One scatter at a time: each copies the pool.
        jax.block_until_ready(out)
        del pages, out


# ------------------------------------------------------------ tracing


def instrument(eng: ServingEngine):
    """Wrap the engine's model calls, the page movers and the engine's
    host-side parts in host spans, and log what each model call and page
    mover was given (read after the window).  Returns the log and a
    function that takes the page-mover wrappers off again."""
    log: Dict[str, list] = {"decode": [], "prefill": [],
                            **{m: [] for m in MOVERS}}

    def spanned(fn, name, note=None):
        def inner(*a, **kw):
            if note is not None:
                log[name].append(note(*a, **kw))
            with devtrace.span(name):
                return fn(*a, **kw)
        return inner

    eng._decode_jit = spanned(eng._decode_jit, "decode",
                              lambda p, toks, pos, pools, ctx, st: ctx.ntok)
    eng._prefill_jit = spanned(
        eng._prefill_jit, "prefill",
        lambda p, batch, pools, ctx, last_pos=None, prefix_kv=None,
        prefix_len=0: (last_pos, prefix_len))
    kops_orig = {m: getattr(kops, m) for m in MOVERS}
    pages = {"page_gather": lambda idx, **kw: idx.shape[0],
             "page_scatter": lambda idx, pages, **kw: idx.shape[0],
             "page_compact": lambda src, dst, **kw: src.shape[0]}
    # Each mover is called on every pool in turn, K and then V (the
    # engine's ``_gather_pages``, ``_scatter_pages`` and ``_compact``), so
    # a call's place in that cycle names its pool; the one layer's pool it
    # is given must have that pool's shape.
    shapes = [p.shape[1:] for p in eng.pools or ()]

    def moved(m):
        def note(pool, *a, **kw):
            i = len(log[m]) % len(shapes)
            if pool.shape != shapes[i]:
                raise RuntimeError(
                    f"{m} call {len(log[m])} was given a pool of shape "
                    f"{pool.shape}, not pool {i}'s {shapes[i]}")
            return i, pages[m](*a, **kw)
        return note

    for m in MOVERS:
        setattr(kops, m, spanned(kops_orig[m], m, moved(m)))
    for name in devtrace.ENGINE_SPANS:
        if hasattr(eng, name):
            setattr(eng, name, spanned(getattr(eng, name), name))
    eng.cache.pack_ctx = spanned(eng.cache.pack_ctx, "pack_ctx")
    eng.cache.stats = spanned(eng.cache.stats, "cache_stats")

    def restore():
        for m, f in kops_orig.items():
            setattr(kops, m, f)
    return log, restore


def call_work(log: Dict[str, list], fam, dm, page_tokens: int) -> dict:
    """Operations and bytes of the logged calls, by the cost functions of
    the family ``fam``."""
    dec_f = dec_b = 0.0
    for ntok in log["decode"]:
        ctx = np.asarray(ntok).reshape(np.shape(ntok)[0], -1).sum(axis=1)
        ctx = ctx[ctx > 0]
        dec_f += fam.decode_flops(dm, ctx)
        dec_b += fam.decode_bytes(dm, ctx)
    pre_f = 0.0
    for last_pos, prefix_len in log["prefill"]:
        pre_f += fam.prefill_flops(dm, int(np.asarray(last_pos)[0]) + 1
                                   + prefix_len)
    # One kernel call moves pages of one pool, all layers: each page read
    # once and written once.
    page = fam.pool_page_bytes(dm, page_tokens)
    mov_b = sum(2.0 * n * page[pool] for m in MOVERS for pool, n in log[m])
    return {"decode_flops": dec_f, "decode_bytes": dec_b,
            "prefill_flops": pre_f, "mover_bytes": mov_b,
            "decode_calls": len(log["decode"]),
            "prefill_calls": len(log["prefill"])}


# ------------------------------------------------------------ the check


def sample(sent: List[Sent], cell: Cell, seed: int):
    """A sample of the finished requests, drawn from the seed, as the
    reference's inputs: the request with the most served tokens and,
    first among the rest, requests that were preempted (their KV went to
    the host tier and back).  Shapes are fixed by the cell, so the
    reference compiles once: (tokens [n, max_seq], positions read
    [n, max_out], served tokens [1, n, max_out], valid [n, max_out],
    the requests)."""
    n = cell.limits["sample_requests"]
    done = [s for s in sent if s.req.done]
    rng = np.random.default_rng(seed ^ 0xC0FFEE)
    longest = max(done, key=lambda s: (len(s.req.out), s.req.rid))
    rest = [s for s in done if s is not longest]
    rng.shuffle(rest)
    rest.sort(key=lambda s: s.req.preemptions == 0)      # preempted first
    pick = [longest] + rest[:n - 1]
    T = cell.mix["engine"]["max_seq"]
    P = cell.mix["output_tokens"]["max"]
    tokens = np.zeros((n, T), np.int32)
    pos = np.zeros((n, P), np.int32)
    served = np.zeros((1, n, P), np.int32)
    valid = np.zeros((n, P), bool)
    for i in range(n):
        r = pick[i % len(pick)].req
        seq = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
        tokens[i, :len(seq)] = seq
        k = len(r.out)
        pos[i, :k] = len(r.prompt) - 1 + np.arange(k)
        served[0, i, :k] = r.out
        valid[i, :k] = i < len(pick)
    return tokens, pos, served, valid, pick


def check(sent: List[Sent], cell: Cell, seed: int, score: Callable,
          control: bool = False) -> Dict[str, dict]:
    """Compare a sample of the finished requests with the reference.

    For each served token it reads the gap by which the reference's
    logit of that token lies below the reference's best; the widest gap
    is compared with its limit.  With ``control``, the reference is also
    run in the precision below the served one, and the same gap is read
    for the token that it puts first at each of those positions."""
    if not any(s.req.done for s in sent):
        return {"finished_requests": {"value": 0, "limit": 1}}
    tokens, pos, served, valid, pick = sample(sent, cell, seed)
    cand = served
    if control:
        _, _, low = score(seed, cell.dims, tokens, pos, served, quant="fp8")
        cand = np.concatenate([served, low[None]])
    best, picked, _ = score(seed, cell.dims, tokens, pos, cand)
    gap = np.where(valid, best - picked, 0.0)
    lim = cell.limits
    wrong = {pick[i].req.rid for i in range(len(pick))
             if gap[0, i].max() > lim["widest_logit_gap"]}
    out = {
        "widest_logit_gap": {"value": float(gap[0].max()),
                             "limit": lim["widest_logit_gap"]},
        "compared_tokens": {"value": int(valid.sum()),
                            "limit": lim["min_compared_tokens"]},
        "compared_preempted": {
            "value": int(sum(s.req.preemptions > 0 for s in pick)),
            "limit": lim.get("min_compared_preempted", 0)},
        "_wrong_requests": len(wrong)}
    if control:
        out["control_widest_logit_gap"] = {
            "value": float(gap[1].max()), "limit": lim["widest_logit_gap"]}
    return out


def control_passed(checks: Dict[str, dict]) -> bool:
    """The verdict ``passed`` gives the control: its widest gap in place
    of the program's, every other number as the program's run read it."""
    return passed(dict(checks,
                       widest_logit_gap=checks["control_widest_logit_gap"]))


def passed(checks: Dict[str, dict]) -> bool:
    ok = True
    for name, c in checks.items():
        if name.startswith(("control_", "_")):
            continue
        if name == "widest_logit_gap":
            ok &= c["value"] <= c["limit"]
        else:                          # counts that must reach their limit
            ok &= c["value"] >= c["limit"]
    return bool(ok)


# ------------------------------------------------------------ readers


def read_metric(name: str, ctx: dict) -> Optional[float]:
    v = _module(CHIP / "metrics" / f"{name}.py").read(ctx)
    return None if v is None else float(v)


# ------------------------------------------------------------ one run


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        score: Callable, device_kind: Optional[str] = None,
        on_engine: Optional[Callable] = None, control: bool = False) -> dict:
    """One run of ``cell``: returns the result line's fields.

    ``score`` is the reference's scoring function; ``on_engine`` lets a
    test reach the window's engine before the window opens."""
    dev = jax.devices()[0]
    kind = device_kind or dev.device_kind
    memory: List[list] = []

    def mem(stage: str) -> None:
        st = getattr(dev, "memory_stats", lambda: None)() or {}
        memory.append([stage, st.get("bytes_in_use", 0),
                       st.get("peak_bytes_in_use", 0)])

    pk = cost.peaks(kind) if trace else None
    compiles = Compiles()
    dm = cell.dims
    e_kw = engine_kwargs(cell.mix)
    mcfg, params = served_model(cell, seed)
    mem("weights")
    warm = ServingEngine(mcfg, params=params, **e_kw)
    warm_up(warm, cell, seed)
    mem("warm-up")
    del warm
    gc.collect()
    eng = ServingEngine(mcfg, params=params, **e_kw)
    mem("window engine")
    if on_engine is not None:
        on_engine(eng)
    plan = traffic.plan(cell.mix, seed, dm.vocab, seconds)
    log, restore = instrument(eng) if trace else (None, None)
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    n0 = compiles.n
    jax.block_until_ready(eng.pools)
    setup_s = time.perf_counter() - t_start
    if trace:
        devtrace.start(tdir)
    step_s: List[float] = []
    sent = drive(eng, plan, cell.mix, seconds, step_s=step_s)
    jax.block_until_ready(eng.pools)
    mem("window")
    reduced = None
    if trace:
        reduced = devtrace.stop_and_reduce(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        restore()
    in_window = compiles.n - n0
    stats = dataclasses.replace(eng.stats)
    peak = memory[-1][2]            # the process's peak, read after the window
    e2e = end_to_end(sent, seconds)
    ctx = {"cell": cell, "dims": dm, "sent": sent, "seconds": seconds,
           "stats": stats, "e2e": e2e, "compiles_in_window": in_window,
           "trace": reduced, "peaks": pk,
           "work": (call_work(log, cell.family, dm,
                              e_kw["geometry"].page_tokens)
                    if trace else None)}
    del eng, params
    gc.collect()
    checks = check(sent, cell, seed, score, control)
    if trace:
        names = cell.per_layer
        values = {n: read_metric(n, ctx) for n in names}
    else:
        names = cell.end_to_end
        values = dict(e2e, setup_s=setup_s)
    metrics = {n: {"value": values[n], "unit": cell.units[n]}
               for n in names if values.get(n) is not None}
    device = {"platform": dev.platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    failed = checks.pop("_wrong_requests", 0)
    out = {"correct": passed(checks), "attempted": e2e["_requests"],
           "failed": failed,
           "metrics": metrics, "device": device}
    if control:
        out["control_correct"] = control_passed(checks)
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = reduced["breakdown"]
    out["check"] = checks
    out["_info"] = {"setup_s": setup_s, "compiles_setup": n0,
                    "compile_s": compiles.s, "compiles_in_window": in_window,
                    "requests": e2e["_requests"], "gaps": e2e["_gaps"],
                    "finished": sum(s.req.done for s in sent),
                    "swaps_out": stats.swaps_out, "faults": stats.faults,
                    "decode_steps": stats.decode_steps,
                    "step_s_p50_max": [_pct(step_s, 50),
                                       max(step_s, default=0.0)],
                    "memory": memory}
    return out

