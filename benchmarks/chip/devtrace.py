"""Host spans around the benchmark's calls, and the reduction of a
profiler trace (``.xplane.pb``) to device busy time, per-program device
time and idle gaps attributed to what the host was doing.

Device planes are named ``/device:TPU:<n>``.  Their ``XLA Modules`` line
holds one event per program run, named after the jitted function
(``jit_decode_step(...)``); their ``XLA Ops`` line holds one event per
operation.  Host spans are the ``TraceAnnotation`` events of the host
plane's threads.  Event times are nanoseconds on one clock for all planes.
"""

from __future__ import annotations

import contextlib
import functools
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

import jax

# Programs by the jitted function's name, as the trace names them.
PROGRAMS = {
    "decode": ("decode_step",),
    "prefill": ("prefill",),
    "page_movers": ("page_gather", "page_scatter", "page_compact"),
}
# Engine methods the traced run wraps in host spans of their own name,
# so that an idle gap inside ``step`` says which part of the step's host
# work it fell in.
ENGINE_SPANS = ("_admit", "_append_with_preemption", "_fault_in",
                "_issue_prefetch", "_drain_prefetches", "_park_prefix",
                "_preempt", "_run_compaction", "_stack_states",
                "_unstack_states")
SPANS = ("step", "submit", "decode", "prefill", "page_gather",
         "page_scatter", "page_compact", "pack_ctx", "cache_stats") \
    + ENGINE_SPANS

_on = False


def span(name: str):
    """A host span while a trace is recorded; nothing otherwise."""
    return jax.profiler.TraceAnnotation(name) if _on \
        else contextlib.nullcontext()


def start(log_dir: str) -> None:
    global _on
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    _on = True


def stop_and_reduce(log_dir: str) -> dict:
    global _on
    jax.profiler.stop_trace()
    _on = False
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under "
                           f"{log_dir}")
    with open(max(files, key=os.path.getmtime), "rb") as f:
        return reduce_bytes(f.read())


def _program(module: str) -> Optional[str]:
    base = re.sub(r"^jit_", "", module.split("(")[0])
    for prog, names in PROGRAMS.items():
        if any(base == n or base.startswith(n + "_") or base.endswith(n)
               for n in names):
            return prog
    return None


@functools.lru_cache(maxsize=None)
def _op_name(hlo: str) -> Optional[str]:
    """'%fusion.175 = bf16[256,64,2,128]' from an op event's HLO text;
    None for a loop or call that contains other op events (its time is
    theirs)."""
    m = re.match(r"(%[\w.\-]+) = (\(?[^{ ]*)", hlo)
    if m is None:
        return hlo[:80]
    if re.search(r"\) (while|conditional|call)\(|^[^=]*= [^ ]+ "
                 r"(while|conditional|call)\(", hlo):
        return None
    return f"{m.group(1)} = {m.group(2)}"


def _union(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def reduce_bytes(xspace: bytes, top: int = 10) -> dict:
    """Busy and window seconds (averaged over the device planes), device
    seconds per program, the top device operations and the longest idle
    gaps by the host span that was open in them, from a serialized
    ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(xspace)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        spans.append((ev.start_ns, ev.start_ns
                                      + ev.duration_ns, ev.name))
    if not devices:
        raise RuntimeError("no TPU device plane in the trace")
    programs: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    busy_s = window_s = 0.0
    gaps: List[Tuple[int, int]] = []
    t_lo = min((s for s, _e, _n in spans), default=None)
    t_hi = max((e for _s, e, _n in spans), default=None)
    for plane in devices:
        iv: List[Tuple[int, int]] = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    prog = _program(ev.name)
                    if prog:
                        programs[prog] = programs.get(prog, 0.0) \
                            + ev.duration_ns / 1e9
            elif line.name == "XLA Ops":
                for ev in line.events:
                    iv.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                    name = _op_name(ev.name)
                    if name:
                        ops[name] = ops.get(name, 0.0) + ev.duration_ns / 1e9
        busy = _union(iv)
        lo = t_lo if t_lo is not None else (busy[0][0] if busy else 0)
        hi = t_hi if t_hi is not None else (busy[-1][1] if busy else 0)
        busy = [(max(s, lo), min(e, hi)) for s, e in busy if e > lo and s < hi]
        busy_s += sum(e - s for s, e in busy) / 1e9
        window_s += (hi - lo) / 1e9
        edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    n = len(devices)
    for k in programs:
        programs[k] /= n
    by_host: Dict[str, float] = {}
    for (a, b), label in zip(gaps, _host_doing(spans, gaps)):
        by_host[label] = by_host.get(label, 0.0) + (b - a) / 1e9 / n
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    top_gaps = sorted(by_host.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_s / n, "window_s": window_s / n,
            "programs": programs,
            "breakdown": {"device_ops": [[k, v / n] for k, v in top_ops],
                          "idle_gaps": [[k, v] for k, v in top_gaps]}}


def _host_doing(spans, gaps) -> List[str]:
    """For each gap, the host spans open at its middle, outermost first:
    'step/_admit', or 'no span' where the host was between calls
    (waiting for an arrival, or in the client loop).  One sweep over the
    spans' edges in time order."""
    edges = sorted([(s, 1, i) for i, (s, _e, _n) in enumerate(spans)]
                   + [(e, 0, i) for i, (_s, e, _n) in enumerate(spans)])
    order = sorted(range(len(gaps)), key=lambda k: gaps[k][0] + gaps[k][1])
    labels = [""] * len(gaps)
    open_: Dict[int, Tuple[int, str]] = {}
    j = 0
    for k in order:
        mid = (gaps[k][0] + gaps[k][1]) // 2
        while j < len(edges) and edges[j][0] <= mid:
            t, opening, i = edges[j]
            if opening:
                open_[i] = (spans[i][0], spans[i][2])
            else:
                open_.pop(i, None)
            j += 1
        labels[k] = "/".join(name for _s, name in sorted(open_.values())) \
            or "no span"
    return labels
