"""Spans and counters of the serving layer.

A span times one piece of the engine's host work and is recorded twice:

- as a ``jax.profiler.TraceAnnotation``: an event on the profiler's host
  plane, on the device trace's clock, named as the span and carrying its
  keyword metadata (``rid``, ``step``, ...) as event stats;
- in the engine's ``EngineStats``: per span name a count (``span_n``),
  total ns (``span_ns``) and self ns (``span_self_ns``: the total less
  the time its child spans cover).

Durations come from ``time.perf_counter_ns``; a thread-local stack of the
open spans gives each span its parent.  One ``jax.monitoring`` listener,
registered once per process by :func:`listen`, counts each backend
compile (or load from the persistent compilation cache) under the
innermost open span (``EngineStats.compiles``): that names the call that
compiled.

Spans time host work only and never wait for the device.  Where the host
does wait (reading a token), the wait is a span of its own,
``device.wait``.  With no profiler recording a span costs one inactive
``TraceAnnotation``, two clock reads and three dict updates, 2.5-4 µs of
host time, so no span sits in a per-page loop.
"""

from __future__ import annotations

import functools
import threading
import time

import jax

# Wait samples each ring of EngineStats keeps (the newest).
WAIT_SAMPLES = 65536

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_listening = False
_listen_lock = threading.Lock()


class _Local(threading.local):
    def __init__(self):
        self.stack: list = []           # the thread's open spans


_local = _Local()


class Span:
    """``with Span(stats, name, **meta) as sp:`` times the block into
    ``stats`` and marks it in any profiler trace.  ``sp.t0`` is its
    start (``perf_counter_ns``); ``sp.ns`` its duration once closed;
    ``sp.set(**meta)`` adds metadata known only inside the block."""

    __slots__ = ("stats", "name", "t0", "ns", "_ann", "_child")

    def __init__(self, stats, name: str, **meta):
        self.stats = stats
        self.name = name
        self._ann = jax.profiler.TraceAnnotation(name, **meta)
        self._child = 0
        self.t0 = self.ns = 0

    def set(self, **meta) -> None:
        self._ann.set_metadata(**meta)

    def __enter__(self) -> "Span":
        _local.stack.append(self)
        self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.ns = ns = time.perf_counter_ns() - self.t0
        self._ann.__exit__(*exc)
        stack = _local.stack
        stack.pop()
        if stack:
            stack[-1]._child += ns
        st, name = self.stats, self.name
        st.span_n[name] = st.span_n.get(name, 0) + 1
        st.span_ns[name] = st.span_ns.get(name, 0) + ns
        st.span_self_ns[name] = st.span_self_ns.get(name, 0) + ns \
            - self._child
        return False


def spanned(name: str):
    """Method decorator: the whole call is a span ``name`` timed into
    ``self.stats``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(self, *args, **kwargs):
            with Span(self.stats, name):
                return fn(self, *args, **kwargs)
        return inner
    return wrap


def _on_duration(event: str, _secs: float, **_kw) -> None:
    if event != _COMPILE_EVENT:
        return
    stack = _local.stack
    if stack:
        sp = stack[-1]
        c = sp.stats.compiles
        c[sp.name] = c.get(sp.name, 0) + 1


def listen() -> None:
    """Register the compile listener (once per process)."""
    global _listening
    with _listen_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listening = True
