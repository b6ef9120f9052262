"""Host spans the program marks on itself, always on.

``span(name, **attrs)`` enters ``jax.profiler.TraceAnnotation`` (so a
profiler trace shows the span on the device trace's clock) and also
appends ``(name, parent, t0_ns, t1_ns, attrs)`` to a bounded in-memory
record (``time.perf_counter_ns``; ``parent`` is the enclosing span of
the same thread). The record covers every call, traced or not, so a rare
slow epoch can be found after the fact; ``drain()`` takes it.
``watch_gc()`` adds each generation-2 collection as a ``python.gc`` span.
"""
from __future__ import annotations

import collections
import gc
import threading
import time

import jax

MAXLEN = 8192
GC_SPAN = "python.gc"

_record = collections.deque(maxlen=MAXLEN)
_local = threading.local()


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class span:
    """Context manager: one recorded host span (see the module doc)."""

    __slots__ = ("name", "attrs", "parent", "t0", "ann")

    def __init__(self, name: str, **attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        st = _stack()
        self.parent = st[-1] if st else None
        st.append(self.name)
        self.ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        _stack().pop()
        _record.append((self.name, self.parent, self.t0, t1, self.attrs))
        return False


def drain() -> list:
    """Every recorded span since the last drain, oldest first (at most
    ``MAXLEN``: older ones are dropped); empties the record."""
    out = []
    while _record:
        out.append(_record.popleft())
    return out


_gc_open: list = []


def _on_gc(phase: str, info: dict):
    if info.get("generation") != 2:
        return
    if phase == "start":
        _gc_open.append(span(GC_SPAN).__enter__())
    elif _gc_open:
        s = _gc_open.pop()
        s.attrs = {"collected": info.get("collected", 0)}
        s.__exit__(None, None, None)


def watch_gc():
    """Record every generation-2 collection as a ``python.gc`` span
    (idempotent)."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
