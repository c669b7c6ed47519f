"""Spans and counters of the program, on the profiler's clock.

``span(name)`` times one phase of the system. It opens a
``jax.profiler.TraceAnnotation`` of the same name, so a profile shows the
phase beside the device's work, and keeps one record in a bounded
in-memory ring (the oldest records drop out). Records nest through a
per-thread stack and inherit their parent's ids (``step=``); work handed
to another thread names its parent's id explicitly. A span takes its
counts once (``s.count(bytes=...)``): there is never a record per chunk,
blob or leaf. Spans are always on and cost a few microseconds each.

The compile counter listens to JAX's monitoring events: seconds spent
tracing, lowering and in the backend compile (which also covers a load
from the persistent compile cache), and the compiles that were not
persistent-cache hits.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from dataclasses import astuple, dataclass, field
from typing import Any, Dict, Iterator, List, Optional

import jax

RING = 4096

_lock = threading.Lock()
_ring: "collections.deque[Span]" = collections.deque(maxlen=RING)
_next_id = itertools.count(1)
_local = threading.local()


@dataclass
class Span:
    """One timed phase: ``perf_counter`` start and end, the thread it ran
    on, its parent's id, ids such as ``step`` and its counts."""
    id: int
    name: str
    parent: Optional[int]
    thread: str
    ids: Dict[str, Any] = field(default_factory=dict)
    counts: Dict[str, float] = field(default_factory=dict)
    start: float = 0.0
    end: Optional[float] = None

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def count(self, **counts: float) -> None:
        self.counts.update(counts)


def _stack() -> List[Span]:
    if not hasattr(_local, "stack"):
        _local.stack = []
    return _local.stack


@contextlib.contextmanager
def span(name: str, parent: Optional[int] = None, **ids: Any
         ) -> Iterator[Span]:
    """Time the block as ``name``. The parent is the span open on this
    thread, whose ids the new span inherits, unless ``parent`` names
    another span's id (work submitted to another thread)."""
    stack = _stack()
    if parent is None and stack:
        parent, ids = stack[-1].id, {**stack[-1].ids, **ids}
    s = Span(next(_next_id), name, parent, threading.current_thread().name,
             ids)
    stack.append(s)
    try:
        with jax.profiler.TraceAnnotation(name):
            s.start = time.perf_counter()
            try:
                yield s
            finally:
                s.end = time.perf_counter()
    finally:
        stack.pop()
        with _lock:
            _ring.append(s)


def records(name: Optional[str] = None, since: Optional[float] = None,
            until: Optional[float] = None) -> List[Span]:
    """Finished spans, oldest first: those named ``name`` (all where
    None) that started at or after ``since`` and ended by ``until``."""
    with _lock:
        out = list(_ring)
    return [s for s in out if (name is None or s.name == name)
            and (since is None or s.start >= since)
            and (until is None or s.end <= until)]


def summary(since: Optional[float] = None, until: Optional[float] = None
            ) -> Dict[str, Dict[str, float]]:
    """Per span name: how many (``n``), their total ``seconds`` and the
    sum of each count (no count is named ``n`` or ``seconds``)."""
    out: Dict[str, Dict[str, float]] = {}
    for s in records(since=since, until=until):
        t = out.setdefault(s.name, {"n": 0, "seconds": 0.0})
        t["n"] += 1
        t["seconds"] += s.seconds
        for k, v in s.counts.items():
            t[k] = t.get(k, 0) + v
    return out


# ------------------------------------------------------------- compiles
@dataclass(frozen=True)
class Compiles:
    """The compile counter at one moment; differences give an interval's.
    ``compile_s`` and ``backend`` include loads from the persistent cache;
    ``compiles`` leaves those out."""
    trace_s: float = 0.0
    lower_s: float = 0.0
    compile_s: float = 0.0
    backend: int = 0
    cache_hits: int = 0

    @property
    def compiles(self) -> int:
        return self.backend - self.cache_hits

    @property
    def seconds(self) -> float:
        return self.trace_s + self.lower_s + self.compile_s

    def __sub__(self, other: "Compiles") -> "Compiles":
        return Compiles(*(a - b for a, b in zip(astuple(self),
                                                astuple(other))))


_DURATIONS = {"/jax/core/compile/jaxpr_trace_duration": 0,
              "/jax/core/compile/jaxpr_to_mlir_module_duration": 1,
              "/jax/core/compile/backend_compile_duration": 2}
_counter = [0.0, 0.0, 0.0, 0, 0]


def _on_duration(event: str, duration: float, **_) -> None:
    i = _DURATIONS.get(event)
    if i is not None:
        with _lock:
            _counter[i] += duration
            if i == 2:
                _counter[3] += 1


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        with _lock:
            _counter[4] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def compiles() -> Compiles:
    with _lock:
        return Compiles(*_counter)
