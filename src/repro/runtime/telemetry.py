"""Host spans and counters of the program, on the profiler's clock.

``span(name)`` times a block of host code and keeps a record
``(name, parent, step, t0_ns, t1_ns)`` in a bounded in-memory ring. The
parent is the innermost span open on the same thread; a span given a
``step`` marks one step, and the spans inside it inherit its number. Each
span also opens a ``jax.profiler.TraceAnnotation`` of the same name (a
``StepTraceAnnotation`` for a step), so in a captured profile the spans
sit on the host plane beside the device's operations. Names read
``repro.<layer>.<what>``. ``record`` adds to a counter and also keeps a
zero-length record of the amount, under the open span and its step, so
that a reader can take the count of each step.

JAX's compile events become records too, ``repro.compile.trace``,
``.lower``, ``.backend`` (a compile or a persistent-cache load) and
``.cache_load``, each ending when the event arrives and starting its
duration earlier, as children of whatever span was open: which step or
dispatch compiled. Backend compiles, cache hits and cache misses are also
counters.

Telemetry time is for operators and the benchmark. The planner, the
estimator and the virtual fleet clock never read it.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Dict, Iterable, Iterator, List, NamedTuple, Optional, Tuple

import jax

# hemt-lint: disable=HL003  telemetry's one clock; no simulated result reads it
_now_ns = time.perf_counter_ns

MAXLEN = 65_536


class Record(NamedTuple):
    name: str
    parent: Optional[str]
    step: Optional[int]
    t0_ns: int
    t1_ns: int
    value: Optional[int] = None      # a counted amount; None for a span


_records: "collections.deque[Record]" = collections.deque(maxlen=MAXLEN)
_counters: Dict[str, int] = collections.Counter()
_lock = threading.Lock()
_local = threading.local()


def _open() -> List[Tuple[str, Optional[int]]]:
    """This thread's stack of open spans, (name, step) each."""
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


@contextlib.contextmanager
def span(name: str, step: Optional[int] = None) -> Iterator[None]:
    """Times the block as span ``name``; ``step`` marks a whole step."""
    stack = _open()
    parent, inherited = stack[-1] if stack else (None, None)
    if step is None:
        ann = jax.profiler.TraceAnnotation(name)
        step = inherited
    else:
        ann = jax.profiler.StepTraceAnnotation(name, step_num=step)
    stack.append((name, step))
    with ann:
        t0 = _now_ns()
        try:
            yield
        finally:
            t1 = _now_ns()
            stack.pop()
            _records.append(Record(name, parent, step, t0, t1))


def count(name: str, n: int = 1) -> None:
    """Adds ``n`` to the process-wide counter ``name``."""
    with _lock:
        _counters[name] += n


def record(name: str, n: int) -> None:
    """Adds ``n`` to the counter ``name`` and keeps a zero-length record of
    ``n`` at this moment, under the open span and its step."""
    count(name, n)
    stack = _open()
    parent, step = stack[-1] if stack else (None, None)
    t = _now_ns()
    _records.append(Record(name, parent, step, t, t, n))


def records() -> List[Record]:
    """The ring buffer's records, oldest first, each appended as it closed."""
    return list(_records)


def covered_ns(intervals: Iterable[Tuple[int, int]]) -> int:
    """Length of the union of ``(t0, t1)`` intervals."""
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def summary() -> dict:
    """Per span name its count, total and self milliseconds (self: the
    span's time less the part of it that its child spans cover), and the
    counters (the records of counted amounts are not spans)."""
    spans: Dict[str, dict] = {}
    children: Dict[str, List[Tuple[int, int]]] = collections.defaultdict(list)
    for r in records():
        if r.value is not None:
            continue
        s = spans.setdefault(r.name, {"count": 0, "total_ms": 0.0})
        s["count"] += 1
        s["total_ms"] += (r.t1_ns - r.t0_ns) / 1e6
        if r.parent is not None:
            children[r.parent].append((r.t0_ns, r.t1_ns))
    for name, s in spans.items():
        s["self_ms"] = s["total_ms"] - covered_ns(children.get(name, ())) / 1e6
    with _lock:
        counters = dict(_counters)
    return {"spans": spans, "counters": counters}


def reset() -> None:
    """Forgets every record and counter (for tests)."""
    _records.clear()
    with _lock:
        _counters.clear()


_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "repro.compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "repro.compile.lower",
    "/jax/core/compile/backend_compile_duration": "repro.compile.backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "repro.compile.cache_load",
}
_COUNTED_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}


def _on_duration(event: str, duration: float, **_: object) -> None:
    name = _COMPILE_EVENTS.get(event)
    if name is None:
        return
    t1 = _now_ns()
    stack = _open()
    parent, step = stack[-1] if stack else (None, None)
    _records.append(Record(name, parent, step, t1 - int(duration * 1e9), t1))
    if name == "repro.compile.backend":
        count("compile.backend")


def _on_event(event: str, **_: object) -> None:
    name = _COUNTED_EVENTS.get(event)
    if name is not None:
        count(name)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
