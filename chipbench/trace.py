"""Host spans and the reduction of a profiler trace to device numbers.

Spans are ``jax.profiler.TraceAnnotation`` ranges named ``chipbench.<x>``,
opened by the benchmark's own files around its calls into each layer of
the program. When the profiler is on they land in the trace's host plane,
on the host's clock; when it is off they cost a few microseconds.

``reduce`` reads one ``.xplane.pb`` with ``jax.profiler.ProfileData``:
  * device operations: the ``XLA Ops`` line of each ``/device:TPU:<n>``
    plane; jitted programs: its ``XLA Modules`` line;
  * the device clock is put on the host's by matching each program run
    (``run_id``) with the host's ``CompleteCallbacks`` event for it: the
    host cannot learn of a run before it ends, so the offset is the least
    of (callback start - run end);
  * busy time is the union of the device operations' intervals inside the
    window span (``chipbench.window``), averaged over the devices;
  * each idle gap is charged to the innermost benchmark span open at its
    middle, or to ``other``;
  * the device operations are listed by time, leaving out those (loops,
    calls) that hold other operations.
"""
from __future__ import annotations

import glob
import os
import re
from contextlib import contextmanager
from typing import Dict, List, Tuple

import jax

PREFIX = "chipbench."
WINDOW = PREFIX + "window"


def span(name: str):
    """A host span ``chipbench.<name>``."""
    return jax.profiler.TraceAnnotation(PREFIX + name)


def wrap(obj, attr: str, name: str) -> bool:
    """Wraps the callable ``obj.<attr>`` (an instance attribute or a bound
    method) in span ``name``. Returns False, and changes nothing, where
    ``obj`` has no such attribute: the metric that reads the span is then
    left out of the run's line."""
    fn = getattr(obj, attr, None)
    if not callable(fn):
        return False

    def wrapped(*a, **kw):
        with span(name):
            return fn(*a, **kw)

    setattr(obj, attr, wrapped)
    return True


@contextmanager
def profiled(log_dir: str):
    """Profiler on for the block; the python tracer stays off, so that the
    host runs at the speed it runs untraced, apart from the spans."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


_HASH = re.compile(r"\(\d+\)$")
_OP = re.compile(r"^%?([^\s=]+)")


def module_name(event_name: str) -> str:
    """'jit_serve_step(1755...)' -> 'jit_serve_step'."""
    return _HASH.sub("", event_name)


def op_name(event_name: str) -> str:
    """'%fusion.12 = bf16[...] fusion(...)' -> 'fusion.12'."""
    m = _OP.match(event_name)
    return m.group(1) if m else event_name


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def reduce(path: str, top: int = 10, prefix: str = PREFIX) -> dict:
    """Device numbers of one trace, all times in seconds.

    Returns busy_s and window_s; per jitted program its device seconds
    and run count inside the window (``modules``); the host spans whose
    names start with ``prefix`` (``spans``: name -> list of durations);
    the ``top`` device operations by time; the idle gaps summed by the
    span charged with them."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host_spans: List[Tuple[str, float, float]] = []
    callbacks: Dict[int, float] = {}
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(prefix):
                        host_spans.append((ev.name[len(prefix):], ev.start_ns,
                                           ev.start_ns + ev.duration_ns))
                    elif ev.name == "CompleteCallbacks":
                        rid = _stats(ev).get("run_id")
                        if rid is not None:
                            callbacks[int(rid)] = min(callbacks.get(int(rid), ev.start_ns),
                                                      ev.start_ns)
        elif plane.name.startswith("/device:TPU:"):
            mods, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        mods.append((module_name(ev.name), ev.start_ns,
                                     ev.duration_ns, _stats(ev).get("run_id")))
                elif line.name == "XLA Ops":
                    ops.extend((op_name(ev.name), ev.start_ns, ev.duration_ns)
                               for ev in line.events)
            devices.append((mods, ops))

    offsets = []
    for mods, _ in devices:
        d = [callbacks[int(r)] - (s + dur) for _, s, dur, r in mods
             if r is not None and int(r) in callbacks]
        offsets.append(min(d) if d else 0.0)

    win = [(a, b) for n, a, b in host_spans if n == "window"]
    if win:
        w0, w1 = win[0]
    else:
        starts = [s + off for (mods, _), off in zip(devices, offsets) for _, s, _, _ in mods]
        ends = [s + d + off for (mods, _), off in zip(devices, offsets) for _, s, d, _ in mods]
        if not starts:
            raise ValueError(f"{path}: no device program ran")
        w0, w1 = min(starts), max(ends)

    inner = sorted(((n, a, b) for n, a, b in host_spans if n != "window"),
                   key=lambda s: s[1])
    busy_total, gaps, modules, op_time = 0.0, {}, {}, {}
    for (mods, ops), off in zip(devices, offsets):
        iv = []
        ops = sorted(ops, key=lambda o: (o[1], -o[2]))
        for i, (name, s, dur) in enumerate(ops):
            a, b = max(s + off, w0), min(s + dur + off, w1)
            if b > a:
                iv.append((a, b))
                # a loop or call that holds other operations is not listed
                # itself: its operations are
                if i + 1 == len(ops) or ops[i + 1][1] >= s + dur:
                    op_time[name] = op_time.get(name, 0.0) + (b - a)
        busy = _union(iv)
        busy_total += sum(b - a for a, b in busy)
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                mid = 0.5 * (a + b)
                who = [n for n, s0, s1 in inner if s0 <= mid <= s1]
                key = who[-1] if who else "other"
                gaps[key] = gaps.get(key, 0.0) + (b - a)
        for name, s, dur, _ in mods:
            if w0 <= s + off and s + dur + off <= w1:
                m = modules.setdefault(name, {"seconds": 0.0, "runs": 0})
                m["seconds"] += dur * 1e-9
                m["runs"] += 1
    nd = max(len(devices), 1)
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")
    spans: Dict[str, List[float]] = {}
    for n, a, b in inner:
        if w0 <= a and b <= w1:
            spans.setdefault(n, []).append((b - a) * 1e-9)
    by = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {
        "devices": len(devices),
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_total / nd * 1e-9,
        "modules": {k: {"seconds": v["seconds"] / nd, "runs": v["runs"] // nd
                        if v["runs"] >= nd else v["runs"]}
                    for k, v in modules.items()},
        "spans": spans,
        "device_ops": [[k, v / nd * 1e-9] for k, v in by(op_time)],
        "idle_gaps": [[k, v / nd * 1e-9] for k, v in by(gaps)],
        "clock_offset_s": [o * 1e-9 for o in offsets],
    }
