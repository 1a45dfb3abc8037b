"""The program's own spans and compile records
(``repro.runtime.telemetry``), read in the process that ran the cell once
the driver has returned.

The benchmark does not import the program for this: where the process
never loaded the module, as at a commit that lacks it, there is nothing to
read and the readers return None. So they do where the process ran on
another backend than the device the run names: such spans time another
machine. A record's clock is ``time.perf_counter``, the clock of the run's
window.
"""
from __future__ import annotations

import sys

MODULE = "repro.runtime.telemetry"
STEP = "repro.train.step"
COMPILE = "repro.compile."


def all_records(run: dict):
    """Every record the process kept, or None."""
    mod = sys.modules.get(MODULE)
    platform = (run.get("device") or {}).get("platform")
    if mod is None or platform is None:
        return None
    import jax
    if jax.default_backend() != platform:
        return None
    return mod.records()


def window_ns(run: dict):
    """(start, end) of the run's window in nanoseconds, or None."""
    w = run.get("window") or {}
    if "start" not in w or "seconds" not in w:
        return None
    t0 = int(round(w["start"] * 1e9))
    return t0, t0 + int(round(w["seconds"] * 1e9))


def in_window(run: dict):
    """The records that lie inside the run's window, or None."""
    recs, win = all_records(run), window_ns(run)
    if recs is None or win is None:
        return None
    return [r for r in recs if win[0] <= r.t0_ns and r.t1_ns <= win[1]]


def covered_ns(intervals) -> int:
    """Length of the union of (t0, t1) intervals."""
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total, end = total + b - a, b
        elif b > end:
            total, end = total + b - end, b
    return total


def steps_in_window(run: dict):
    """The records of the training steps that lie whole inside the run's
    window, by step number, or None."""
    recs = in_window(run)
    if recs is None:
        return None
    whole = {r.step for r in recs if r.name == STEP}
    by_step: dict = {}
    for r in recs:
        if r.step in whole:
            by_step.setdefault(r.step, []).append(r)
    return by_step


def per_step_ms(run: dict, ns_of) -> float | None:
    """``ns_of(records of the window's whole steps)`` in milliseconds per
    step, or None where the window holds no step or ``ns_of`` finds
    nothing (returns None)."""
    by_step = steps_in_window(run)
    if not by_step:
        return None
    ns = ns_of([r for rs in by_step.values() for r in rs])
    return ns / 1e6 / len(by_step) if ns is not None else None
