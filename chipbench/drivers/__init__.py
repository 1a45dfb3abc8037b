"""One driver per kind of traffic (the ``kind`` key of a traffic file).

A driver's ``run(c, name, t, seed, seconds, log_dir)`` sets the program up
from the seed, measures for ``seconds`` (traced when ``log_dir`` is given),
reads the peak memory, frees the program's state and compares what the
timed path produced with the reference. It returns a dict with the keys
``e2e``, ``attempted``, ``failed``, ``numbers``, ``memory_peak_bytes``,
``window`` and ``trace``.
"""
from __future__ import annotations

import gc
import importlib
import statistics
import time
from contextlib import nullcontext

import jax

from .. import trace as T


def driver(kind: str):
    return importlib.import_module(f"{__name__}.{kind}")


def peak_bytes() -> int:
    """Peak bytes in use on the fullest device."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def measure(unit, seconds: float, log_dir):
    """Runs ``unit()`` back to back for ``seconds``.

    ``unit`` does one whole unit of work (a step, a round), returns once its
    results are on the host, and returns a record. Untraced, a unit that
    ends past the window's end is not counted, and time runs to the end of
    the last unit counted. Traced, every unit run is inside the window span
    and counted, since the per-layer numbers are per unit.
    Returns (records counted, (start, end) on perf_counter, units run)."""
    records, ran = [], 0
    ctx = T.profiled(log_dir) if log_dir else nullcontext()
    with ctx:
        with T.span("window"):
            start = time.perf_counter()
            deadline, end = start + seconds, start
            while time.perf_counter() < deadline:
                rec = unit()
                ran += 1
                now = time.perf_counter()
                if now <= deadline or log_dir:
                    records.append(rec)
                    end = now
    return records, (start, end), ran


def free():
    gc.collect()


def p95(values) -> float:
    return statistics.quantiles(values, n=20, method="inclusive")[-1]
