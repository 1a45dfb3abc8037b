"""The readers of the program's own spans (``program_spans.py`` and the
metrics ``sched_self_ms``, ``staging_self_ms``, ``step_serial_host_ms``,
``setup_compile_s``) on synthetic records: what lies in the window counts,
and nothing is read where the program's telemetry module is absent."""
import sys
import types
from typing import NamedTuple, Optional

import jax
import pytest

from chipbench import program_spans as P
from chipbench.bench import Bench

READERS = ["sched_self_ms", "staging_self_ms", "step_serial_host_ms", "setup_compile_s"]
MS = 1_000_000
S = 1_000_000_000


class Record(NamedTuple):
    name: str
    parent: Optional[str]
    step: Optional[int]
    t0_ns: int
    t1_ns: int


def step_records(step: int, base: int, sched_child: bool = False):
    """One training step at ``base`` ns, 1000 ms long: 4 ms of host work
    before its first dispatch, 2 ms after its wait ends."""
    at = lambda name, parent, a, b: Record(name, parent, step, base + int(a * MS),
                                           base + int(b * MS))
    top = "repro.train.step"
    recs = [at("repro.train.schedule", top, 0.1, 0.5),
            at("repro.train.put", "repro.train.stage", 1.0, 1.4),
            at("repro.train.stage", top, 0.5, 1.5),
            at("repro.train.acc_init", top, 1.5, 4.0),
            at("repro.train.dispatch", top, 4.0, 4.2),
            at("repro.train.dispatch", top, 4.3, 4.4),
            at("repro.train.observe", top, 4.4, 4.5),
            at("repro.train.wait", top, 4.5, 998.0),
            at(top, None, 0.0, 1000.0)]
    if sched_child:
        recs.insert(0, at("repro.compile.trace", "repro.train.schedule", 0.2, 0.3))
    return recs


def records():
    """Set-up (compiles, step 0) before a window of 10 s to 13 s holding
    steps 1 and 2 and one late compile; step 3 runs past the window."""
    setup = [Record("repro.compile.trace", "repro.train.dispatch", 0, 1 * S, S + S // 2),
             Record("repro.compile.backend", "repro.train.dispatch", 0, S + 4 * S // 10, 3 * S),
             Record("repro.compile.cache_load", "repro.train.dispatch", 0, 2 * S, 5 * S // 2),
             # ends after the window starts: not set-up
             Record("repro.compile.lower", None, None, 9 * S, 10 * S + 1)]
    return (setup + step_records(0, 5 * S) + step_records(1, 10 * S + S // 2, sched_child=True)
            + step_records(2, 11 * S + S // 2) + step_records(3, 12 * S + S // 2))


RUN = {"window": {"start": 10.0, "seconds": 3.0},
       "device": {"platform": jax.default_backend(), "kind": "test"}}


@pytest.fixture
def program(monkeypatch):
    mod = types.SimpleNamespace(records=records)
    monkeypatch.setitem(sys.modules, P.MODULE, mod)
    return mod


def read(name, run=RUN):
    return Bench().reader(name)(dict(run))


def test_the_window_keeps_its_own_records(program):
    got = P.in_window(RUN)
    assert got and all(10 * S <= r.t0_ns and r.t1_ns <= 13 * S for r in got)
    # step 3 runs past the window's end: its first spans lie inside it,
    # but only whole steps count
    assert 3 in {r.step for r in got}
    by_step = P.steps_in_window(RUN)
    assert sorted(by_step) == [1, 2]
    assert [len(by_step[1]), len(by_step[2])] == [10, 9]


def test_per_step_host_metrics(program):
    # schedule 0.4 ms a step, less step 1's 0.1 ms compile inside it
    assert read("sched_self_ms") == pytest.approx(0.35)
    # stage 1.0 ms a step, the put inside it included
    assert read("staging_self_ms") == pytest.approx(1.0)
    # 4 ms to the first dispatch, 2 ms from the wait's end to the step's
    assert read("step_serial_host_ms") == pytest.approx(6.0)


def test_setup_compile_is_the_union_before_the_window(program):
    # trace 1.0-1.5 s and backend 1.4-3.0 s (the cache load inside it):
    # 2.0 s; the lowering that ends inside the window is not set-up
    assert read("setup_compile_s") == pytest.approx(2.0)


def test_nothing_to_read_without_the_module(monkeypatch):
    monkeypatch.delitem(sys.modules, P.MODULE, raising=False)
    assert [read(n) for n in READERS] == [None] * 4


def test_nothing_to_read_from_another_backend_or_without_a_window(program):
    tpu = dict(RUN, device={"platform": "no-such-backend", "kind": "test"})
    assert [read(n, tpu) for n in READERS] == [None] * 4
    assert [read(n, {"device": RUN["device"]}) for n in READERS] == [None] * 4
    empty = dict(RUN, window={"start": 100.0, "seconds": 1.0})
    assert [read(n, empty) for n in READERS[:3]] == [None] * 3


def test_covered_counts_overlaps_once():
    assert P.covered_ns([(0, 10), (5, 12), (20, 25), (21, 22)]) == 17
    assert P.covered_ns([]) == 0


def test_the_metrics_are_declared():
    spec = {m["name"]: m for m in Bench().spec["per_layer"]}
    for name in READERS:
        assert spec[name]["source"] == "program_span"
    assert spec["setup_compile_s"]["moves"] == "setup_s"
    assert set(spec["setup_compile_s"]["workloads"]) == {"granite-3-8b.train.seq4k",
                                                          "granite-3-8b.serve.b32"}
