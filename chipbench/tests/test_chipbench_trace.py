"""The trace reduction on a small trace recorded on a TPU v5e: two jitted
programs (``grain_accumulate``, a matmul pair; ``serve_step``, a reduction)
run three times each, with host spans ``bench.schedule`` (a 2 ms sleep),
``bench.dispatch`` and ``bench.readback`` around them."""
from pathlib import Path

import pytest

from chipbench import trace as T

TRACE = str(Path(__file__).resolve().parents[1] / "testdata" / "two_programs.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return T.reduce(TRACE, prefix="bench.")


def test_programs_and_runs(red):
    assert red["devices"] == 1
    mods = red["modules"]
    assert set(mods) == {"jit_grain_accumulate", "jit_serve_step"}
    assert mods["jit_grain_accumulate"]["runs"] == 3
    assert mods["jit_serve_step"]["runs"] == 3
    # 193.4 us and 12.7 us a run on the device
    assert mods["jit_grain_accumulate"]["seconds"] == pytest.approx(3 * 193.4e-6, rel=2e-3)
    assert mods["jit_serve_step"]["seconds"] == pytest.approx(3 * 12.73e-6, rel=2e-3)


def test_busy_is_the_union_of_operations(red):
    # the copy-start/copy-done pair of the prefetch lies inside a run; the
    # union of each run's operations is within 10 ns of the run itself
    runs = sum(m["seconds"] for m in red["modules"].values())
    assert red["busy_s"] == pytest.approx(runs, rel=1e-3)
    assert red["busy_s"] < red["window_s"]


def test_device_clock_is_put_on_the_host_clock(red):
    # each run ends on the device before the host's callback for it:
    # the least gap is 1.7355 ms on this trace
    assert red["clock_offset_s"][0] == pytest.approx(1.7355e-3, rel=1e-3)


def test_idle_gaps_are_charged_to_host_spans(red):
    gaps = dict(red["idle_gaps"])
    assert set(gaps) <= {"schedule", "dispatch", "readback", "other"}
    assert sum(gaps.values()) == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    # the two 2 ms sleeps between the three rounds hold most of the idle time
    assert max(gaps, key=gaps.get) == "schedule"
    assert gaps["schedule"] > 0.5 * sum(gaps.values())
    assert red["spans"]["schedule"] and len(red["spans"]["dispatch"]) >= 4


def test_names():
    assert T.module_name("jit_serve_step(17551692580032284764)") == "jit_serve_step"
    assert T.op_name("%fusion.12 = bf16[8]{0} fusion(%a)") == "fusion.12"
