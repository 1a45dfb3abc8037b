"""The harness on the CPU: lookups by name, the result line, refusal
without a TPU, metrics that find nothing to read, and a cell added as
new files alone."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import run as R
from chipbench import trace as T
from chipbench.bench import Bench
from chipbench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
DEV = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
LIMITS = {"loss_gap": 0.02, "grad_norm_gap": 0.05, "change_norm_gap": 0.05,
          "served_logit_gap": 0.05}


def test_every_name_resolves():
    b = Bench()
    for w in b.spec["workloads"]:
        cell = b.cell(w["name"])
        c, t = b.config(cell["config"]), b.traffic(cell["traffic"])
        assert c["num_hidden_layers"] > 0 and t["kind"] in ("train", "serve")
        assert set(b.limits(w["name"]))
        e2e = {m["name"] for m in b.end_to_end(w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = b.per_layer(w["name"])
        assert layer and all(m["moves"] in e2e for m in layer)
    for m in b.spec["per_layer"]:
        assert callable(b.reader(m["name"]))
    with pytest.raises(KeyError):
        b.cell("no-such-cell")


def test_config_files_hold_what_runs():
    b = Bench()
    for entry in b.spec["configs"]:
        c = b.config(entry["name"])
        assert c["source"] == entry["source"]
        assert set(entry["reduced"]) == set(c["reduced"])
        for key, (published, run) in c["reduced"].items():
            assert c[key] == run and published > run


def test_result_line_untraced():
    line = R.execute(tiny.TinyBench(LIMITS), "granite-3-8b.train.seq4k", 7, 0.3,
                     False, DEV, 0.0)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert set(line["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert line["device"]["kind"] == "TPU v5 lite" and "memory_peak_bytes" in line["device"]
    assert {c["name"] for c in line["checks"]} == {"loss_gap", "grad_norm_gap",
                                                  "change_norm_gap"}


@pytest.mark.parametrize("cell,found", [
    ("granite-3-8b.train.seq4k",
     {"sched_host_ms", "grain_accumulate_ms", "train_mfu", "device_idle_share.train"}),
    ("granite-3-8b.serve.b32",
     {"decode_step_ms", "decode_step_roofline", "serve_mfu", "device_idle_share.serve"})])
def test_result_line_traced(monkeypatch, cell, found):
    """The traced line, with the trace reduction replaced by that of a
    small trace recorded on the chip (a CPU run has no TPU plane). That
    trace has no staging span and no prefill program: their metrics read
    null and are left out of the line."""
    reduced = T.reduce(str(TESTDATA / "two_programs.xplane.pb"), prefix="bench.")
    monkeypatch.setattr(T, "reduce", lambda path: reduced)
    monkeypatch.setattr(T, "find_xplane", lambda d: d)
    line = R.execute(tiny.TinyBench(LIMITS), cell, 7, 0.3, True, DEV, 0.0)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device",
                          "breakdown", "checks"]
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    assert set(line["metrics"]) == found
    assert found <= {m["name"] for m in tiny.TinyBench(LIMITS).per_layer(cell)}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert 0 < len(line["breakdown"]["device_ops"]) <= 10
    assert {k for k, _ in line["breakdown"]["idle_gaps"]} <= {
        "schedule", "dispatch", "readback", "other"}


def test_missing_span_source_reads_null():
    class NoScheduler:
        pass
    assert T.wrap(NoScheduler(), "_schedule", "schedule") is False
    b = Bench()
    run = {"trace": {"spans": {}, "modules": {}, "window_s": 1.0, "busy_s": 0.5,
                     "devices": 1}, "window": {"units": 3}}
    assert b.reader("sched_host_ms")(run) is None
    assert b.reader("staging_host_ms")(run) is None
    assert b.reader("grain_accumulate_ms")(run) is None
    assert b.reader("device_idle_share.train")(run) == pytest.approx(50.0)


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "granite-3-8b.train.seq4k", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_a_new_cell_is_new_files_only(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as files
    and BENCHMARK.json entries run without touching an existing file."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    new = tmp_path / "chipbench"
    c = dict(tiny.dense(), program_arch="granite-3-8b")
    (new / "configs" / "toy-dense.json").write_text(json.dumps(c))
    (new / "traffic" / "train.toy.json").write_text(json.dumps(tiny.train_mix()))
    (new / "limits" / "toy-dense.train.toy.json").write_text(json.dumps({"limits": LIMITS}))
    (new / "metrics" / "toy_steps.py").write_text(
        "def read(run):\n    return float(run['window']['units'])\n")
    spec["configs"].append({"name": "toy-dense", "source": "test", "reduced": [],
                            "file": "chipbench/configs/toy-dense.json", "why": "toy"})
    spec["workloads"].append({"name": "toy-dense.train.toy", "config": "toy-dense",
                              "traffic": "train.toy", "chips": 1, "why": "toy"})
    spec["end_to_end"][1]["workloads"].append("toy-dense.train.toy")
    spec["per_layer"].append({"name": "toy_steps", "unit": "steps", "better": "higher",
                              "source": "host_clock", "layer": "trainer",
                              "moves": "train_tokens_per_s",
                              "workloads": ["toy-dense.train.toy"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    b = Bench(tmp_path)
    assert [m["name"] for m in b.per_layer("toy-dense.train.toy")] == ["toy_steps"]
    line = R.execute(b, "toy-dense.train.toy", 3, 0.3, False, DEV, 0.0)
    assert line["correct"] and set(line["metrics"]) == {"setup_s", "train_tokens_per_s"}
    run = {"window": {"units": 4}}
    assert b.reader("toy_steps")(run) == 4.0
