"""Runs one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell, its configuration and its traffic
are looked up by name in ``BENCHMARK.json``. The run refuses to start
(exit 2, no result) unless JAX finds a TPU with as many chips as the cell
asks for. Then it makes the weights and inputs from ``--seed``, warms up
the cell's shapes (set-up), measures for ``--seconds``, reads the peak
memory, frees the program's state and compares what the timed path
produced with the plain reference. The numbers compared go to standard
error as its last lines; the last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and ``checks`` last.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    __package__ = "chipbench"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    return ap.parse_args(argv)


def device_info(chips: int) -> dict:
    """Platform, kind and count of the devices; raises SystemExit unless
    they are TPUs, at least ``chips`` of them."""
    # the TPU runtime logs under /tmp/tpu_logs unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"chipbench: JAX found no device: {e}") from None
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise SystemExit(f"chipbench: no TPU: JAX found {info}")
    if info["count"] < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX found {info}")
    return info


def cache_compiles() -> None:
    """Every program of the run into the checkout's persistent compile
    cache (or ``JAX_COMPILATION_CACHE_DIR``'s), however quickly it
    compiled, so that only a checkout's first run compiles."""
    import jax
    from .program import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def metrics_line(bench, cell: str, res: dict, trace_on: bool, dev: dict) -> dict:
    """The ``metrics`` of the result line: the cell's end-to-end metrics,
    or with tracing its per-layer metrics that found something to read."""
    out = {}
    if trace_on:
        run = dict(res, cell=cell, device=dev)
        for m in bench.per_layer(cell):
            v = bench.reader(m["name"])(run)
            if v is not None and math.isfinite(v):
                out[m["name"]] = {"value": float(v), "unit": m["unit"]}
        return out
    for m in bench.end_to_end(cell):
        v = res["setup_s"] if m["name"] == "setup_s" else res["e2e"].get(m["name"])
        if v is not None and math.isfinite(v):
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def execute(bench, cell_name: str, seed: int, seconds: float, trace_on: bool,
            dev: dict, t_start: float) -> dict:
    """Everything after the device check: the run's result line."""
    from . import compare
    from .drivers import driver

    cell = bench.cell(cell_name)
    c = bench.config(cell["config"])
    t = bench.traffic(cell["traffic"])
    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace_on else None
    try:
        res = driver(t["kind"]).run(c, cell["config"], t, seed, seconds, log_dir)
        res["setup_s"] = res["window"]["start"] - t_start
        if log_dir:
            from . import trace as T
            res["trace"] = T.reduce(T.find_xplane(log_dir))
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)

    ok, checks = compare.judge(res["numbers"], bench.limits(cell_name))
    metrics = metrics_line(bench, cell_name, res, trace_on, dev)
    # a window too short for one whole unit measures nothing
    measured = trace_on or all(m["name"] in metrics for m in bench.end_to_end(cell_name))
    ok = ok and measured and res["failed"] == 0 and res["attempted"] > 0
    device = dict(dev, memory_peak_bytes=res["memory_peak_bytes"])
    line = {"correct": ok, "attempted": res["attempted"], "failed": res["failed"],
            "metrics": metrics, "device": device}
    if trace_on:
        tr = res["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    args = parse(argv)
    from .bench import Bench
    bench = Bench()
    cell = bench.cell(args.workload)
    dev = device_info(int(cell["chips"]))
    cache_compiles()
    line = execute(bench, args.workload, args.seed, args.seconds, bool(args.trace),
                   dev, T_START)
    for ch in line["checks"]:
        print(f"check {ch['name']} = {ch['value']!r} (limit {ch['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
