"""Benchmark driver: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run                 # all, CSV to stdout
  PYTHONPATH=src python -m benchmarks.run fig17           # substring filter
  PYTHONPATH=src python -m benchmarks.run --json          # + BENCH_sim.json
  PYTHONPATH=src python -m benchmarks.run --json out.json
  PYTHONPATH=src python -m benchmarks.run --check         # CI perf gate

``--json`` persists the perf-trajectory rows — simulator engine throughput
at 1k/10k/100k tasks (benchmarks.bench_sim_engine) and the kernel rows
(benchmarks.bench_kernels) — so successive PRs can diff BENCH_sim.json.

``--check [PATH]`` re-runs only the gated sections — the sim_engine,
speculation_io, faults, resident, serving, and batched rows — and exits
non-zero if any timed row
regressed by more than the threshold against the committed baseline (or
vanished from the fresh run) — the ROADMAP CI gate.  The
threshold defaults to 2x and can be overridden per environment —
``--threshold 4`` beats the ``BENCH_CHECK_THRESHOLD`` env var beats the
default — because hardcoded headroom is wrong for noisy shared CI
runners.  Derived-only rows (us_per_call == 0) are skipped; a PR that
intentionally changes the row set regenerates the baseline with
``--json`` in the same change.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

MODULES = [
    "benchmarks.bench_claim1",
    "benchmarks.bench_fig5_network",
    "benchmarks.bench_fig7_adaptive",
    "benchmarks.bench_fig8_provisioned",
    "benchmarks.bench_fig13_burstable",
    "benchmarks.bench_fig17_kmeans",
    "benchmarks.bench_fig18_pagerank",
    "benchmarks.bench_hemt_dp",
    "benchmarks.bench_speculation",
    "benchmarks.bench_speculation_io",
    "benchmarks.bench_faults",
    "benchmarks.bench_resident",
    "benchmarks.bench_serving",
    "benchmarks.bench_oa_hemt",
    "benchmarks.bench_sim_engine",
    "benchmarks.bench_batched",
    "benchmarks.bench_kernels",
]

# modules whose rows land in the --json perf-trajectory file
JSON_SECTIONS = {
    "benchmarks.bench_speculation": "speculation",
    "benchmarks.bench_speculation_io": "speculation_io",
    "benchmarks.bench_faults": "faults",
    "benchmarks.bench_resident": "resident",
    "benchmarks.bench_serving": "serving",
    "benchmarks.bench_oa_hemt": "oa_hemt",
    "benchmarks.bench_sim_engine": "sim",
    "benchmarks.bench_batched": "batched",
    "benchmarks.bench_kernels": "kernels",
}

# sections the --check gate re-runs live and compares against the baseline
GATED_SECTIONS = {
    "sim": "benchmarks.bench_sim_engine",
    "speculation_io": "benchmarks.bench_speculation_io",
    "faults": "benchmarks.bench_faults",
    "resident": "benchmarks.bench_resident",
    "serving": "benchmarks.bench_serving",
    "batched": "benchmarks.bench_batched",
}

DEFAULT_THRESHOLD = 2.0


def resolve_threshold(cli: "float | None" = None) -> float:
    """--check regression threshold: CLI flag > BENCH_CHECK_THRESHOLD env
    var > the 2x default.  A malformed, non-positive, or NaN value is a
    configuration error, not something to silently paper over — a zero or
    NaN threshold would make the gate always-fail or always-pass."""
    if cli is not None:
        return _valid_threshold(float(cli), f"--threshold {cli}")
    env = os.environ.get("BENCH_CHECK_THRESHOLD")
    if env is None or env == "":
        return DEFAULT_THRESHOLD
    try:
        val = float(env)
    except ValueError:
        raise SystemExit(
            f"BENCH_CHECK_THRESHOLD={env!r} is not a number") from None
    return _valid_threshold(val, f"BENCH_CHECK_THRESHOLD={env!r}")


def _valid_threshold(val: float, label: str) -> float:
    if val != val:                            # NaN: every comparison False
        raise SystemExit(f"{label} is NaN")
    if val <= 0.0:
        raise SystemExit(f"{label} must be positive")
    return val


def compare_rows(baseline_rows, fresh_rows,
                 threshold: float = DEFAULT_THRESHOLD):
    """Regression messages for fresh sim_engine rows vs. a baseline.

    A baseline row regresses when its fresh ``us_per_call`` exceeds
    ``threshold`` times the committed one, or when it is missing from the
    fresh run (renames must regenerate the baseline in the same PR).
    Derived-only rows (``us_per_call`` <= 0) and rows that exist only in
    the fresh run (newly added) are ignored.
    """
    fresh = {r["name"]: r for r in fresh_rows}
    msgs = []
    for base in baseline_rows:
        base_us = base.get("us_per_call", 0.0)
        if base_us <= 0.0:
            continue
        got = fresh.get(base["name"])
        if got is None:
            msgs.append(f"{base['name']}: missing from fresh run")
        elif got["us_per_call"] > threshold * base_us:
            msgs.append(f"{base['name']}: {got['us_per_call']:.0f}us vs "
                        f"baseline {base_us:.0f}us "
                        f"(>{threshold:g}x regression)")
    return msgs


def run_check(baseline_path: str, fresh_rows=None,
              threshold: "float | None" = None) -> int:
    """The ``--check`` CI gate: fresh rows of every gated section
    (``GATED_SECTIONS``: sim_engine + speculation_io + faults +
    resident + serving + batched) vs. the
    committed
    baseline.  ``fresh_rows`` can be injected for tests — either a dict
    ``{section: [row dicts]}`` (only the given sections are compared) or
    a plain list of ``BenchRow.as_dict`` dicts, compared as the ``sim``
    section; by default the gated benchmarks run live.
    ``threshold=None`` resolves via :func:`resolve_threshold` (env var or
    the 2x default)."""
    threshold = resolve_threshold(threshold)
    try:
        with open(baseline_path) as fh:
            baseline = json.load(fh)
    except OSError as exc:
        print(f"cannot read baseline {baseline_path}: {exc}", file=sys.stderr)
        return 1
    except json.JSONDecodeError as exc:
        print(f"baseline {baseline_path} is not valid JSON: {exc}",
              file=sys.stderr)
        return 1
    if fresh_rows is None:
        fresh_by = {}
        for section, modname in GATED_SECTIONS.items():
            mod = __import__(modname, fromlist=["rows"])
            fresh_by[section] = [r.as_dict() for r in mod.rows()]
    elif isinstance(fresh_rows, dict):
        fresh_by = fresh_rows
    else:
        fresh_by = {"sim": fresh_rows}
    msgs = []
    for section, fresh in fresh_by.items():
        msgs.extend(compare_rows(baseline.get(section, []), fresh,
                                 threshold))
    for m in msgs:
        print(f"REGRESSION {m}", file=sys.stderr)
    if msgs:
        print(f"{len(msgs)} gated row(s) regressed vs {baseline_path}",
              file=sys.stderr)
        return 1
    n_timed = sum(1 for section in fresh_by
                  for r in baseline.get(section, [])
                  if r.get("us_per_call", 0.0) > 0.0)
    print(f"OK: {n_timed} timed gated row(s) within {threshold:g}x "
          f"of {baseline_path}")
    return 0


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("filter", nargs="?", default="",
                        help="substring filter on module names")
    parser.add_argument("--json", nargs="?", const="BENCH_sim.json",
                        default=None, metavar="PATH",
                        help="also write perf-trajectory rows as JSON "
                             "(default path: BENCH_sim.json; path must end "
                             "in .json — write `run.py <filter> --json`, a "
                             "bare word after --json is taken as the path)")
    parser.add_argument("--check", nargs="?", const="BENCH_sim.json",
                        default=None, metavar="PATH",
                        help="re-run the gated rows (sim_engine + "
                             "speculation_io + faults + resident + "
                             "serving + batched) and exit non-zero on "
                             "us_per_call regressions beyond the "
                             "threshold vs the given baseline JSON "
                             "(default: BENCH_sim.json)")
    parser.add_argument("--threshold", type=float, default=None,
                        metavar="X",
                        help="--check regression threshold (default: "
                             "BENCH_CHECK_THRESHOLD env var, else "
                             f"{DEFAULT_THRESHOLD:g}x) — loaded CI runners "
                             "want more headroom than a quiet laptop")
    args = parser.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.check is not None:
        raise SystemExit(run_check(args.check, threshold=args.threshold))
    if args.json is not None and not args.json.endswith(".json"):
        parser.error(f"--json path {args.json!r} must end in .json "
                     f"(did you mean `run.py {args.json} --json`?)")

    print("name,us_per_call,derived")
    failures = 0
    sections: dict = {name: [] for name in JSON_SECTIONS.values()}
    for modname in MODULES:
        if args.filter and args.filter not in modname:
            continue
        try:
            mod = __import__(modname, fromlist=["rows"])
            mod_rows = list(mod.rows())
            for row in mod_rows:
                print(row.csv(), flush=True)
            section = JSON_SECTIONS.get(modname)
            if section is not None:
                sections[section].extend(r.as_dict() for r in mod_rows)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{modname},ERROR,", flush=True)
            traceback.print_exc()
    if args.json is not None:
        # never clobber the tracked trajectory file with a partial view:
        # only write when every JSON-section module ran and none failed
        ran_all = all(not args.filter or args.filter in m for m in JSON_SECTIONS)
        if failures:
            print(f"not writing {args.json}: {failures} module(s) failed",
                  file=sys.stderr)
        elif not ran_all:
            print(f"not writing {args.json}: filter {args.filter!r} excludes "
                  "perf-trajectory modules", file=sys.stderr)
        else:
            with open(args.json, "w") as fh:
                json.dump({"schema": 1, **sections}, fh, indent=1)
                fh.write("\n")
            print(f"wrote {args.json}", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
