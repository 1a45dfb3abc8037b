"""hemt-lint (repro.analysis): per-rule fixture snippets, waiver
semantics, the CLI, and the repo self-check gate (ISSUE 10).

Each rule gets positive (flagged), negative (clean), and waiver cases as
in-memory fixture files; the virtual path drives rule scoping exactly as
it does on disk.  The self-check test at the bottom is the tier-1 gate:
the committed tree must lint clean.
"""
import json
import textwrap

from repro.analysis import (Finding, Rule, all_rules, get_rule,
                            lint_source, parse_waivers, self_check)
from repro.analysis.lint import lint_paths, main

CORE = "src/repro/core/fixture.py"
ENGINE = "src/repro/core/engine.py"
BATCHED = "src/repro/core/batched.py"
KERNEL = "src/repro/kernels/fixture.py"
RUNTIME = "src/repro/runtime/fixture.py"
MODELS = "src/repro/models/fixture.py"


def codes(source, path=CORE, select=None):
    src = textwrap.dedent(source)
    return [f.code for f in lint_source(src, path, select).findings]


def run(source, path=CORE, select=None):
    return lint_source(textwrap.dedent(source), path, select)


# ---------------------------------------------------------------------------
# registry / protocol
# ---------------------------------------------------------------------------

def test_registry_has_the_six_rules_sorted():
    got = [r.code for r in all_rules()]
    assert got == sorted(got)
    assert {"HL001", "HL002", "HL003", "HL004", "HL005",
            "HL006"} <= set(got)


def test_rules_satisfy_the_protocol():
    for rule in all_rules():
        assert isinstance(rule, Rule)
        assert rule.description
        assert get_rule(rule.code) is rule


# ---------------------------------------------------------------------------
# HL001 frozen-spec
# ---------------------------------------------------------------------------

UNFROZEN_SPEC = """
    from dataclasses import dataclass

    @dataclass
    class PullSpec:
        n_tasks: int = 0
"""

def test_hl001_unfrozen_root_spec_flagged():
    assert codes(UNFROZEN_SPEC) == ["HL001"]


def test_hl001_frozen_spec_clean():
    assert codes("""
        from dataclasses import dataclass
        from typing import Tuple

        @dataclass(frozen=True)
        class PullSpec:
            works: Tuple[float, ...] = ()
    """) == []


def test_hl001_unhashable_field_flagged():
    out = run("""
        from dataclasses import dataclass, field
        from typing import List
        import numpy as np

        @dataclass(frozen=True)
        class StaticSpec:
            works: List[float] = field(default_factory=list)
            grid: np.ndarray = None
    """)
    assert [f.code for f in out.findings] == ["HL001", "HL001"]
    assert "works" in out.findings[0].message
    assert "grid" in out.findings[1].message


def test_hl001_suffix_convention_and_closure():
    # *Trace matches by suffix; Inner is pulled in via the field
    # annotation closure and must itself be frozen
    out = run("""
        from dataclasses import dataclass
        from typing import Optional

        @dataclass
        class Inner:
            x: int = 0

        @dataclass(frozen=True)
        class ReplayTrace:
            inner: Optional[Inner] = None
    """)
    assert [f.code for f in out.findings] == ["HL001"]
    assert "Inner" in out.findings[0].message


def test_hl001_non_spec_dataclass_and_tests_exempt():
    mutable_report = """
        from dataclasses import dataclass
        from typing import List

        @dataclass
        class StageReport:
            rows: List[float] = None
    """
    assert codes(mutable_report) == []                  # not a spec name
    assert codes(UNFROZEN_SPEC, "tests/test_x.py") == []  # tests exempt


# ---------------------------------------------------------------------------
# HL002 seeded-rng
# ---------------------------------------------------------------------------

def test_hl002_legacy_and_stdlib_and_unseeded_flagged():
    out = run("""
        import random
        import numpy as np
        from numpy.random import seed

        def sample(xs):
            np.random.seed(0)
            random.shuffle(xs)
            rng = np.random.default_rng()
            return rng
    """)
    got = [f.code for f in out.findings]
    assert got == ["HL002"] * 4


def test_hl002_seeded_generator_clean():
    assert codes("""
        import numpy as np

        def _rng(seed: int) -> np.random.Generator:
            return np.random.default_rng(seed)

        def jitter(seed, n):
            return np.random.default_rng(int(seed)).normal(size=n)
    """) == []


def test_hl002_scope_is_core_runtime_workloads():
    legacy = """
        import numpy as np
        def f():
            return np.random.rand(3)
    """
    assert codes(legacy, RUNTIME) == ["HL002"]
    assert codes(legacy, "src/repro/workloads/fixture.py") == ["HL002"]
    assert codes(legacy, MODELS) == []      # models/ draws via jax.random keys


def test_hl002_jax_random_exempt():
    assert codes("""
        import jax

        def init(key):
            return jax.random.split(key, 2)
    """) == []


# ---------------------------------------------------------------------------
# HL003 wall-clock
# ---------------------------------------------------------------------------

def test_hl003_time_datetime_flagged():
    out = run("""
        import time
        import datetime
        from time import perf_counter
        from datetime import datetime as dt

        def stamp():
            return (time.time(), perf_counter(), dt.now(),
                    datetime.datetime.utcnow())
    """)
    # perf_counter is flagged at its from-import; the other three at use
    assert [f.code for f in out.findings] == ["HL003"] * 4


def test_hl003_sim_clock_and_benchmarks_exempt():
    assert codes("""
        def advance(clock: float, dt: float) -> float:
            return clock + dt
    """) == []
    wall = """
        import time
        def bench():
            return time.time()
    """
    assert codes(wall, "benchmarks/bench_x.py") == []
    assert codes(wall, "tests/test_x.py") == []


# ---------------------------------------------------------------------------
# HL004 float-eq
# ---------------------------------------------------------------------------

def test_hl004_float_literal_and_annotation_flagged():
    out = run("""
        def solve(a: float, b, w):
            if a == b:                 # annotated param
                return 1
            return (w != 0.0)          # float literal
    """)
    assert [f.code for f in out.findings] == ["HL004", "HL004"]


def test_hl004_dataclass_field_attr_flagged():
    assert codes("""
        from dataclasses import dataclass

        @dataclass(frozen=True)
        class TaskSpec:
            weight: float = 0.0

        def route(t, u):
            return t.weight == u.weight
    """) == ["HL004"]
    # engine spec float fields are known across files
    assert codes("""
        def route(t, m):
            return t.io_mb != m
    """, ENGINE) == ["HL004"]


def test_hl004_tolerant_and_int_compares_clean():
    assert codes("""
        EPS = 1e-9

        def close(a: float, b: float) -> bool:
            return abs(a - b) <= EPS

        def count_eq(n: int) -> bool:
            return n == 0
    """) == []


def test_hl004_scope_is_core_only():
    src = """
        def f(a: float):
            return a == 0.5
    """
    assert codes(src, RUNTIME) == []
    assert codes(src, CORE) == ["HL004"]


# ---------------------------------------------------------------------------
# HL005 tracer-safety
# ---------------------------------------------------------------------------

def test_hl005_python_if_on_traced_value_flagged():
    out = run("""
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
    """, KERNEL)
    assert [f.code for f in out.findings] == ["HL005"]
    assert "if" in out.findings[0].message


def test_hl005_item_cast_and_data_dep_shapes_flagged():
    out = run("""
        import jax
        import jax.numpy as jnp

        def outer(xs):
            def step(carry, x):
                v = float(x)                 # concretizing cast
                idx = jnp.nonzero(carry)     # data-dependent shape
                hit = jnp.where(carry > 0)   # one-arg where
                return carry, x.item()       # .item()
            return jax.lax.scan(step, 0.0, xs)
    """, BATCHED)
    assert sorted(f.code for f in out.findings) == ["HL005"] * 4


def test_hl005_static_args_and_untraced_clean():
    assert codes("""
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("mode",))
        def f(x, mode):
            if mode == "fast":           # static_argnames -> python value
                return x * 2
            return x

        def kernel(ref, *, n_chunks: int):
            if n_chunks > 1:             # kw-only params are static
                return ref
            return ref

        def plain(x):
            if x > 0:                    # never traced: no entry point
                return x
            return -x
    """, KERNEL) == []


def test_hl005_partial_bound_kernel_traced():
    # the ssd_scan idiom: partial(kernel, ...) handed to pallas_call
    assert codes("""
        import functools
        from jax.experimental import pallas as pl

        def _kernel(x_ref, o_ref):
            if x_ref[0] > 0:
                o_ref[0] = 1.0

        def launch(x):
            k = functools.partial(_kernel)
            return pl.pallas_call(k, grid=(1,))(x)
    """, KERNEL) == ["HL005"]


def test_hl005_scope_is_kernels_and_batched():
    src = """
        import jax

        @jax.jit
        def f(x):
            return x.item()
    """
    assert codes(src, CORE) == []          # core/fixture.py: out of scope
    assert codes(src, BATCHED) == ["HL005"]


# ---------------------------------------------------------------------------
# HL006 arg-mutation
# ---------------------------------------------------------------------------

def test_hl006_param_stores_flagged():
    out = run("""
        import numpy as np

        def _closed_form_static(speeds, works):
            works[0] = 0.0
            speeds += 1.0
            works.sort()
            return works

        def batched_closed_pull(works):
            wk = np.asarray(works)       # asarray aliases, taint survives
            wk[0] = 1.0
            return wk
    """, ENGINE)
    assert [f.code for f in out.findings] == ["HL006"] * 4


def test_hl006_copy_and_locals_clean():
    assert codes("""
        import numpy as np

        def _closed_form_static(speeds, works):
            works = np.array(works)      # fresh copy: taint cleared
            works[0] = 0.0
            counts = np.zeros(3)
            counts[1] += 1               # local, never parameter storage
            return works, counts

        def helper_not_a_solver(xs):
            xs[0] = 1                    # outside the solver prefixes
            return xs
    """, ENGINE) == []


def test_hl006_scope_is_engine_and_batched():
    src = """
        def _closed_form_static(works):
            works[0] = 1.0
            return works
    """
    assert codes(src, BATCHED) == ["HL006"]
    assert codes(src, CORE) == []          # other core modules: out of scope


# ---------------------------------------------------------------------------
# waivers
# ---------------------------------------------------------------------------

def test_waiver_inline_and_standalone():
    out = run("""
        def solve(a: float, b: float):
            x = a == b  # hemt-lint: disable=HL004  exact sentinel
            # hemt-lint: disable=HL004  covers the next line
            y = a != b
            return x, y
    """)
    assert out.findings == []
    assert len(out.suppressed) == 2
    assert out.unused_waivers == []


def test_waiver_wrong_code_does_not_suppress():
    out = run("""
        def solve(a: float, b: float):
            return a == b  # hemt-lint: disable=HL001
    """)
    assert [f.code for f in out.findings] == ["HL004"]
    assert out.unused_waivers  # and the HL001 waiver is reported unused


def test_unused_waiver_reported_and_strings_ignored():
    out = run("""
        def clean():
            return 0  # hemt-lint: disable=HL004
    """)
    assert out.findings == []
    assert [(ln, code) for _, ln, code in out.unused_waivers] \
        == [(3, "HL004")]
    assert out.exit_code == 1      # stale waivers fail the gate too
    # a waiver spelled inside a string is documentation, not a waiver
    assert parse_waivers('msg = "# hemt-lint: disable=HL004"\n') == {}


def test_select_limits_waiver_policing():
    # --select HL002 must not call HL004 waivers unused
    out = run("""
        def solve(a: float, b: float):
            return a == b  # hemt-lint: disable=HL004  exactness note
    """, select=["HL002"])
    assert out.findings == [] and out.unused_waivers == []


def test_syntax_error_is_a_finding():
    out = lint_source("def broken(:\n", CORE)
    assert [f.code for f in out.findings] == ["HL000"]
    assert out.exit_code == 1


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _write_fixture(tmp_path, rel, source):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source).lstrip("\n"), encoding="utf-8")
    return p


def test_cli_text_and_exit_codes(tmp_path, capsys):
    _write_fixture(tmp_path, "src/repro/core/bad.py", """
        import numpy as np
        def f():
            return np.random.rand(3)
    """)
    assert main([str(tmp_path / "src")]) == 1
    text = capsys.readouterr().out
    assert "bad.py:3:" in text and "HL002" in text
    assert "1 finding(s)" in text

    _write_fixture(tmp_path, "src/repro/core/bad.py", "x = 1\n")
    assert main([str(tmp_path / "src")]) == 0


def test_cli_json_report_and_output_artifact(tmp_path, capsys):
    _write_fixture(tmp_path, "src/repro/core/bad.py", """
        import time
        def f():
            return time.perf_counter()
    """)
    report_path = tmp_path / "hemt-lint.json"
    rc = main(["--format=json", "--output", str(report_path),
               str(tmp_path / "src")])
    assert rc == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["version"] == 1
    assert payload["counts"] == {"HL003": 1}
    assert payload["findings"][0]["line"] == 3
    # the artifact the CI job uploads is byte-identical to stdout
    assert json.loads(report_path.read_text()) == payload


def test_cli_list_rules_and_select(tmp_path, capsys):
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in all_rules():
        assert rule.code in out

    _write_fixture(tmp_path, "src/repro/core/bad.py", """
        import time
        def f(a: float):
            return a == 0.0, time.time()
    """)
    assert main(["--select", "HL004", str(tmp_path / "src")]) == 1
    assert "HL003" not in capsys.readouterr().out


def test_pycache_skipped(tmp_path):
    _write_fixture(tmp_path, "src/repro/core/__pycache__/junk.py",
                   "import random\nrandom.random()\n")
    assert lint_paths([str(tmp_path / "src")]).files_checked == 0


# ---------------------------------------------------------------------------
# the repo self-check gate (the CI hemt-lint job runs the same thing)
# ---------------------------------------------------------------------------

def test_repo_tree_is_clean():
    report = self_check()
    assert report.files_checked > 50       # really walked src/
    msgs = [f.format() for f in report.findings]
    assert msgs == [], "hemt-lint violations in src/:\n" + "\n".join(msgs)
    assert report.unused_waivers == [], report.unused_waivers
    assert report.exit_code == 0


def test_repo_waivers_are_documented():
    # every committed waiver carries its justification in-tree; if this
    # count drifts, update it alongside the new waiver + justification
    report = self_check()
    assert len(report.suppressed) == 9
    codes_used = {f.code for f in report.suppressed}
    assert codes_used == {"HL003", "HL004"}


def test_finding_is_ordered_and_formattable():
    a = Finding("a.py", 1, 0, "HL001", "x")
    b = Finding("a.py", 2, 0, "HL001", "x")
    assert a < b
    assert a.format() == "a.py:1:0: HL001 x"
    assert a.to_json()["code"] == "HL001"
