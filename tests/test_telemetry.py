"""The program's host spans and counters (``runtime/telemetry.py``), and
the spans of a HeMT-DP training step."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ArchBundle, TrainConfig, get_reduced
from repro.runtime import telemetry
from repro.runtime.hemt_driver import HeMTTrainer, SliceSpec
from repro.runtime.train_loop import train_state_init

STEP_CHILDREN = {"repro.train.schedule", "repro.train.stage", "repro.train.acc_init",
                 "repro.train.dispatch", "repro.train.observe", "repro.train.wait"}


@pytest.fixture(autouse=True)
def fresh():
    telemetry.reset()
    yield
    telemetry.reset()


def by_name(recs, name):
    return [r for r in recs if r.name == name]


def test_nesting_gives_parents_and_self_times():
    with telemetry.span("repro.t.outer", step=7):
        with telemetry.span("repro.t.inner"):
            time.sleep(0.02)
        time.sleep(0.01)
    inner, outer = telemetry.records()
    assert (inner.name, inner.parent, inner.step) == ("repro.t.inner", "repro.t.outer", 7)
    assert (outer.name, outer.parent, outer.step) == ("repro.t.outer", None, 7)
    assert outer.t0_ns <= inner.t0_ns < inner.t1_ns <= outer.t1_ns
    s = telemetry.summary()["spans"]
    assert s["repro.t.outer"]["count"] == 1
    assert s["repro.t.outer"]["total_ms"] >= 30.0
    assert s["repro.t.inner"]["self_ms"] == pytest.approx(s["repro.t.inner"]["total_ms"])
    outer_ms = (outer.t1_ns - outer.t0_ns) / 1e6
    inner_ms = (inner.t1_ns - inner.t0_ns) / 1e6
    assert s["repro.t.outer"]["self_ms"] == pytest.approx(outer_ms - inner_ms)
    assert 10.0 <= s["repro.t.outer"]["self_ms"] < 20.0 <= inner_ms


def test_self_time_counts_overlapping_children_once():
    assert telemetry.covered_ns([(0, 10), (5, 12), (20, 25), (21, 22)]) == 17
    assert telemetry.covered_ns([]) == 0


def test_a_span_is_recorded_when_its_block_raises():
    with pytest.raises(ValueError):
        with telemetry.span("repro.t.fails"):
            raise ValueError("boom")
    with telemetry.span("repro.t.after"):
        pass
    fails, after = telemetry.records()
    assert fails.name == "repro.t.fails" and after.parent is None


def test_the_buffer_stays_bounded():
    for _ in range(telemetry.MAXLEN + 100):
        with telemetry.span("repro.t.many"):
            pass
    recs = telemetry.records()
    assert len(recs) == telemetry.MAXLEN
    assert telemetry.summary()["spans"]["repro.t.many"]["count"] == telemetry.MAXLEN


def test_counters():
    telemetry.count("t.a")
    telemetry.count("t.a", 4)
    telemetry.count("t.b", 2)
    c = telemetry.summary()["counters"]
    assert c["t.a"] == 5 and c["t.b"] == 2
    telemetry.reset()
    assert telemetry.summary() == {"spans": {}, "counters": {}}


@pytest.mark.parametrize("arch,in_place,copied", [
    ("granite-3-8b", 2, 0),            # every layer attention
    ("mamba2-2.7b", 0, 2),             # every layer SSM
    ("jamba-1.5-large-398b", 1, 7)])   # 1 attention + 7 mamba a group
def test_decode_step_counts_cache_layers_by_kind(arch, in_place, copied):
    """Tracing the decode step counts the layers whose cache takes the
    one-token in-place write and those whose state is copied back whole;
    running the traced step counts nothing more."""
    from repro.models.model import init_decode_state, init_params
    from repro.runtime.serve_loop import make_serve_step
    cfg = get_reduced(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    state = init_decode_state(cfg, 2, 8)
    step = jax.jit(make_serve_step(cfg))
    telemetry.reset()
    step(params, state, jnp.ones((2,), jnp.int32))
    c = telemetry.summary()["counters"]
    assert c.get("decode.cache_layers_in_place", 0) == in_place
    assert c.get("decode.cache_layers_copied", 0) == copied
    step(params, state, jnp.ones((2,), jnp.int32))
    assert telemetry.summary()["counters"] == c


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 700), (False, 0)],
                         ids=["causal", "window", "noncausal"])
def test_chunked_attention_counts_the_tiles_its_mask_leaves_open(causal, window):
    """Tracing chunked_attention at S = 4096 with its own block sizes counts
    as computed exactly the (query block, key block) tiles in which a
    brute-force pass over the mask finds an open position, the rest as
    skipped."""
    import inspect

    import numpy as np

    from repro.models.attention import chunked_attention
    s = 4096
    blocks = inspect.signature(chunked_attention).parameters
    bq, bk = blocks["block_q"].default, blocks["block_k"].default
    pos = np.arange(s)
    rel = pos[:, None] - pos[None, :]
    ok = np.ones((s, s), bool)
    if causal:
        ok &= rel >= 0
    if window:
        ok &= rel < window
    open_tiles = int(ok.reshape(s // bq, bq, s // bk, bk).any(axis=(1, 3)).sum())
    x = jax.ShapeDtypeStruct((1, s, 2, 8), jnp.float32)
    jax.eval_shape(lambda q, k, v: chunked_attention(
        q, k, v, causal=causal, window=window, scale=1.0), x, x, x)
    c = telemetry.summary()["counters"]
    assert c["attention.tiles_computed"] == open_tiles
    assert c["attention.tiles_skipped"] == (s // bq) * (s // bk) - open_tiles


def test_a_fresh_jit_compiles_once():
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)
    x, y = jnp.arange(8.0), jnp.ones(8)
    telemetry.reset()
    with telemetry.span("repro.t.call", step=0):
        f(x).block_until_ready()
    backend = by_name(telemetry.records(), "repro.compile.backend")
    assert len(backend) == 1
    assert (backend[0].parent, backend[0].step) == ("repro.t.call", 0)
    assert backend[0].t0_ns < backend[0].t1_ns
    assert telemetry.summary()["counters"]["compile.backend"] == 1
    telemetry.reset()
    f(y).block_until_ready()
    assert not [r for r in telemetry.records() if r.name.startswith("repro.compile.")]
    assert "compile.backend" not in telemetry.summary()["counters"]


def test_run_step_spans_and_no_recompiles_in_steady_state():
    cfg = dataclasses.replace(get_reduced("granite-3-8b"), n_layers=2)
    bundle = ArchBundle(model=cfg, train=TrainConfig(lr=1e-3, warmup_steps=2,
                                                     total_steps=50))
    tr = HeMTTrainer(cfg, bundle, [SliceSpec("s0"), SliceSpec("s1", [(0.0, 0.5)])],
                     grain_batch=2, global_batch=8, seq_len=16, mode="hemt")
    state = train_state_init(jax.random.PRNGKey(0), cfg, bundle)
    for _ in range(3):
        state, _ = tr.run_step(state)
    recs = telemetry.records()
    steps = by_name(recs, "repro.train.step")
    assert [r.step for r in steps] == [0, 1, 2]
    for st in steps:
        assert st.parent is None
        kids = [r for r in recs if r.parent == "repro.train.step" and r.step == st.step]
        assert {r.name for r in kids} == STEP_CHILDREN
        assert all(st.t0_ns <= r.t0_ns and r.t1_ns <= st.t1_ns for r in kids)
        put = [r for r in by_name(recs, "repro.train.put") if r.step == st.step]
        assert len(put) == 1 and put[0].parent == "repro.train.stage"
    # every program is lowered and compiled in step 0; later steps only
    # trace the eager zeros of the accumulator again
    compiles = [r for r in recs if r.name.startswith("repro.compile.") and r.step is not None]
    assert {r.step for r in compiles if r.name != "repro.compile.trace"} == {0}
    assert {r.parent for r in compiles if r.step > 0} <= {"repro.train.acc_init"}
    c = telemetry.summary()["counters"]
    assert c["train.steps"] == 3 and c["train.grains"] == 3 * 4
    assert c["train.staged_bytes"] == 3 * 2 * 8 * 16 * 4     # tokens + labels, int32


@pytest.mark.parametrize("capacity_factor,batch,path", [
    (4.0, 1, "grouped"), (4.0, 2, "capacity"), (1.25, 1, "capacity")])
def test_moe_layers_are_counted_by_dispatch_path(capacity_factor, batch, path):
    """Tracing a stack counts its MoE layers by the path their calls take:
    grouped for one sequence that every expert can take whole, else the
    capacity path; running the traced program counts nothing more."""
    from repro.models.model import init_params, loss_fn
    base = get_reduced("granite-moe-1b-a400m")
    cfg = dataclasses.replace(base, moe=dataclasses.replace(
        base.moe, capacity_factor=capacity_factor))
    params = init_params(jax.random.PRNGKey(0), cfg)
    rows = {"tokens": jnp.ones((batch, 16), jnp.int32),
            "labels": jnp.ones((batch, 16), jnp.int32)}
    f = jax.jit(lambda p: loss_fn(p, rows, cfg))
    telemetry.reset()
    f(params)
    c = telemetry.summary()["counters"]
    other = "capacity" if path == "grouped" else "grouped"
    assert c[f"moe.layers_{path}"] == cfg.n_layers
    assert f"moe.layers_{other}" not in c
    f(params)
    assert telemetry.summary()["counters"] == c


def test_run_step_reads_back_the_moe_rows_of_each_step():
    """On the grouped path (grains of one sequence) every routed row is
    kept: per step the trainer records S*k rows a layer and grain, none
    dropped, at the step's wait, and the largest group at least the mean
    one."""
    base = get_reduced("granite-moe-1b-a400m")
    cfg = dataclasses.replace(base, moe=dataclasses.replace(
        base.moe, capacity_factor=float(base.moe.n_experts // base.moe.top_k)))
    bundle = ArchBundle(model=cfg, train=TrainConfig(lr=1e-3, warmup_steps=2,
                                                     total_steps=50))
    tr = HeMTTrainer(cfg, bundle, [SliceSpec("s0"), SliceSpec("s1", [(0.0, 0.5)])],
                     grain_batch=1, global_batch=8, seq_len=16, mode="hemt")
    state = train_state_init(jax.random.PRNGKey(0), cfg, bundle)
    for _ in range(2):
        state, _ = tr.run_step(state)
    k, e, layers, grains = cfg.moe.top_k, cfg.moe.n_experts, cfg.n_layers, 8
    routed = 16 * k * layers * grains
    recs = [r for r in telemetry.records() if r.name.startswith("moe.")]
    assert {(r.name, r.step) for r in recs} == {
        (f"moe.{n}", s) for n in ("routed_rows", "dropped_rows", "max_expert_rows")
        for s in (0, 1)}
    for r in recs:
        assert r.parent == "repro.train.step" and r.t0_ns == r.t1_ns
        if r.name == "moe.routed_rows":
            assert r.value == routed
        elif r.name == "moe.dropped_rows":
            assert r.value == 0
        else:
            assert routed // e <= r.value <= routed // k
    c = telemetry.summary()
    assert c["counters"]["moe.routed_rows"] == 2 * routed
    assert c["counters"]["moe.dropped_rows"] == 0
    assert not [n for n in c["spans"] if n.startswith("moe.")]
