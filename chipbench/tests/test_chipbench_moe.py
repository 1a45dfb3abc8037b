"""The granite-moe-1b-a400m cell: its configuration at published widths,
the program's dropless grouped dispatch against the plain reference, and
the reader of ``moe_expert_load_max`` on synthetic records."""
import json
import sys
import types
from pathlib import Path
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import program_spans as P
from chipbench import weights as W
from chipbench.bench import Bench
from chipbench.program import model_config
from chipbench.reference import decoder, moe
from chipbench.tests import tiny

CELL = "granite-moe-1b-a400m.train.seq4k"
CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def test_the_configuration_is_the_published_one_cut_in_depth():
    b = Bench()
    c = b.config("granite-moe-1b-a400m")
    widths = json.loads((CONFIGS / "granite-moe-1b-a400m.json").read_text())
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "num_local_experts", "num_experts_per_tok",
                "vocab_size", "tie_word_embeddings"):
        assert c[key] == widths[key], key
    assert c["reduced"] == {"num_hidden_layers": [24, 12]}
    assert c["assumed"]["head_dim"] == 64
    # every expert can take every token of a sequence: nothing is dropped
    cf = c["assumed"]["capacity_factor"]
    assert cf == c["num_local_experts"] / c["num_experts_per_tok"]
    assert moe.capacity(4096, c) == 4096
    assert model_config(c, "granite-moe-1b-a400m").moe.capacity_factor == cf
    assert b.cell(CELL)["config"] == "granite-moe-1b-a400m"


def dropless():
    c = tiny.moe()
    c["assumed"] = dict(c["assumed"], capacity_factor=c["num_local_experts"]
                        / c["num_experts_per_tok"])
    return c


@pytest.mark.parametrize("skew", [0.0, 4.0], ids=["even", "skewed"])
def test_grouped_dispatch_matches_the_reference(skew):
    """Output, aux loss and gradients of the program's grouped path agree
    with the reference at capacity = S; ``skew`` biases the router towards
    expert 0, which then takes most rows."""
    from repro.models.moe import is_dropless, moe_apply
    c = dropless()
    s, d = 32, int(c["hidden_size"])
    cfg = model_config(c, "granite-moe-1b-a400m").moe
    assert moe.capacity(s, c) == s and is_dropless(cfg, s)
    w = jax.tree.map(lambda a: a.astype(jnp.float32), W.make(c, W.seed_words(3)))
    p = {k: w[k][0] for k in ("router", "w_gate", "w_up", "w_down")}
    # x has mean 1 in every coordinate, so this adds about ``skew`` to
    # expert 0's logit of every token
    p["router"] = p["router"].at[:, 0].add(skew / d)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(s, d)) + 1.0, jnp.float32)
    ct = jnp.asarray(np.random.default_rng(2).normal(size=(s, d)), jnp.float32)

    def prog(p, x):
        out, aux = moe_apply(p, x[None], cfg)
        return jnp.sum(out[0] * ct) + aux, (out[0], aux)

    def ref(p, x):
        out, aux = moe.ffn(p, x, c, False)
        return jnp.sum(out * ct) + aux, (out, aux)

    (_, (o1, a1)), g1 = jax.value_and_grad(prog, argnums=(0, 1), has_aux=True)(p, x)
    (_, (o2, a2)), g2 = jax.value_and_grad(ref, argnums=(0, 1), has_aux=True)(p, x)
    if skew:
        top = jax.lax.top_k(x @ p["router"], int(c["num_experts_per_tok"]))[1]
        assert float(jnp.mean(jnp.any(top == 0, axis=-1))) > 0.9
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-4, atol=1e-5)
    assert float(a1) == pytest.approx(float(a2), rel=1e-5)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-5)


def test_loss_and_gradients_match_at_capacity_s():
    """The whole model, through the program's loss, on the dropless path."""
    from chipbench.program import Program
    from repro.models.model import loss_fn
    c = dropless()
    prog = Program(c, "granite-moe-1b-a400m")
    w = jax.tree.map(lambda a: a.astype(jnp.float32), W.make(c, W.seed_words(5)))
    toks = W.rows(5, W.TRAIN_STREAM, [0], 33, int(c["vocab_size"]))[0]
    tok, lab = jnp.asarray(toks[:-1]), jnp.asarray(toks[1:])
    lp, gp = jax.value_and_grad(
        lambda w: loss_fn(prog.tree(w), {"tokens": tok[None], "labels": lab[None]},
                          prog.cfg))(w)
    lr, gr = jax.value_and_grad(
        lambda w: decoder.seq_loss(decoder.unstack(w), tok, lab, c, False))(w)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for k in gr:
        np.testing.assert_allclose(np.asarray(gp[k]), np.asarray(gr[k]),
                                   rtol=2e-3, atol=1e-6, err_msg=k)


class Record(NamedTuple):
    name: str
    parent: Optional[str]
    step: Optional[int]
    t0_ns: int
    t1_ns: int
    value: Optional[int] = None


S = 1_000_000_000


def step(n: int, base: int, max_rows: int, rows: int = 3_145_728):
    at = base + S // 2
    return [Record("moe.routed_rows", "repro.train.step", n, at, at, rows),
            Record("moe.dropped_rows", "repro.train.step", n, at, at, 0),
            Record("moe.max_expert_rows", "repro.train.step", n, at, at, max_rows),
            Record("repro.train.step", None, n, base, base + S)]


RUN = {"window": {"start": 10.0, "seconds": 3.0}, "cell": CELL,
       "device": {"platform": jax.default_backend(), "kind": "test"}}


@pytest.fixture
def program(monkeypatch):
    """Set-up step 0, steps 1 and 2 in the window, step 3 past its end."""
    recs = (step(0, 5 * S, 999_999) + step(1, 10 * S, 110_000)
            + step(2, 11 * S, 120_000) + step(3, 12 * S + S // 2, 999_999))
    monkeypatch.setitem(sys.modules, P.MODULE, types.SimpleNamespace(records=lambda: recs))


def read(run=RUN):
    return Bench().reader("moe_expert_load_max")(dict(run))


def test_load_max_is_the_mean_of_the_window_steps(program):
    # 32 experts: 12 layers x 8 grains of 32768 rows, a group of 1024 on average
    assert read() == pytest.approx(32 * (110_000 + 120_000) / 2 / 3_145_728)


def test_load_max_reads_nothing_without_the_counters(monkeypatch):
    monkeypatch.delitem(sys.modules, P.MODULE, raising=False)
    assert read() is None
    dense = [Record("repro.train.step", None, 1, 10 * S, 11 * S)]
    monkeypatch.setitem(sys.modules, P.MODULE, types.SimpleNamespace(records=lambda: dense))
    assert read() is None


def test_the_new_metric_is_declared_for_the_cell_alone():
    spec = {m["name"]: m for m in Bench().spec["per_layer"]}
    m = spec["moe_expert_load_max"]
    assert (m["workloads"], m["moves"], m["source"]) == (
        [CELL], "train_tokens_per_s", "program_counter")
    assert {e["name"] for e in Bench().end_to_end(CELL)} == {"setup_s", "train_tokens_per_s"}
