"""A run, with the chip check skipped, at toy sizes on the CPU: sound, it
reads correct; with the timed path broken underneath, it reads not
correct, once for each fault a cell of its kind can have.

Training: a step that returns its state unchanged; half of the batch
left out, the mean taken over the rest; a token altered where it is made.
Serving: a decode step that returns its state unchanged; a token altered
where it is produced. (No cell spans chips, so no exchange between chips
can be left out.)"""
import jax.numpy as jnp
import pytest

from chipbench import program
from chipbench.run import execute
from chipbench.tests import tiny

LIMITS = {"loss_gap": 0.02, "grad_norm_gap": 0.05, "change_norm_gap": 0.05,
          "served_logit_gap": 0.05}
DEV = {"platform": "cpu", "kind": "cpu", "count": 1}
TRAIN = "granite-3-8b.train.seq4k"
SERVE = "granite-3-8b.serve.b32"


def run(cell):
    return execute(tiny.TinyBench(LIMITS), cell, 2 ** 33 + 3, 0.3, False, DEV, 0.0)


@pytest.mark.parametrize("cell", [TRAIN, SERVE])
def test_sound_run_is_correct(cell):
    line = run(cell)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"


def test_train_state_unchanged(monkeypatch):
    from repro.runtime import hemt_driver
    real = hemt_driver.make_apply_step

    def broken(cfg, bundle, **kw):
        step = real(cfg, bundle, **kw)

        def apply_step(state, acc, total):
            new, metrics = step(state, acc, total)
            return new._replace(params=state.params, opt=state.opt), metrics
        return apply_step

    monkeypatch.setattr(hemt_driver, "make_apply_step", broken)
    assert not run(TRAIN)["correct"]


def test_train_half_batch(monkeypatch):
    from repro.runtime import hemt_driver
    real_acc, real_apply = hemt_driver.grain_accumulate_cached, hemt_driver.make_apply_step

    def half_acc(cfg, bundle, **kw):
        fn = real_acc(cfg, bundle, **kw)
        return lambda params, acc, grains: fn(
            params, acc, {k: v[: v.shape[0] // 2] for k, v in grains.items()})

    def half_apply(cfg, bundle, **kw):
        fn = real_apply(cfg, bundle, **kw)
        return lambda state, acc, total: fn(state, acc, total // 2)

    monkeypatch.setattr(hemt_driver, "grain_accumulate_cached", half_acc)
    monkeypatch.setattr(hemt_driver, "make_apply_step", half_apply)
    assert not run(TRAIN)["correct"]


def test_train_token_altered(monkeypatch):
    real = program.RowSource.batch_block

    def altered(self, indices):
        out = real(self, indices)
        tok = out["tokens"].copy()
        tok[0, 0, 5] = (tok[0, 0, 5] + 1) % self.vocab
        return {"tokens": tok, "labels": out["labels"]}

    monkeypatch.setattr(program.RowSource, "batch_block", altered)
    assert not run(TRAIN)["correct"]


def test_serve_state_unchanged(monkeypatch):
    real = program.make_serve_step

    def broken(cfg, **kw):
        step = real(cfg, **kw)

        def serve_step(params, state, tokens):
            nxt, logits, _ = step(params, state, tokens)
            return nxt, logits, state
        return serve_step

    monkeypatch.setattr(program, "make_serve_step", broken)
    assert not run(SERVE)["correct"]


def test_serve_token_altered(monkeypatch):
    real = program.make_serve_step

    def broken(cfg, **kw):
        step = real(cfg, **kw)

        def serve_step(params, state, tokens):
            nxt, logits, new = step(params, state, tokens)
            # every request's token at every fifth position, so that any
            # sample of the requests holds one
            return jnp.where(state["length"] % 5 == 2, (nxt + 1) % cfg.vocab_size,
                             nxt), logits, new
        return serve_step

    monkeypatch.setattr(program, "make_serve_step", broken)
    assert not run(SERVE)["correct"]
