"""The control, at toy size on the CPU: the reference computed at fp8's
precision, put in the program's place, reads not correct where the
program reads correct, under one set of limits. (On the chip, at the
cells' own sizes, ``calibrate.py`` reads the same two numbers; the limits
in ``limits/`` are set from those readings.)"""
import jax
import numpy as np
import pytest

from chipbench import compare
from chipbench import weights as W
from chipbench.drivers import serve as DS
from chipbench.drivers import train as DT
from chipbench.program import Program
from chipbench.reference import decoder
from chipbench.tests import tiny

TRAIN_LIMITS = {"loss_gap": 0.006, "grad_norm_gap": 0.006, "change_norm_gap": 0.006}
SERVE_LIMIT = 0.15


@pytest.mark.parametrize("seed", [1, 2 ** 33 + 1])
def test_train_control_fails_where_program_passes(seed):
    c, t = tiny.dense(), tiny.train_mix()
    res = DT.run(c, "granite-3-8b", t, seed, 0.05)
    ok, _ = compare.judge(res["numbers"], TRAIN_LIMITS)
    assert ok, res["numbers"]
    make = jax.jit(lambda w: W.make(c, w))
    batches = [DT.rows_of_step(seed, k, t, int(c["vocab_size"]))
               for k in range(int(t["check_steps"]))]
    low = decoder.train(lambda: make(W.seed_words(seed)), batches, c, t, lowp=True)
    ctl, checks = compare.judge(compare.train_numbers(low, res["readings"]["reference"]),
                                TRAIN_LIMITS)
    assert not ctl, checks


def one_round(c, t, seed):
    prog = Program(c, "granite-3-8b")
    params = prog.params(seed)
    prefill, decode = prog.serve_steps(t)
    tok, st = prefill(params, DS.prompts(seed, 0, t, prog.vocab))
    out = [np.asarray(tok)]
    for _ in range(int(t["decode_steps"])):
        tok, _, st = decode(params, st, tok)
        out.append(np.asarray(tok))
    return [(0, np.stack(out, axis=1), None)], prog.vocab


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serve_control_fails_where_program_passes(seed):
    c, t = tiny.dense(), dict(tiny.serve_mix(), check_requests=4)
    served, vocab = one_round(c, t, seed)
    assert DS.check(c, t, seed, served, vocab) <= SERVE_LIMIT
    assert DS.check(c, t, seed, served, vocab, lowp=True) > SERVE_LIMIT
