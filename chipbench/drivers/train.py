"""HeMT-DP training: ``HeMTTrainer.run_step`` back to back.

Set-up builds the trainer and its state from the seed and drives it
through the traffic file's ``check_steps`` first steps, through the
window's own ``run_step`` and rows; they compile every program of the
step and give the readings that the reference is compared with. The same
trainer and state then run the window.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from .. import compare, flops
from .. import trace as T
from .. import weights as W
from ..program import Program
from ..reference import decoder
from . import free, measure, peak_bytes


def rows_of_step(seed: int, step: int, t: dict, vocab: int):
    b, s = int(t["global_batch"]), int(t["seq_len"])
    toks = W.rows(seed, W.TRAIN_STREAM, range(step * b, (step + 1) * b), s + 1, vocab)
    return toks[:, :-1], toks[:, 1:]


def run(c: dict, name: str, t: dict, seed: int, seconds: float, log_dir=None) -> dict:
    prog = Program(c, name)
    trainer, state = prog.trainer(t, seed)
    b1 = float(t["beta1"])
    got = {"loss": []}
    for k in range(int(t["check_steps"])):
        state, rep = trainer.run_step(state)
        got["loss"].append(rep.loss)
        if k == 0:
            got["grad_norm"] = {n: v / (1.0 - b1)
                                for n, v in prog.leaf_norms(state.opt.mu).items()}
    got["change_norm"] = prog.change_norms(state.params, seed)

    if log_dir:
        T.wrap(trainer, "_schedule", "schedule")
        T.wrap(getattr(trainer, "source", None), "load_stacked", "staging")
        T.wrap(trainer, "grain_accumulate", "dispatch")
        T.wrap(trainer, "apply_step", "dispatch")

    box = {"state": state}
    del state

    def unit():
        with T.span("run_step"):
            box["state"], rep = trainer.run_step(box["state"])
        return rep.loss

    losses, (t0, t1), ran = measure(unit, seconds, log_dir)
    memory = peak_bytes()
    del box, trainer
    free()

    tokens = len(losses) * int(t["global_batch"]) * int(t["seq_len"])
    failed = sum(1 for x in losses if not np.isfinite(x))
    per_token = flops.train_flops_per_token(c, int(t["seq_len"]))
    window = {"start": t0, "units": len(losses), "tokens": tokens, "seconds": t1 - t0,
              "model_flops": tokens * per_token, "flops_per_token": per_token}

    r0 = time.perf_counter()
    make = jax.jit(lambda words: W.make(c, words))
    ref = decoder.train(lambda: make(W.seed_words(seed)),
                        [rows_of_step(seed, k, t, prog.vocab)
                         for k in range(int(t["check_steps"]))], c, t)
    numbers = compare.train_numbers(got, ref)
    return {
        "e2e": {"train_tokens_per_s": tokens / (t1 - t0) if tokens else float("nan")},
        "attempted": ran, "failed": failed, "numbers": numbers,
        "memory_peak_bytes": memory, "window": window,
        "reference_s": time.perf_counter() - r0,
        "readings": {"program": got, "reference": ref},
    }
