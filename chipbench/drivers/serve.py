"""Closed-loop serving rounds through ``make_prefill_step`` and
``make_serve_step``.

A round prefills ``batch`` prompts into a ``cache_len``-slot cache, reads
the first tokens back, then runs ``decode_steps`` greedy decode steps and
reads every step's tokens back on the host. Round ``r``'s prompts are rows
``r * batch ..`` of the seed's prompt stream, so a round is the same work
whatever ran before it.
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
from . import free, measure, p95, peak_bytes


def prompts(seed: int, rnd: int, t: dict, vocab: int) -> np.ndarray:
    b = int(t["batch"])
    return W.rows(seed, W.PROMPT_STREAM, range(rnd * b, (rnd + 1) * b),
                  int(t["prompt_len"]), vocab)


def run(c: dict, name: str, t: dict, seed: int, seconds: float, log_dir=None) -> dict:
    prog = Program(c, name)
    params = prog.params(seed)
    prefill, decode = prog.serve_steps(t)
    steps = int(t["decode_steps"])
    # warm-up: one prefill and two decode steps compile both programs
    tok, st = prefill(params, jax.device_put(prompts(seed, 1 << 30, t, prog.vocab)))
    for _ in range(2):
        tok, _, st = decode(params, st, tok)
    np.asarray(tok)
    del st

    box = {"round": 0}
    served = []

    def unit():
        r = box["round"]
        box["round"] += 1
        with T.span("prompts"):
            p = jax.device_put(prompts(seed, r, t, prog.vocab))
        with T.span("dispatch"):
            tok, st = prefill(params, p)
        with T.span("readback"):
            out = [np.asarray(tok)]
        times = [time.perf_counter()]
        for _ in range(steps):
            with T.span("dispatch"):
                tok, _, st = decode(params, st, tok)
            with T.span("readback"):
                out.append(np.asarray(tok))
            times.append(time.perf_counter())
        rec = (r, np.stack(out, axis=1), np.diff(np.asarray(times)))
        served.append(rec)
        return rec

    rounds, (t0, t1), ran = measure(unit, seconds, log_dir)
    memory = peak_bytes()
    del params, prefill, decode
    free()

    b, plen = int(t["batch"]), int(t["prompt_len"])
    tokens = len(rounds) * b * (steps + 1)
    gaps = np.concatenate([g for _, _, g in rounds]) if rounds else np.zeros(0)
    per_round = flops.prefill_flops(c, b, plen) + sum(
        flops.decode_flops(c, b, plen + i) for i in range(steps))
    window = {"start": t0, "units": len(rounds), "tokens": tokens, "seconds": t1 - t0,
              "model_flops": len(rounds) * per_round,
              "prefill_calls": len(rounds), "decode_steps": len(rounds) * steps,
              "decode_flops_per_step": flops.decode_flops(c, b, plen + steps // 2),
              "decode_bytes_per_step": flops.decode_bytes(c, b, int(t["cache_len"]))}
    e2e = {"serve_tokens_per_s": tokens / (t1 - t0) if tokens else float("nan"),
           "itl_p95_ms": 1e3 * p95(gaps) if len(gaps) > 1 else float("nan")}

    r0 = time.perf_counter()
    numbers = {"served_logit_gap": check(c, t, seed, served, prog.vocab)}
    return {"e2e": e2e, "attempted": ran * b, "failed": 0, "numbers": numbers,
            "memory_peak_bytes": memory, "window": window,
            "reference_s": time.perf_counter() - r0}


def sample(seed: int, served: list, t: dict) -> list:
    """(round, row) of ``check_requests`` finished requests drawn from the
    seed. Every request of this mix is of the longest length."""
    b = int(t["batch"])
    done = [(r, i) for r, _, _ in served for i in range(b)]
    rng = np.random.default_rng([seed, W.SAMPLE_STREAM])
    pick = rng.choice(len(done), size=min(int(t["check_requests"]), len(done)),
                      replace=False)
    return [done[j] for j in sorted(pick)]


def check(c: dict, t: dict, seed: int, served: list, vocab: int, *, lowp=False) -> float:
    """Widest gap of a served token below the reference's best logit over
    the sampled requests (with ``lowp``, the fp8 control's own first
    choices take the served tokens' place)."""
    make = jax.jit(lambda words: W.make(c, words))
    w = make(W.seed_words(seed))
    plen, steps = int(t["prompt_len"]), int(t["decode_steps"])
    toks = {r: out for r, out, _ in served}
    worst = 0.0
    for r, i in sample(seed, served, t):
        row = np.concatenate([prompts(seed, r, t, vocab)[i], toks[r][i, :steps]])
        ref = np.asarray(decoder.serve_logits(w, row, plen - 1, c))
        if lowp:
            low = np.asarray(decoder.serve_logits(w, row, plen - 1, c, lowp=True))
            pick = low.argmax(axis=1)
        else:
            pick = toks[r][i]
        worst = max(worst, compare.logit_gap(ref, pick))
    return worst
