"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 101-112 --control-seeds 101-103

In one process, for each seed:
  * the program's reading: the cell's set-up as a run makes it (training:
    the check steps through the window's own trainer; serving: one round
    at the cell's load), then the comparison with the reference;
  * for the control seeds, the control's reading: the reference in
    fp8 (e4m3) put in the program's place; and for a training cell the
    faults read with the reference in the program's place: half of each
    step's rows left out (the mean over the rest), and one row's tokens
    altered where they are made.
One JSON line per reading goes to standard output. The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    __package__ = "chipbench"


def seeds(spec: str) -> list:
    out = []
    for part in spec.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += list(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def worst(got: dict, ref: dict, n: int = 4) -> dict:
    """The leaves with the largest gaps, with both sides' norms."""
    out = {}
    for key in ("grad_norm", "change_norm"):
        med = sorted(ref[key].values())[len(ref[key]) // 2]
        gaps = sorted(((abs(got[key][k] - v) / max(v, med), k, got[key][k], v)
                       for k, v in ref[key].items()), reverse=True)
        out[key] = [[k, g, p, r] for g, k, p, r in gaps[:n]]
    return out


def train_readings(c, name, t, seed, control: bool) -> None:
    import jax
    import numpy as np
    from . import compare
    from . import weights as W
    from .drivers import free
    from .drivers.train import rows_of_step
    from .program import Program
    from .reference import decoder

    prog = Program(c, name)
    trainer, state = prog.trainer(t, seed)
    got = {"loss": []}
    for k in range(int(t["check_steps"])):
        state, rep = trainer.run_step(state)
        got["loss"].append(rep.loss)
        if k == 0:
            got["grad_norm"] = {n: v / (1.0 - float(t["beta1"]))
                                for n, v in prog.leaf_norms(state.opt.mu).items()}
    got["change_norm"] = prog.change_norms(state.params, seed)
    del trainer, state
    free()
    make = jax.jit(lambda words: W.make(c, words))
    weights = lambda: make(W.seed_words(seed))
    batches = [rows_of_step(seed, k, t, prog.vocab) for k in range(int(t["check_steps"]))]
    t0 = time.perf_counter()
    ref = decoder.train(weights, batches, c, t)
    emit(kind="program", seed=seed, reference_s=time.perf_counter() - t0,
         **compare.train_numbers(got, ref), losses=got["loss"], ref_losses=ref["loss"],
         worst=worst(got, ref))
    if not control:
        return
    low = decoder.train(weights, batches, c, t, lowp=True)
    emit(kind="control_fp8", seed=seed, **compare.train_numbers(low, ref),
         losses=low["loss"])
    half = [(tk[: len(tk) // 2], lb[: len(lb) // 2]) for tk, lb in batches]
    h = decoder.train(weights, half, c, t)
    emit(kind="fault_half_batch", seed=seed, **compare.train_numbers(h, ref))
    alt = [(tk.copy(), lb.copy()) for tk, lb in batches]
    rng = np.random.default_rng([seed, 99])
    alt[0][0][0] = rng.integers(0, prog.vocab, size=alt[0][0][0].shape)
    a = decoder.train(weights, alt, c, t)
    emit(kind="fault_row_altered", seed=seed, **compare.train_numbers(a, ref))


def serve_readings(c, name, t, seed, control: bool) -> None:
    import jax
    import numpy as np
    from .drivers import free
    from .drivers.serve import check, prompts
    from .program import Program

    prog = Program(c, name)
    params = prog.params(seed)
    prefill, decode = prog.serve_steps(t)
    tok, st = prefill(params, jax.device_put(prompts(seed, 0, t, prog.vocab)))
    out = [np.asarray(tok)]
    for _ in range(int(t["decode_steps"])):
        tok, _, st = decode(params, st, tok)
        out.append(np.asarray(tok))
    served = [(0, np.stack(out, axis=1), None)]
    del params, st
    free()
    emit(kind="program", seed=seed, served_logit_gap=check(c, t, seed, served, prog.vocab))
    if control:
        emit(kind="control_fp8", seed=seed,
             served_logit_gap=check(c, t, seed, served, prog.vocab, lowp=True))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    from .bench import Bench
    from .run import cache_compiles, device_info
    bench = Bench()
    cell = bench.cell(args.workload)
    emit(kind="device", device=device_info(int(cell["chips"])))
    cache_compiles()
    c, t = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    ctl = set(seeds(args.control_seeds))
    fn = train_readings if t["kind"] == "train" else serve_readings
    for s in seeds(args.seeds):
        fn(c, cell["config"], t, s, s in ctl)
    return 0


if __name__ == "__main__":
    sys.exit(main())
