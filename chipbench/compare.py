"""The numbers that decide ``correct``, each beside its limit.

Training (the first ``check_steps`` steps, taken by the window's own
trainer during set-up, against ``reference.decoder.train``):
  loss_gap         the largest |program - reference| of a step's loss, nats
  grad_norm_gap    over leaves (per layer), |g_p - g_r| / max(g_r, median g_r),
                   where g is the norm of the first step's clipped gradient;
                   the program's is read from its first moment after one
                   step, mu / (1 - beta1)
  change_norm_gap  the same of the norm of each leaf's change over the
                   checked steps; leaves whose reference gradient is under
                   a thousandth of the median leaf's are left out, since
                   Adam moves them by round-off alone
Serving (a sample of the finished requests, against the reference's
forward over each prompt and its served tokens):
  served_logit_gap the widest gap by which a served (greedy) token's
                   reference logit lies below the reference's best there
"""
from __future__ import annotations

import math

import numpy as np

QUIET = 1e-3   # of the median leaf's gradient norm


def _rel_gap(prog: dict, ref: dict, keep=None) -> float:
    names = [k for k in ref if keep is None or k in keep]
    med = float(np.median([ref[k] for k in names]))
    gaps = [abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in names]
    return float(max(gaps))


def train_numbers(prog: dict, ref: dict) -> dict:
    gmed = float(np.median(list(ref["grad_norm"].values())))
    moving = {k for k, g in ref["grad_norm"].items() if g >= QUIET * gmed}
    return {
        "loss_gap": float(max(abs(a - b) for a, b in zip(prog["loss"], ref["loss"]))),
        "grad_norm_gap": _rel_gap(prog["grad_norm"], ref["grad_norm"]),
        "change_norm_gap": _rel_gap(prog["change_norm"], ref["change_norm"], moving),
    }


def logit_gap(ref_logits: np.ndarray, tokens: np.ndarray) -> float:
    """Widest gap of ``tokens`` below the best of each row of ``ref_logits``."""
    ref = np.asarray(ref_logits, np.float64)
    got = np.take_along_axis(ref, np.asarray(tokens)[:, None], axis=1)[:, 0]
    return float(np.max(ref.max(axis=1) - got))


def judge(numbers: dict, lim: dict) -> tuple:
    """(correct, checks): every number finite and at most its limit."""
    checks = [{"name": k, "value": v, "limit": lim[k]} for k, v in numbers.items()]
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks)
    return ok, checks
