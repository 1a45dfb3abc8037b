"""Model FLOP/s of the traced training window over the chips' bf16 peak:
FLOPs per token (``flops.train_flops_per_token``) times the tokens of
every step in the window, over the window's length and the peak."""
from chipbench.peaks import peaks


def read(run):
    tr, w = run.get("trace"), run.get("window") or {}
    if not tr or not w.get("tokens") or tr["window_s"] <= 0:
        return None
    peak = peaks(run["device"]["kind"])["bf16_flops_per_s"] * tr["devices"]
    return 100.0 * w["model_flops"] / tr["window_s"] / peak
