"""Model FLOP/s of the traced serving window over the chips' bf16 peak:
the prefill and decode FLOPs of every round in the window
(``flops.prefill_flops``, ``flops.decode_flops``) over its length."""
from chipbench.peaks import peaks


def read(run):
    tr, w = run.get("trace"), run.get("window") or {}
    if not tr or not w.get("model_flops") or tr["window_s"] <= 0:
        return None
    peak = peaks(run["device"]["kind"])["bf16_flops_per_s"] * tr["devices"]
    return 100.0 * w["model_flops"] / tr["window_s"] / peak
