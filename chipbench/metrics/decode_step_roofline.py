"""Share of its roofline that the decode step reaches: the least time the
chip could take for one step, the larger of its FLOPs over the bf16 peak
and the bytes it must read (every weight once, the K/V cache at its
allocated length; ``flops.decode_bytes``) over the HBM bandwidth, divided
by the step's device time."""
from chipbench.peaks import peaks


def read(run):
    tr, w = run.get("trace"), run.get("window") or {}
    m = ((tr or {}).get("modules") or {}).get("jit_serve_step")
    if not m or not m["runs"] or "decode_bytes_per_step" not in w:
        return None
    pk = peaks(run["device"]["kind"])
    least = max(w["decode_flops_per_step"] / pk["bf16_flops_per_s"],
                w["decode_bytes_per_step"] / pk["hbm_bytes_per_s"])
    return 100.0 * least / (m["seconds"] / m["runs"])
