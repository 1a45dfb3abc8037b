"""Device time per training step of the jitted ``grain_accumulate``
program (``runtime/train_loop.py``), from the trace's XLA Modules line."""


def read(run):
    m = ((run.get("trace") or {}).get("modules") or {}).get("jit_grain_accumulate")
    steps = (run.get("window") or {}).get("units")
    return 1e3 * m["seconds"] / steps if m and steps else None
