"""Device time per call of the jitted prefill step
(``make_prefill_step``, ``runtime/serve_loop.py``)."""


def read(run):
    m = ((run.get("trace") or {}).get("modules") or {}).get("jit_prefill_step")
    return 1e3 * m["seconds"] / m["runs"] if m and m["runs"] else None
