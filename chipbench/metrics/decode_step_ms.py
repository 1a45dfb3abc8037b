"""Device time per call of the jitted decode step
(``make_serve_step``, ``runtime/serve_loop.py``)."""


def read(run):
    m = ((run.get("trace") or {}).get("modules") or {}).get("jit_serve_step")
    return 1e3 * m["seconds"] / m["runs"] if m and m["runs"] else None
