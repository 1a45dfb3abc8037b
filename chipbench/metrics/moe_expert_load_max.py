"""Mean over the window's training steps of the largest expert group over
the mean group: of each step, ``E * moe.max_expert_rows / moe.routed_rows``,
where ``moe.max_expert_rows`` sums the largest expert group of every
dispatch (layer and grain) and ``moe.routed_rows`` every routed row. These
are the program's per-step counters (records kept by
``repro.runtime.telemetry.record``), read back with the step's loss. 1.0 is
perfectly even routing; the excess is the straggling expert that every
other expert's rows wait for."""
from chipbench.bench import Bench
from chipbench.program_spans import steps_in_window
from chipbench.weights import dims

MAX, ROWS = "moe.max_expert_rows", "moe.routed_rows"


def read(run):
    shares = []
    for rs in (steps_in_window(run) or {}).values():
        got = {r.name: getattr(r, "value", None) for r in rs if r.name in (MAX, ROWS)}
        if got.get(ROWS) and got.get(MAX) is not None:
            shares.append(got[MAX] / got[ROWS])
    if not shares:
        return None
    b = Bench()
    experts = dims(b.config(b.cell(run["cell"])["config"]))["experts"]
    return experts * sum(shares) / len(shares)
