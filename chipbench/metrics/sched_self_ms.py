"""Host self time per training step of the program's span
``repro.train.schedule`` (``HeMTTrainer._schedule``: the virtual-clock
schedule from ``core/engine.py`` and ``core/planner.py``): its time less
the part its child spans cover."""
from chipbench.program_spans import covered_ns, per_step_ms

NAME = "repro.train.schedule"


def _self_ns(recs):
    mine = [r for r in recs if r.name == NAME]
    if not mine:
        return None
    kids = [(r.t0_ns, r.t1_ns) for r in recs if r.parent == NAME]
    return sum(r.t1_ns - r.t0_ns for r in mine) - covered_ns(kids)


def read(run):
    return per_step_ms(run, _self_ns)
