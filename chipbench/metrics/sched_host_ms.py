"""Host time per training step in the trainer's scheduler
(``HeMTTrainer._schedule``: the virtual-clock schedule from
``core/engine.py`` and ``core/planner.py``), from span ``schedule``."""


def read(run):
    d = ((run.get("trace") or {}).get("spans") or {}).get("schedule")
    return 1e3 * sum(d) / len(d) if d else None
