"""Host time per training step of the program's span ``repro.train.stage``
(``plan_grain_ranges``, ``source.load_stacked`` of ``data/grains.py`` and
the block's copy to the device, span ``repro.train.put``, inside it)."""
from chipbench.program_spans import per_step_ms

NAME = "repro.train.stage"


def _ns(recs):
    mine = [r for r in recs if r.name == NAME]
    return sum(r.t1_ns - r.t0_ns for r in mine) if mine else None


def read(run):
    return per_step_ms(run, _ns)
