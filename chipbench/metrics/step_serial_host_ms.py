"""Host time per training step during which the step's main program is not
yet queued: from the start of span ``repro.train.step`` to the start of its
first ``repro.train.dispatch``, plus from the end of its
``repro.train.wait`` (the loss read back) to the end of the step. The
device idles through this time unless the previous step's programs are
still running."""
from chipbench.program_spans import STEP, steps_in_window


def read(run):
    serial = []
    for rs in (steps_in_window(run) or {}).values():
        step = [r for r in rs if r.name == STEP][0]
        dispatch = [r.t0_ns for r in rs if r.name == "repro.train.dispatch"]
        wait = [r.t1_ns for r in rs if r.name == "repro.train.wait"]
        if dispatch and wait:
            serial.append((min(dispatch) - step.t0_ns) + (step.t1_ns - max(wait)))
    return sum(serial) / len(serial) / 1e6 if serial else None
