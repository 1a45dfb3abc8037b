"""Seconds of set-up spent tracing, lowering, compiling or loading
programs from the persistent compile cache: the union of the intervals of
the program's ``repro.compile.*`` records that end before the window
starts (a union, since a cache load lies inside its backend compile and a
nested jit's trace inside its caller's)."""
from chipbench.program_spans import COMPILE, all_records, covered_ns, window_ns


def read(run):
    recs, win = all_records(run), window_ns(run)
    if recs is None or win is None:
        return None
    return covered_ns((r.t0_ns, r.t1_ns) for r in recs
                      if r.name.startswith(COMPILE) and r.t1_ns <= win[0]) / 1e9
