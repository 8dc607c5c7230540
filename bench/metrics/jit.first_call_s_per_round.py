"""Compilation: seconds of the program's ``jit:<program>`` spans (the
first call of a program it had not built: trace, lower, compile or load
from the persistent cache, and dispatch) inside the window, a round. A
program that records no spans of its own gives no reading."""


def read(run):
    rounds = run.window_rounds()
    if not rounds or not run.rec.program_spans:
        return None
    t0, t1 = run.window
    return sum(s.t1 - s.t0 for s in run.rec.program_spans
               if s.name.startswith("jit:") and t0 <= s.t0 and s.t1 <= t1) \
        / len(rounds)
