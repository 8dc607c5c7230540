"""Compilation, outside the window: seconds of the program's
``jit:<program>`` spans while set-up's warm-up session is served, the
first calls of every program session 0 uses (trace, lower, compile or
load from the persistent cache, and dispatch). The window holds none of
them, so this is where a change to a first call shows. A program that
records no spans of its own gives no reading."""


def read(run):
    spans = run.setup.program_spans if run.setup is not None else []
    calls = [s.t1 - s.t0 for s in spans if s.name.startswith("jit:")]
    return sum(calls) if calls else None
