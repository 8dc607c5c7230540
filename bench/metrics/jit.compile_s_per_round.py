"""Compilation: seconds of ``/jax/core/compile/*`` events (tracing,
lowering, and compiling or loading from the persistent cache) inside the
window, a round."""


def read(run):
    rounds = run.window_rounds()
    if not rounds:
        return None
    t0, t1 = run.window
    return sum(s for t, _, s in run.rec.compile_events
               if t0 <= t <= t1) / len(rounds)
