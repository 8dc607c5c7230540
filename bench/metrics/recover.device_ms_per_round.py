"""Collective recovery: device time of the ``jit_collective_recover``
programs (the program's name for its collective recovery pass) in the
traced run, ms a round of the window. The trace starts as the window
opens and stops as it closes. A program that names its recovery
otherwise gives no reading."""


def read(run):
    tr = run.reduced
    rounds = run.window_rounds()
    if tr is None or not rounds:
        return None
    ns = tr.modules.get("jit_collective_recover")
    if ns is None:
        return None
    return ns * 1e-6 / len(rounds)
