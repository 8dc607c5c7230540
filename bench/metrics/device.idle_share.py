"""Device: share of the traced window in which no operation ran on the
chip, 1 - (union of device operation intervals / window)."""


def read(run):
    tr = run.reduced
    if tr is None or not tr.busy or tr.window_s <= 0:
        return None
    return 1.0 - tr.busy_s() / tr.window_s
