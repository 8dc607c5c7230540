"""Diff storage: mean over the window's rounds of the program's
``reuse["compression"]["compression_ratio"]`` (dense bytes over stored
bytes of a Master family)."""


def read(run):
    vals = [c for r in run.window_rounds() for c in r.compression]
    if not vals:
        return None
    return sum(vals) / len(vals)
