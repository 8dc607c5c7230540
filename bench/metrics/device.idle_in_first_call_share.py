"""Device: share of device 0's idle time in the traced window that falls
inside the program's ``td:jit:<program>`` spans, the first calls of the
programs it had not built. A trace with no program spans, or no idle
time, gives no reading."""
from bench.trace import overlap, union


def read(run):
    tr = run.reduced
    if tr is None or not tr.busy or not tr.program_spans:
        return None
    w0, w1 = tr.window
    idle = (w1 - w0) - overlap(tr.busy[0], w0, w1)
    if idle <= 0:
        return None
    calls = union([(max(s, w0), min(e, w1)) for n, s, e in tr.program_spans
                   if n.startswith("jit:") and e > w0 and s < w1])
    inside = sum((e - s) - overlap(tr.busy[0], s, e) for s, e in calls)
    return inside / idle
