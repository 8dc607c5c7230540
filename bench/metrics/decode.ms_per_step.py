"""Paged decode: time from the return of ``policy.recover`` to the call
of ``policy.store``, ms per decode step (``gen_len - 1`` a batch)."""


def read(run):
    spans = [s for s in run.window_spans() if s.name == "decode"]
    if not spans:
        return None
    steps = len(spans) * (run.traffic["gen_len"] - 1)
    return sum(s.dur for s in spans) / steps * 1e3
