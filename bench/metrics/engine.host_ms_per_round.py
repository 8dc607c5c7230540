"""Round loop: host time of a round outside its plan, recover, decode and
store spans (prompt building, pool bookkeeping, ledgers), ms a round."""


def read(run):
    rounds = run.window_rounds()
    if not rounds:
        return None
    inner = sum(s.dur for s in run.window_spans()
                if s.name in ("plan", "recover", "decode", "store"))
    outer = sum(s.dur for s in run.window_spans() if s.name == "round")
    return (outer - inner) / len(rounds) * 1e3
