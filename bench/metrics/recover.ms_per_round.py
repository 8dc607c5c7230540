"""Collective recovery: span ``recover`` around ``policy.recover``, ms a
round. It blocks on the recovered KV."""


def read(run):
    rounds = run.window_rounds()
    if not rounds:
        return None
    return sum(s.dur for s in run.window_spans()
               if s.name == "recover") / len(rounds) * 1e3
