"""Store and diff build: span ``store`` around ``policy.store``, ms a
round."""


def read(run):
    rounds = run.window_rounds()
    if not rounds:
        return None
    return sum(s.dur for s in run.window_spans()
               if s.name == "store") / len(rounds) * 1e3
