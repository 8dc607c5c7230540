"""Restore: span ``plan`` around ``policy.plan`` (the Master-Mirror
restore and the assembly of the cached arrays), ms a round. In a traced
run the span ends on ``block_until_ready`` of the plan's arrays."""


def read(run):
    rounds = run.window_rounds()
    if not rounds:
        return None
    return sum(s.dur for s in run.window_spans()
               if s.name == "plan") / len(rounds) * 1e3
