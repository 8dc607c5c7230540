"""Compilation: jitted programs the process had not built, counted by the
program in each round's ``reuse["jit"]["new_programs"]`` (``{name: n}``;
a list of them where a round serves several gather groups), a round of
the window. A program that keeps no such count gives no reading."""


def read(run):
    rounds = run.window_rounds()
    jit = [r.reuse["jit"] for r in rounds if "jit" in r.reuse]
    if not rounds or not jit:
        return None
    n = sum(sum(e["new_programs"].values())
            for j in jit for e in (j if isinstance(j, list) else [j]))
    return n / len(rounds)
