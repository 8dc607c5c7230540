"""Collective recovery: change of the collector's ``align_passes``
counter across ``policy.recover``, a round."""


def read(run):
    rounds = run.window_rounds()
    if not rounds:
        return None
    return sum(b.passes for b in run.window_batches()) / len(rounds)
