"""Runs of a tiny cell on the CPU with the timed path sound, read against
its fp8 control, and broken in each way the cell can break; one JSON line
per run. Driven by ``test_bench_faults.py`` in a child process, so that
JAX's persistent cache and the patches stay out of the test worker.

    python3 bench/tests/scenarios.py <tmp_dir> <seed>

The faults, each planted where the program produces the thing it breaks:

* ``state_unchanged``: the paged decode step returns the cache it was
  given, so no generated token's KV is kept;
* ``half_batch``: recovery serves the second half of a batch with the
  first half's logits and caches (half the batch left out);
* ``token_altered``: the decode step's logits of the first agent are
  negated, so the token it serves is the least likely one.

A cell on one chip has no exchange between chips to leave out.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run  # noqa: E402
from bench.tests.tiny import tiny_cell  # noqa: E402


def _state_unchanged(engine_mod):
    real = engine_mod.decode_step_paged

    def step(params, cfg, tok, cache, **kw):
        logits, _ = real(params, cfg, tok, cache, **kw)
        return logits, cache
    engine_mod.decode_step_paged = step
    return lambda: setattr(engine_mod, "decode_step_paged", real)


def _token_altered(engine_mod):
    real = engine_mod.decode_step_paged

    def step(params, cfg, tok, cache, **kw):
        logits, cache = real(params, cfg, tok, cache, **kw)
        return logits.at[0].set(-logits[0]), cache
    engine_mod.decode_step_paged = step
    return lambda: setattr(engine_mod, "decode_step_paged", real)


def _half_batch(pic_mod):
    real = pic_mod.PICPolicy.recover

    def recover(self, plan, tokens):
        res = real(self, plan, tokens)
        n = res.logits.shape[0]
        h = n // 2
        if h:
            def fill(x, axis):
                idx = [slice(None)] * x.ndim
                src, dst = list(idx), list(idx)
                src[axis], dst[axis] = slice(0, n - h), slice(h, n)
                return x.at[tuple(dst)].set(x[tuple(src)])
            res.logits = fill(res.logits, 0)
            res.cache = {k: fill(v, 1) if k in ("k", "v") else v
                         for k, v in res.cache.items()}
        return res
    pic_mod.PICPolicy.recover = recover
    return lambda: setattr(pic_mod.PICPolicy, "recover", real)


def main(tmp: str, seed: int) -> None:
    run.CACHE = Path(tmp) / "cache"
    cell = tiny_cell(Path(tmp))
    import repro.serving.engine as engine_mod
    import repro.serving.policies.pic as pic_mod

    faults = {"sound": None,
              "state_unchanged": lambda: _state_unchanged(engine_mod),
              "half_batch": lambda: _half_batch(pic_mod),
              "token_altered": lambda: _token_altered(engine_mod)}
    for name, plant in faults.items():
        undo = plant() if plant else None
        try:
            out = run.measure(cell, seed, 0.1, False, True,
                              controls=("fp8",) if plant is None else ())
        finally:
            if undo:
                undo()
        print(json.dumps({"run": name, "correct": out["correct"],
                          "checks": out["checks"],
                          "controls": out.get("controls", {})}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]))
