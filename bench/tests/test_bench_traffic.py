"""The benchmark's traffic copy: every seed gives the same shapes, and
the shapes are the ones the program builds."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.traffic.generator import (generate_session, load_traffic,
                                     prompt_lengths, sources)

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
FILES = sorted(p.stem for p in TRAFFIC.glob("*.json"))
VOCAB = 152064


def _shapes(s):
    return ([len(s.init_histories[a]) for a in s.agent_ids],
            [[len(t[a]) for a in s.agent_ids] for t in s.tasks],
            [[len(b) for b in blocks] for blocks in s.shared])


@pytest.mark.parametrize("name", FILES)
def test_seeds_change_tokens_not_shapes(name):
    spec = load_traffic(TRAFFIC / f"{name}.json")
    a = generate_session(spec, VOCAB, 7, 0)
    b = generate_session(spec, VOCAB, 2**31 + 12345, 0)
    c = generate_session(spec, VOCAB, 7, 1)
    assert _shapes(a) == _shapes(b) == _shapes(c)
    for x, y in ((a, b), (a, c)):
        assert any(not np.array_equal(x.init_histories[i],
                                      y.init_histories[i])
                   for i in x.agent_ids)
        assert any(not np.array_equal(x.tasks[1][i], y.tasks[1][i])
                   for i in x.agent_ids)
    again = generate_session(spec, VOCAB, 7, 0)
    assert all(np.array_equal(a.init_histories[i], again.init_histories[i])
               for i in a.agent_ids)
    for s in (a, b):
        for h in s.init_histories.values():
            assert h.min() >= 0 and h.max() < VOCAB - 1


@pytest.mark.parametrize("name", FILES)
def test_prompt_lengths_match_the_program(name):
    from repro.core.rounds import AgentState, round_prompt

    spec = load_traffic(TRAFFIC / f"{name}.json")
    s = generate_session(spec, VOCAB, 3, 0)
    bt, g = spec["block_tokens"], spec["gen_len"]
    shared = [np.zeros(g, np.int32)] * spec["agents"]
    for r in range(spec["rounds_per_session"]):
        want = prompt_lengths(spec, r)
        for i, a in enumerate(s.agent_ids):
            hist = np.concatenate([s.init_histories[a],
                                   np.zeros(g * r, np.int32)])
            order = sources(spec)[i] if r else []
            lay = round_prompt(AgentState(a, hist), shared if r else [],
                               s.tasks[r][a], VOCAB - 1,
                               layout_order=order, align_blocks=bt)
            assert lay.length == want[i], (name, r, a)


def test_cells_name_existing_traffic():
    bench = json.loads((TRAFFIC.parents[1] / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        assert w["traffic"] in FILES
