"""The output check: served tokens against the float32 reference of the
served semantics (``bench/reference/tokendance.py``).

Once the window has closed, a sample of the agent-rounds it finished is
drawn from ``--seed``, always with one of the longest prompts in it. The
reference replays each sampled request's session from round 0 over the
tokens the program served, and at every position where the program
served a token it reads the gap ``max(reference logits) - reference logit
of the served token``. The first served token comes from collective
recovery (restore and recovery are upstream of it), the rest from paged
decode steps. The numbers compared are the widest gap over the sample
and the count of replayed agent-rounds whose prompt, as the program
served it, differs from the one the reference built.

A control (``controls=("fp8",)``, ``bench/calibrate.py``) replays the
same session with weights rounded to a lower precision and reads, at the
same positions, the float32 gap of the token that it puts first.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List

import numpy as np

from bench.reference import tokendance
from bench.traffic.generator import generate_session


@dataclass
class Request:
    session: int
    round: int
    agent: str
    prompt: np.ndarray     # [S]
    served: np.ndarray     # [G]
    selected: np.ndarray = None   # [n_sel] positions the program recomputed


def finished_requests(batches) -> List[Request]:
    out = []
    for b in batches:
        if b.outputs is None:
            continue
        for i, a in enumerate(b.agents):
            out.append(Request(b.session, b.round, a, b.tokens[i],
                               b.outputs[i], None if b.selected is None
                               else b.selected[i]))
    return out


def sample(requests: List[Request], k: int, seed: int) -> List[Request]:
    """``k`` requests drawn from ``seed``, one of the longest among them."""
    if len(requests) <= k:
        return list(requests)
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(seed) % (1 << 63), 0x5A3F1E]))
    longest = max(len(r.prompt) for r in requests)
    top = [i for i, r in enumerate(requests) if len(r.prompt) == longest]
    first = int(rng.choice(top))
    rest = [i for i in range(len(requests)) if i != first]
    pick = [first] + [int(i) for i in rng.choice(rest, k - 1, replace=False)]
    return [requests[i] for i in sorted(pick)]


def compare(model, weights, cfg, traffic, seed: int, served: List[Request],
            picked: List[Request], controls=()) -> Dict[str, dict]:
    """Replay the sessions of ``picked`` over everything ``served`` and
    read the numbers compared: under ``"program"`` for the served tokens,
    and under each control's name for the tokens that control puts
    first."""
    by_session = defaultdict(list)
    for r in picked:
        by_session[r.session].append(r)
    out = {k: {"max_gap": 0.0, "max_gap_first": 0.0, "prompts_differ": 0}
           for k in ("program",) + tuple(controls)}
    out["program"].update(gaps=[], selections_differ=0, margins=[], ties=0)
    for s, reqs in sorted(by_session.items()):
        sess = generate_session(traffic, cfg["vocab_size"], seed, s)
        idx = {a: i for i, a in enumerate(sess.agent_ids)}
        last = max(r.round for r in reqs)
        mine = [r for r in served if r.session == s and r.round <= last]
        tokens = {(r.round, idx[r.agent]): r.served for r in mine}
        want = {(r.round, idx[r.agent]) for r in reqs}
        prompts, ref, sel = tokendance.replay(model, weights, cfg, traffic,
                                              sess, tokens, last, want)
        differ = sum(
            1 for r in mine
            if not np.array_equal(prompts[(r.round, idx[r.agent])], r.prompt))
        low = {q: tokendance.replay(model, weights, cfg, traffic, sess,
                                    tokens, last, want, quant=q)[1]
               for q in controls}
        out["program"]["prompts_differ"] += differ
        # for the log only: where the program recomputed other blocks than
        # the replay, how far apart the replay ranks them
        bt = traffic["block_tokens"]
        for r in mine:
            blocks, score, n_sets = sel[(r.round, idx[r.agent])]
            o = out["program"]
            o["ties"] += n_sets > 1
            if r.selected is None:
                continue
            prog = set(np.unique(np.asarray(r.selected) // bt).tolist())
            mine_ = set(np.asarray(blocks).tolist())
            if prog != mine_:
                o["selections_differ"] += 1
                o["margins"].append(float(
                    min(score[b] for b in mine_ - prog)
                    / max(score[b] for b in prog - mine_) - 1))
        for key, o in out.items():
            for r in reqs:
                lg = ref[(r.round, idx[r.agent])].astype(np.float64)
                pick = (np.asarray(r.served, np.int64) if key == "program"
                        else np.argmax(low[key][(r.round, idx[r.agent])], -1))
                gap = lg.max(-1) - lg[np.arange(len(pick)), pick]
                o["max_gap"] = max(o["max_gap"], float(gap.max()))
                o["max_gap_first"] = max(o["max_gap_first"], float(gap[0]))
                if key == "program":
                    o["gaps"].append((s, r.round, r.agent, float(gap.max()),
                                      float(gap[0])))
    return out
