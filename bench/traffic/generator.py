"""Fixed-shape All-Gather traffic: the benchmark's own copy of the
program's synthetic trace generator (``repro.core.rounds.generate_trace``).

The copy differs in one way. The program draws each agent's history
jitter from the same seed as the token contents, so two seeds give two
sets of prompt lengths, hence two sets of compiled shapes. Here the
lengths come from the traffic file's ``length_seed`` alone, and
``--seed`` (with the session index) only draws token ids, so every seed
of a cell serves the same shapes in the same order.

A traffic file (``bench/traffic/<name>.json``) holds::

    agents               number of agents N
    rounds_per_session   rounds one engine serves before a fresh one
    memory_round         round after which resident memory is read
    history              {"base": b, "jitter": j}: agent i's initial
                         history is b + U[0, j) tokens, drawn once from
    length_seed          ... this seed
    task_len, gen_len    task tokens per round, generated tokens per round
    recompute_ratio      share of cached blocks recovery recomputes
    block_tokens         KV block (page) size the prompts align to
    topology             {"kind": "all_gather"} or
                         {"kind": "neighborhood", "k": k}
    sample_requests      requests the output check compares
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List

import numpy as np

KEYS = ("agents", "rounds_per_session", "memory_round", "history",
        "length_seed", "task_len", "gen_len", "recompute_ratio",
        "block_tokens", "topology", "sample_requests")


def load_traffic(path: Path) -> dict:
    spec = json.loads(Path(path).read_text())
    missing = [k for k in KEYS if k not in spec]
    if missing:
        raise ValueError(f"{path}: traffic file lacks {missing}")
    if spec["gen_len"] % spec["block_tokens"]:
        raise ValueError(f"{path}: gen_len must be a whole number of blocks")
    if spec["memory_round"] < 2 or \
            spec["memory_round"] >= spec["rounds_per_session"]:
        raise ValueError(f"{path}: memory_round must be a window round")
    return spec


@dataclass
class SessionTraffic:
    """One session's inputs: initial histories and per-round tasks.

    ``shared[r]`` are the replay output blocks of round ``r`` (empty in
    round 0); an engine in generate mode replaces them with its own
    outputs and reads them only for agents it has not served yet.
    """

    agent_ids: List[str]
    init_histories: Dict[str, np.ndarray]
    tasks: List[Dict[str, np.ndarray]]
    shared: List[List[np.ndarray]]
    vocab_size: int


def history_lengths(spec: dict) -> List[int]:
    """Initial history length of every agent, fixed by ``length_seed``."""
    rng = np.random.default_rng(spec["length_seed"])
    h = spec["history"]
    return [int(h["base"]) + int(rng.integers(0, h["jitter"]))
            if h["jitter"] else int(h["base"])
            for _ in range(spec["agents"])]


def _content_rng(seed: int, session: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed) % (1 << 63), int(session), 0x7AFF1C]))


def generate_session(spec: dict, vocab_size: int, seed: int,
                     session: int) -> SessionTraffic:
    """Token ids of one session, drawn from ``seed`` and ``session``.

    Ids lie in ``[0, vocab_size - 1)``: the last id is the separator and
    pad token of the program's prompt layout."""
    rng = _content_rng(seed, session)
    n, rounds = spec["agents"], spec["rounds_per_session"]

    def toks(k):
        return rng.integers(0, vocab_size - 1, size=k).astype(np.int32)

    ids = [f"agent{i}" for i in range(n)]
    inits = {a: toks(h) for a, h in zip(ids, history_lengths(spec))}
    tasks, shared = [], []
    for r in range(rounds):
        shared.append([toks(spec["gen_len"]) for _ in range(n)] if r else [])
        tasks.append({a: toks(spec["task_len"]) for a in ids})
    return SessionTraffic(ids, inits, tasks, shared, vocab_size)


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def sources(spec: dict) -> List[List[int]]:
    """Indices of the agents whose outputs each agent reads."""
    n, topo = spec["agents"], spec["topology"]
    if topo["kind"] == "all_gather":
        return [list(range(n)) for _ in range(n)]
    if topo["kind"] == "neighborhood":
        k = int(topo["k"])
        return [list(dict.fromkeys((i + d) % n for d in range(-k, k + 1)))
                for i in range(n)]
    raise ValueError(f"unknown topology {topo}")


def prompt_lengths(spec: dict, round_idx: int) -> List[int]:
    """Built prompt length of every agent in ``round_idx``: the history
    (grown by ``gen_len`` a round), each source's output block, and the
    task, each padded to whole blocks."""
    bt, g = spec["block_tokens"], spec["gen_len"]
    out = []
    for h0, src in zip(history_lengths(spec), sources(spec)):
        hist = _ceil_to(h0 + g * round_idx, bt)
        shared = len(src) * _ceil_to(g, bt) if round_idx else 0
        out.append(hist + shared + _ceil_to(spec["task_len"], bt))
    return out

