"""Operations and bytes the served algorithm has to spend, computed from
shapes alone, and the chip's peaks.

Counts follow what TokenDance must do at the cell's ``recompute_ratio``
and nothing the program spends besides: a recompute round prefills every
prompt token and computes one row of logits per agent; a reuse round runs
every position through layers ``0 .. check_layer`` and only the selected
positions through the rest; a decode step runs one token per agent
through every layer and the head. Repeated runs of a shape, recomputed
logits that are never read, and copies are not counted, so a share of a
peak computed from these counts cannot pass 100%.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
CHECK_LAYER = 1          # the engine's default check_layer
BYTES = 2                # bf16 weights and KV


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def layer_params(d: dict) -> int:
    """Matmul weights of one layer (biases and norms are left out)."""
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    return D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F


def head_params(d: dict) -> int:
    return d["D"] * d["V"]


def n_sel_for_blocks(n_blocks: int, n_fresh_blocks: int, ratio: float,
                     bt: int) -> int:
    """Copy of the program's ``core.pic.n_sel_for_blocks`` for a prompt
    of ``n_blocks`` blocks whose last ``n_fresh_blocks`` (the task) are
    fresh: every fresh block plus ``ratio`` of the cached ones, at least
    one and at most all, in tokens."""
    cached = n_blocks - n_fresh_blocks
    return min(n_blocks,
               n_fresh_blocks + max(1, math.ceil(ratio * cached))) * bt


def _attn(d: dict, layers: int, q_keys: int) -> int:
    """Score and value products for ``q_keys`` (query, key) pairs."""
    return 2 * 2 * layers * d["H"] * d["hd"] * q_keys


def prefill_flops(d: dict, N: int, S: int) -> int:
    causal = S * (S + 1) // 2
    return (2 * N * S * d["L"] * layer_params(d) + N * _attn(d, d["L"], causal)
            + 2 * N * head_params(d))


def recovery_flops(d: dict, N: int, S: int, n_sel: int, bt: int) -> int:
    """Check-layer pass over all ``S`` positions, then ``n_sel`` selected
    positions through the remaining layers. The selected queries' keys
    are counted at their least: the last block (always selected) sees the
    whole prompt, the other selected positions are taken as the first
    ones."""
    c = CHECK_LAYER + 1
    rest = d["L"] - c
    causal = S * (S + 1) // 2
    low = n_sel - bt
    sel_keys = bt * S - bt * (bt - 1) // 2 + low * (low + 1) // 2
    return (2 * N * S * c * layer_params(d) + N * _attn(d, c, causal)
            + 2 * N * n_sel * rest * layer_params(d)
            + N * _attn(d, rest, sel_keys) + 2 * N * head_params(d))


def decode_step_cost(d: dict, N: int, length: int) -> tuple:
    """(flops, bytes) of one decode step for ``N`` agents whose new token
    sits at position ``length - 1``: every matmul weight read once, the
    batch's KV up to ``length`` read and the new token's KV written."""
    w = d["L"] * layer_params(d) + head_params(d)
    kv_tok = 2 * d["L"] * d["KV"] * d["hd"] * BYTES
    flops = 2 * N * w + N * _attn(d, d["L"], length)
    return flops, w * BYTES + N * length * kv_tok + N * kv_tok


def decode_flops(d: dict, N: int, S: int, G: int) -> int:
    """The ``G - 1`` decode steps after the first token."""
    return sum(decode_step_cost(d, N, S + t)[0] for t in range(1, G))


def batch_flops(d: dict, traffic: dict, N: int, S: int, kind: str) -> int:
    """Work one batch of a round has to do: its recovery (or prefill) and
    its decode."""
    bt, G = traffic["block_tokens"], traffic["gen_len"]
    if kind == "recompute":
        f = prefill_flops(d, N, S)
    else:
        fresh = -(-traffic["task_len"] // bt)
        n_sel = n_sel_for_blocks(S // bt, fresh, traffic["recompute_ratio"],
                                 bt)
        f = recovery_flops(d, N, S, n_sel, bt)
    return f + decode_flops(d, N, S, G)
