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

The counts are summed layer by layer over the :class:`Layer` shapes that
the architecture's reference describes (``dims(cfg)["layers"]``): a token
runs its layer's attention projections, dense MLP, shared experts and
router, and its share of the top-k routed experts; a sliding-window
layer's queries see at most ``window`` keys, and a decode step reads at
most that many cached rows of it.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import NamedTuple, Optional, Sequence

PEAKS = Path(__file__).resolve().parent / "peaks.json"
CHECK_LAYER = 1          # the engine's default check_layer
BYTES = 2                # bf16 weights and KV


class Layer(NamedTuple):
    """One layer's shape, as the counts need it."""

    H: int                   # query heads
    KV: int                  # key/value heads
    qk: int                  # query and key head size
    v: int                   # value head size
    attention: str = "full"  # "full" (causal) or "sliding"
    window: int = 0          # keys a query sees in a sliding layer
    F: int = 0               # dense SwiGLU width, 0 for none
    experts: int = 0         # routed SwiGLU experts held
    top_k: int = 0           # routed experts a token takes
    expert_F: int = 0        # a routed expert's width
    shared_F: int = 0        # the shared experts' width, summed
    router: int = 0          # router outputs: the experts routed over


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def layers(d: dict) -> tuple:
    """Each layer's shape: ``d["layers"]``, or ``L`` dense full-attention
    layers of the model-wide sizes ``H``, ``KV``, ``hd`` and ``F``."""
    if "layers" in d:
        return tuple(d["layers"])
    return (Layer(d["H"], d["KV"], d["hd"], d["hd"], F=d["F"]),) * d["L"]


def dense_params(lay: Layer, D: int) -> int:
    """Matmul weights every token of the layer reads: attention
    projections, dense MLP, shared experts and router (biases and norms
    are left out)."""
    attn = (D * lay.H * lay.qk + D * lay.KV * (lay.qk + lay.v)
            + lay.H * lay.v * D)
    return attn + 3 * D * (lay.F + lay.shared_F) + D * lay.router


def expert_params(lay: Layer, D: int) -> int:
    """Matmul weights of one routed expert."""
    return 3 * D * lay.expert_F


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


def _seen(lay: Layer, n: int) -> int:
    """Keys seen by the queries at positions ``0 .. n - 1``, each causal
    and, in a sliding layer, capped by the window."""
    w = lay.window if lay.attention == "sliding" else 0
    if not w or n <= w:
        return n * (n + 1) // 2
    return w * (w + 1) // 2 + (n - w) * w


def _keys(lay: Layer, a: int, b: int) -> int:
    """Keys seen by the queries at positions ``a .. b - 1``."""
    return _seen(lay, b) - _seen(lay, a)


def _attn(lay: Layer, q_keys: int) -> int:
    """Score and value products for ``q_keys`` (query, key) pairs."""
    return 2 * lay.H * (lay.qk + lay.v) * q_keys


def _matmuls(lay: Layer, D: int, tokens: int) -> int:
    """Matmul operations of ``tokens`` tokens through one layer. A chip
    that holds a share of the routed experts computes the same share of
    the tokens' top-k."""
    f = 2 * tokens * dense_params(lay, D)
    if lay.experts:
        pairs = tokens * lay.top_k * lay.experts // lay.router
        f += 2 * pairs * expert_params(lay, D)
    return f


def prefill_flops(d: dict, N: int, S: int) -> int:
    D = d["D"]
    return (sum(_matmuls(lay, D, N * S) + N * _attn(lay, _keys(lay, 0, S))
                for lay in layers(d))
            + 2 * N * head_params(d))


def recovery_flops(d: dict, N: int, S: int, n_sel: int, bt: int) -> int:
    """Check-layer pass over all ``S`` positions, then ``n_sel`` selected
    positions through the remaining layers. The selected queries' keys
    are counted at their least: the last block (always selected) sees the
    whole prompt, the other selected positions are taken as the first
    ones."""
    D, c = d["D"], CHECK_LAYER + 1
    f = 2 * N * head_params(d)
    for i, lay in enumerate(layers(d)):
        if i < c:
            f += _matmuls(lay, D, N * S) + N * _attn(lay, _keys(lay, 0, S))
        else:
            sel = _keys(lay, S - bt, S) + _keys(lay, 0, n_sel - bt)
            f += _matmuls(lay, D, N * n_sel) + N * _attn(lay, sel)
    return f


def decode_step_cost(d: dict, N: int, length: int,
                     experts_read: Optional[Sequence[int]] = None) -> tuple:
    """(flops, bytes) of one decode step for ``N`` agents whose new token
    sits at position ``length - 1``: every dense matmul weight read once,
    the routed experts the step read, the batch's KV up to ``length``
    (a sliding layer's last ``window`` rows) read and the new token's KV
    written. ``experts_read`` gives, per layer, the routed experts the
    program reports the step read; without it each routed layer reads
    its top-k, never more than it holds."""
    D, flops = d["D"], 2 * N * head_params(d)
    weights, kv = head_params(d), 0
    for i, lay in enumerate(layers(d)):
        seen = _keys(lay, length - 1, length)
        flops += _matmuls(lay, D, N) + N * _attn(lay, seen)
        read = (experts_read[i] if experts_read is not None
                else min(lay.top_k, lay.experts))
        weights += dense_params(lay, D) + read * expert_params(lay, D)
        kv += N * (seen + 1) * lay.KV * (lay.qk + lay.v) * BYTES
    return flops, weights * BYTES + kv


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
