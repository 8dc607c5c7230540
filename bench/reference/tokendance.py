"""Float32 reference of the served semantics: TokenDance's rounds replayed
from round 0, with the model's layer math from the cell's model
reference (``bench/reference/<architecture>.py``).

Nothing here imports the program or takes anything it made: the replay
builds every prompt itself from the session's traffic and the tokens
the program served, keeps its own caches, and makes its own selection.

A session's agents share one prompt layout rule (the paper's All-Gather
round, block-aligned): agent ``i``'s prompt in round ``r`` is its history
(its initial tokens and every token it served so far), then the output of
each source agent in round ``r - 1`` (none in round 0), then its task;
each segment padded with the separator id ``V - 1`` to whole blocks of
``block_tokens``.

Round 0 computes every position. A later round reuses KV computed in
round ``r - 1`` at other positions and recomputes only part of it
(CacheBlend-style selective recomputation, paper §2.2 and §4.2):

* cached KV: each source's output block holds the KV that agent's decode
  left for it (its last token never ran through the model, so its row is
  zero), looked up by the block's tokens, the last agent to serve those
  tokens winning; the history span holds the agent's own last-round
  history-span KV as recovered, then its own output block's KV, laid from
  position 0. Cached keys are moved by RoPE from the position they were
  computed at to the one they now sit at. The task is not cached;
* layers ``0 .. check_layer`` run fresh over every position; the squared
  distance between the fresh and the cached keys of ``check_layer``,
  summed over each block of cached positions, ranks the cached blocks;
* every block holding a fresh position or the last position, and
  ``max(1, ceil(recompute_ratio * cached blocks))`` of the highest-ranked
  cached blocks, are recomputed: their positions run through the
  remaining layers, attending over the cached KV with their own rows
  replaced by what they compute; every other row keeps the cached KV;
* the first served token is read from the last position; the other
  served tokens follow one at a time, each attending over the recovered
  KV and the served tokens before it.

The program ranks the blocks from bf16 keys, so where two cached blocks
score within ``TIE`` of each other at the cut, float32 cannot say which
one it recomputes. There the replay runs every set of blocks the band
allows (:func:`choices`) and keeps the one whose logits the served tokens
fit best (the widest gap of a served token below the best logit is
least; the highest-ranked set where that ties). The program's own choice
of blocks is never read.

At ``recompute_ratio`` 1 every block is recomputed and the replay is the
plain causal forward.

An architecture is a module ``bench/reference/<architecture>.py`` that
gives the replay:

* ``dims(cfg)``: a dict of hashable sizes, with ``L`` layers, ``V`` ids,
  the cached K and V row's ``KV`` heads of ``hd`` (alike in every
  layer), and ``layers``, each layer's ``bench.roofline.Layer``;
* ``make_weights(seed, cfg)``, ``weight_bytes(weights)``;
* ``blocks(weights)``: the layers' weights, either one tree of stacked
  ``[L, ...]`` leaves, where every layer runs the same code and the
  replay reads one layer at a time by index under ``fori_loop`` and
  ``scan`` (the layer index it passes on may then be traced), or a list
  of ``L`` trees, where the replay loops over the layers in Python and
  passes each its own index, so that layers may differ in kind (a
  leading dense layer, a window beside full attention, routed experts);
* ``embed(weights, tokens, quant)`` and ``head(weights, h, d, quant)``;
* per layer ``li``: ``qkv(p, h, pos, d, li, quant)``,
  ``attend(q, q_pos, k, v, kv_pos, d, li)``,
  ``finish(p, h, o, d, li, quant)``, and ``move_keys(k, delta, d, li)``,
  which moves cached keys ``delta`` positions on by the layer's own
  position encoding.
"""
from __future__ import annotations

import itertools
import math
from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.roofline import CHECK_LAYER, n_sel_for_blocks
from bench.traffic.generator import sources

#: relative score difference within which the float32 ranking of two
#: cached blocks does not decide which one the program recomputes
TIE = 0.05
MAX_CHOICES = 20


def _pad(tokens: np.ndarray, bt: int, sep: int) -> np.ndarray:
    return np.concatenate([np.asarray(tokens, np.int32),
                           np.full((-len(tokens)) % bt, sep, np.int32)])


def _layer(blocks, li):
    """Layer ``li``'s weights: an item of a list, or a slice of stacked
    leaves, read by index where ``li`` is traced."""
    if isinstance(blocks, list):
        return blocks[li]
    if isinstance(li, int):
        return jax.tree.map(lambda a: a[li], blocks)
    # one layer's weights are read where they are used: a slice of the
    # stacked layers outside the loop would copy them all
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, li, keepdims=False), blocks)


@partial(jax.jit, static_argnames=("model", "bt", "d_items", "quant"))
def _check(model, weights, tokens, base_k, src, cached, *, bt, d_items,
           quant):
    """Layers ``0 .. CHECK_LAYER`` run fresh over one prompt. Returns the
    blocks' ranking scores [nb] (``inf`` on blocks always recomputed), the
    residual stream [S, D], the fresh keys and values of those layers, and
    the cached keys [L, S, KV, hd] moved from positions ``src`` to their
    own."""
    d = dict(d_items)
    blocks = model.blocks(weights)
    pos = jnp.arange(tokens.shape[0])
    delta = pos - src
    if isinstance(blocks, list):
        base_k = jnp.stack([model.move_keys(base_k[li], delta, d, li)
                            for li in range(len(blocks))])
    else:
        base_k = jax.vmap(lambda k, li: model.move_keys(k, delta, d, li))(
            base_k, jnp.arange(base_k.shape[0]))
    h = model.embed(weights, tokens, quant)
    fresh_k, fresh_v = [], []
    for li in range(CHECK_LAYER + 1):
        p = _layer(blocks, li)
        q, k, v = model.qkv(p, h, pos, d, li, quant)
        h = model.finish(p, h, model.attend(q, pos, k, v, pos, d, li), d, li,
                         quant)
        fresh_k.append(k)
        fresh_v.append(v)
    dev = jnp.where(cached, jnp.sum(
        (fresh_k[CHECK_LAYER] - base_k[CHECK_LAYER]) ** 2, axis=(-1, -2)), 0.0)
    always = (~cached).reshape(-1, bt).any(-1).at[-1].set(True)
    score = jnp.where(always, jnp.inf, dev.reshape(-1, bt).sum(-1))
    return score, h, jnp.stack(fresh_k), jnp.stack(fresh_v), base_k


@partial(jax.jit, static_argnames=("model", "bt", "d_items", "quant"))
def _recover(model, weights, h, fresh_k, fresh_v, base_k, base_v, blocks, *,
             bt, d_items, quant):
    """Recovered KV [L, S, KV, hd] with ``blocks`` recomputed, and the
    final residual stream at the last position."""
    d = dict(d_items)
    layer_w = model.blocks(weights)
    pos = jnp.arange(h.shape[0])
    sel = jnp.sort((blocks[:, None] * bt + jnp.arange(bt)).reshape(-1))
    rec_k, rec_v = base_k, base_v
    for li in range(CHECK_LAYER + 1):
        rec_k = rec_k.at[li, sel].set(fresh_k[li][sel])
        rec_v = rec_v.at[li, sel].set(fresh_v[li][sel])

    def layer(li, carry):
        hs, rec_k, rec_v = carry
        p = _layer(layer_w, li)
        q, k, v = model.qkv(p, hs, sel, d, li, quant)
        bk = rec_k[li].at[sel].set(k)
        bv = rec_v[li].at[sel].set(v)
        hs = model.finish(p, hs, model.attend(q, sel, bk, bv, pos, d, li), d,
                          li, quant)
        return hs, rec_k.at[li].set(bk), rec_v.at[li].set(bv)

    carry = (h[sel], rec_k, rec_v)
    if isinstance(layer_w, list):
        for li in range(CHECK_LAYER + 1, len(layer_w)):
            carry = layer(li, carry)
    else:
        carry = jax.lax.fori_loop(CHECK_LAYER + 1, base_k.shape[0], layer,
                                  carry)
    hs, rec_k, rec_v = carry
    return rec_k, rec_v, hs[-1]


def choices(score: np.ndarray, n_blocks: int, tie: float) -> List[np.ndarray]:
    """The sets of ``n_blocks`` blocks the ranking allows, the
    highest-ranked set first. Blocks scored ``inf`` are always in. Where
    cached blocks score within ``tie`` (relative) of the cut between the
    last block in and the first block out, the program, which ranks them
    at its own precision, may order them either way: every set that
    takes the blocks above that band and fills up from inside it is
    allowed. Where that makes more than ``MAX_CHOICES`` sets, the band
    narrows to the blocks nearest the cut."""
    order = np.argsort(-score, kind="stable")
    first = np.sort(order[:n_blocks])
    if tie <= 0 or n_blocks >= len(score) or np.isinf(score[order[n_blocks]]):
        return [first]
    cut = 0.5 * (score[order[n_blocks - 1]] + score[order[n_blocks]])
    above = order[score[order] > cut * (1 + tie)]
    band = order[np.abs(score[order] - cut) <= cut * tie]
    need = n_blocks - len(above)
    if math.comb(len(band), need) > MAX_CHOICES:
        return choices(score, n_blocks, tie / 2)
    sets = [np.sort(np.concatenate([above, list(c)])).astype(np.int64)
            for c in itertools.combinations(band, need)]
    sets.sort(key=lambda b: not np.array_equal(b, first))
    return sets


@partial(jax.jit, static_argnames=("model", "d_items", "quant"))
def _extend(model, weights, tokens, rec_k, rec_v, *, d_items, quant):
    """KV [L, T, KV, hd] and final residual stream [T, D] of ``tokens``
    [T] placed right after the recovered prompt."""
    d = dict(d_items)
    layer_w = model.blocks(weights)
    S, T = rec_k.shape[1], tokens.shape[0]
    pos = S + jnp.arange(T)
    kv_pos = jnp.arange(S + T)

    def layer(h, xs):
        p, li, bk, bv = xs
        q, k, v = model.qkv(p, h, pos, d, li, quant)
        kk = jnp.concatenate([bk, k])
        vv = jnp.concatenate([bv, v])
        h = model.finish(p, h, model.attend(q, pos, kk, vv, kv_pos, d, li), d,
                         li, quant)
        return h, (k, v)

    h = model.embed(weights, tokens, quant)
    if isinstance(layer_w, list):
        ks, vs = [], []
        for li, p in enumerate(layer_w):
            h, (k, v) = layer(h, (p, li, rec_k[li], rec_v[li]))
            ks.append(k)
            vs.append(v)
        return jnp.stack(ks), jnp.stack(vs), h
    h, (k, v) = jax.lax.scan(
        layer, h, (layer_w, jnp.arange(rec_k.shape[0]), rec_k, rec_v))
    return k, v, h


@partial(jax.jit, static_argnames=("model", "d_items", "quant"))
def _head(model, weights, h, *, d_items, quant):
    return model.head(weights, h, dict(d_items), quant)


def replay(model, weights: dict, cfg: dict, traffic: dict, session,
           served: Dict[Tuple[int, int], np.ndarray], last_round: int,
           want, quant: str | None = None):
    """Replay rounds ``0 .. last_round`` of one session.

    ``served[(r, i)]`` are the tokens agent ``i`` served in round ``r``;
    ``want`` names the agent-rounds whose logits are returned. Returns
    ``(prompts, logits, selected)``: the prompt the replay built for
    every agent-round; float32 logits [G, V] at every served position of
    each wanted one (the first row from the prompt's last position); and
    for every agent-round the blocks it recomputed, the blocks' ranking
    scores and how many sets the ranking allowed (:func:`choices`).
    ``quant`` rounds the weights as ``model`` does for a control."""
    d = model.dims(cfg)
    d_items = tuple(sorted(d.items()))
    bt, G = traffic["block_tokens"], traffic["gen_len"]
    ratio = float(traffic["recompute_ratio"])
    sep = cfg["vocab_size"] - 1
    n = traffic["agents"]
    srcs = sources(traffic)
    ids = session.agent_ids
    hist: List[np.ndarray] = [np.asarray(session.init_histories[a], np.int32)
                              for a in ids]
    stale = [None] * n      # (k, v, span length, own output's tokens)
    index = {}              # output tokens -> (k, v, src) of last round
    prompts, logits, selected = {}, {}, {}
    zero_row = jnp.zeros((d["L"], 1, d["KV"], d["hd"]), jnp.float32)
    for r in range(last_round + 1):
        outs = [np.asarray(served[(r - 1, j)], np.int32)
                for j in range(n)] if r else []
        new_stale, new_index = [None] * n, {}
        for i in range(n):
            h_span = _pad(hist[i], bt, sep)
            segs = [h_span] + [outs[j] for j in (srcs[i] if r else [])]
            task = _pad(session.tasks[r][ids[i]], bt, sep)
            tokens = np.concatenate(segs + [task])
            S, Hs = len(tokens), len(h_span)
            prompts[(r, i)] = tokens
            ks, vs, src_parts = [], [], []
            cached = np.zeros(S, bool)
            if r:
                hk, hv, span, own = stale[i]
                ok, ov, osrc = index[own.tobytes()]
                assert span + G == Hs, (r, i, span, Hs)
                ks += [hk, ok]
                vs += [hv, ov]
                src_parts += [np.arange(span), osrc]
                for j in srcs[i]:
                    ok, ov, osrc = index[outs[j].tobytes()]
                    ks.append(ok)
                    vs.append(ov)
                    src_parts.append(osrc)
                cached[:S - len(task)] = True
            n_fresh = S - int(cached.sum())
            if n_fresh:
                fill = jnp.zeros((d["L"], n_fresh, d["KV"], d["hd"]),
                                 jnp.float32)
                ks.append(fill)
                vs.append(fill)
                src_parts.append(np.arange(S - n_fresh, S))
            nb = S // bt
            nb_fresh = int((~cached).reshape(nb, bt).any(-1).sum()
                           + bool(cached[-bt:].all()))
            score, h, fk, fv, base_k = _check(
                model, weights, jnp.asarray(tokens), jnp.concatenate(ks, 1),
                jnp.asarray(np.concatenate(src_parts), jnp.int32),
                jnp.asarray(cached), bt=bt, d_items=d_items, quant=quant)
            score = np.asarray(score)
            toks = np.asarray(served[(r, i)], np.int32)
            sets = choices(score, n_sel_for_blocks(nb, nb_fresh, ratio, bt)
                           // bt, TIE if quant is None else 0.0)
            best = None
            for blocks in sets:
                # where the ranking allows several sets, the served tokens
                # tell which one the program took: the one whose logits
                # they fit best, the highest-ranked set where that ties
                rec_k, rec_v, h_last = _recover(
                    model, weights, h, fk, fv, base_k, jnp.concatenate(vs, 1),
                    jnp.asarray(blocks, jnp.int32), bt=bt, d_items=d_items,
                    quant=quant)
                out_k, out_v, h_dec = _extend(
                    model, weights, jnp.asarray(toks[:-1]), rec_k, rec_v,
                    d_items=d_items, quant=quant)
                lg, gap = None, 0.0
                if (r, i) in want or len(sets) > 1:
                    lg = np.asarray(_head(
                        model, weights, jnp.concatenate([h_last[None], h_dec]),
                        d_items=d_items, quant=quant))
                    gap = float((lg.max(-1) - lg[np.arange(G), toks]).max())
                if best is None or gap < best[0]:
                    best = (gap, blocks, rec_k, rec_v, out_k, out_v, lg)
            _, blocks, rec_k, rec_v, out_k, out_v, lg = best
            selected[(r, i)] = (blocks, score, len(sets))
            if (r, i) in want:
                logits[(r, i)] = lg
            out_k = jnp.concatenate([out_k, zero_row], 1)
            out_v = jnp.concatenate([out_v, zero_row], 1)
            new_index[toks.tobytes()] = (out_k, out_v, np.arange(S, S + G))
            new_stale[i] = (rec_k[:, :Hs], rec_v[:, :Hs], Hs, toks)
            hist[i] = np.concatenate([hist[i], toks])
        stale, index = new_stale, new_index
    return prompts, logits, selected
