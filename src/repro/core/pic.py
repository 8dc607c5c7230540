"""Position-independent caching (PIC) with CacheBlend-style selective
recomputation (paper §2.2), used as the per-position recovery backend for
collective reuse (§4.2).

Given a prompt whose segments have cached KV computed at *other* absolute
positions, the recovery pipeline is:

  1. RoPE-align cached keys from their source positions to the target
     positions (rotation composes, so one extra rotation suffices). The
     SHARED blocks are identical for every request in an All-Gather round,
     so their alignment is performed once per group; private (history)
     caches are aligned per request — that work is inherently private in
     both TokenDance and the per-request baseline.
  2. Run the first ``check_layer + 1`` layers fully fresh and measure the
     key deviation ||K_fresh - K_cached||^2 on the check layer.
  3. Select the ``n_sel`` most deviating positions (fresh positions are
     always selected) and recompute ONLY those through the remaining
     layers, attending over the merged (aligned + recomputed) KV.

The result is one recovered KV cache per request in which unselected
positions carry the aligned cached values — the structural source of the
cross-agent similarity that Diff-Aware Storage exploits.

TokenDance's collective path batches the whole round group into one call:
one shared RoPE alignment of the shared blocks and one batched
important-position pass identify each request's positions simultaneously,
so the per-round reuse overhead is paid once (paper §4.2). Outputs are
bit-identical to per-request recovery (paper §6.6).

A round's prompts grow with the conversation, so the serving engine runs
recovery (and decode) at a *bucketed* length, :func:`bucket_len`: the
prompt is right-padded to a whole number of ``BUCKET_BLOCKS`` KV blocks
and :func:`pic_prefill` takes the real length and the real selection
budget as operands. Padded positions are neither cached nor selectable,
and causal attention keeps them out of every real position, so the
recomputed blocks and the real rows of the result are those of the
unpadded pass.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (
    _noshard,
    apply_rope,
    gqa_attention,
    moe_block,
    rmsnorm,
    rope_cos_sin,
    rope_shift,
    swiglu_mlp,
)
from repro.models.transformer import _logits

BIG = 1.0e30

#: the serving path pads a prompt up to a whole number of this many KV
#: blocks, so every prompt length of a bucket shares one program
BUCKET_BLOCKS = 8


def bucket_len(S: int, block_tokens: int) -> int:
    """Length the round programs run a prompt of ``S`` tokens at: ``S``
    rounded up to a multiple of ``BUCKET_BLOCKS`` blocks of
    ``block_tokens``. Token-level selection (``block_tokens`` 0) has no
    block to bucket by and runs at ``S``."""
    if not block_tokens:
        return S
    unit = BUCKET_BLOCKS * block_tokens
    return -(-S // unit) * unit


@dataclass
class PagedHistory:
    """Private histories handed to :func:`pic_prefill` in PAGED form — the
    zero-densify dual of the dense ``priv_k``/``priv_v`` inputs.

    The recovery pass consumes the family page pool directly: each
    layer's base KV is assembled by reading ``pool[l][page_idx]`` at the
    point the layer's attention/merge needs it, so no ``[B, L, S, ...]``
    dense private cache ever exists — neither on the host nor as a jit
    intermediate. This is the XLA form of the paged attention consumer;
    on a TPU backend the same stream is the Pallas kernel
    ``kernels.flash_prefill.flash_prefill_paged_kernel`` (page table in
    the BlockSpec index map).

    Structural contract (the collector gates on it, see
    ``PagedPrivate.fast_path_ok``): the paged span's source positions
    equal its target positions — so the pool pages need NO RoPE
    realignment; the identity rotation is *skipped*, not approximated
    (bit-exact because rotating by a zero delta is the identity on
    floats) — and the private mask covers exactly the span+tail region
    written here. Only the dense decode tail (fresh content with no
    pages yet) is rotated, an O(T) operation.

    Fields: pools ``[L, P, bt, KV, hd]``; ``page_idx`` int32 [B, nbh];
    ``src`` int32 [B, S] (used for the tail rotation only);
    ``start`` static placement of the paged span and ``span_len`` its
    length (static, or a traced int32 scalar under a page table padded
    past the span); tails ``[B, L, T, KV, hd]`` or None.
    """

    pool_k: jax.Array
    pool_v: jax.Array
    page_idx: jax.Array
    src: jax.Array
    start: int
    span_len: "int | jax.Array"
    tail_k: Optional[jax.Array] = None
    tail_v: Optional[jax.Array] = None

    @property
    def tail_len(self) -> int:
        return 0 if self.tail_k is None else int(self.tail_k.shape[2])


@jax.tree_util.register_dataclass
@dataclass
class PICResult:
    """Output of one recovery pass (batched over a request group)."""

    recovered_k: jax.Array   # [L, B, S, KV, hd]
    recovered_v: jax.Array   # [L, B, S, KV, hd]
    deviation: jax.Array     # [B, S]   check-layer key deviation (0 at fresh)
    sel_idx: jax.Array       # [B, n_sel] recomputed positions (sorted)
    logits: jax.Array        # [B, V]   last-position logits
    hidden_sel: jax.Array    # [B, n_sel, D] final hidden at selected positions


def _layer(params: dict, l: int) -> dict:
    return jax.tree.map(lambda a: a[l], params["blocks"])


def align_cached_keys(cached_k: jax.Array, src_pos: jax.Array,
                      tgt_pos: jax.Array, theta: float) -> jax.Array:
    """RoPE-align cached keys [L, S, KV, hd] from src to target positions.

    This is the operation TokenDance performs ONCE per round group for the
    shared blocks; the per-request baseline repeats it per agent.
    """
    return jax.vmap(lambda k: rope_shift(k, src_pos, tgt_pos, theta))(cached_k)


def _fresh_block(h, p, cfg, positions, cos, sin, shard):
    """One standard full-attention block; returns (h, k, v)."""
    from repro.models.layers import attention_block

    x = rmsnorm(h, p["ln1"], cfg.rmsnorm_eps)
    S = h.shape[1]
    a_out, (k, v) = attention_block(
        x, p["attn"], cfg=cfg, positions=positions, window=S,
        cos=cos, sin=sin, shard=shard)
    h = h + a_out
    x2 = rmsnorm(h, p["ln2"], cfg.rmsnorm_eps)
    if cfg.is_moe:
        m, _ = moe_block(x2, p["moe"], cfg=cfg, shard=shard)
        h = h + m
    else:
        h = h + swiglu_mlp(x2, p["mlp"], shard)
    return h, k, v


def _selective_block(h_sel, p, cfg, *, sel_pos, cos_sel, sin_sel,
                     k_base, v_base, sel_dst, shard):
    """Recompute one layer at the selected positions only.

    h_sel: [B, n, D]; k_base/v_base: [B, S, KV, hd] (aligned cache); the
    fresh K/V of the selected tokens are scattered into the base at
    ``sel_dst`` before attention (an index past ``S`` writes nothing).
    Returns (h_sel', k_merged, v_merged).
    """
    B, n, D = h_sel.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    x = rmsnorm(h_sel, p["ln1"], cfg.rmsnorm_eps)
    ap = p["attn"]
    q = jnp.einsum("bnd,dhk->bnhk", x, ap["wq"].reshape(D, H, hd))
    k = jnp.einsum("bnd,dhk->bnhk", x, ap["wk"].reshape(D, KV, hd))
    v = jnp.einsum("bnd,dhk->bnhk", x, ap["wv"].reshape(D, KV, hd))
    if "bq" in ap:
        q = q + ap["bq"].reshape(H, hd)
        k = k + ap["bk"].reshape(KV, hd)
        v = v + ap["bv"].reshape(KV, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, ap["q_norm"], cfg.rmsnorm_eps)
        k = rmsnorm(k, ap["k_norm"], cfg.rmsnorm_eps)
    q = apply_rope(q, cos_sel, sin_sel)
    k = apply_rope(k, cos_sel, sin_sel)

    def scatter(base_b, vals_b, idx_b):
        return base_b.at[idx_b].set(vals_b, mode="drop")

    k_merged = jax.vmap(scatter)(k_base, k, sel_dst)
    v_merged = jax.vmap(scatter)(v_base, v, sel_dst)

    S = k_base.shape[1]
    kv_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    out = gqa_attention(q, k_merged, v_merged, q_pos=sel_pos, kv_pos=kv_pos,
                        window=S, softcap=cfg.attn_logit_softcap)
    out = jnp.einsum("bnhk,hkd->bnd", out, ap["wo"].reshape(H, hd, D))
    h_sel = h_sel + shard(out, "act_resid")
    x2 = rmsnorm(h_sel, p["ln2"], cfg.rmsnorm_eps)
    if cfg.is_moe:
        m, _ = moe_block(x2, p["moe"], cfg=cfg, shard=shard)
        h_sel = h_sel + m
    else:
        h_sel = h_sel + swiglu_mlp(x2, p["mlp"], shard)
    return h_sel, k_merged, v_merged


def _paged_base_layer(ph: PagedHistory, aligned_k: jax.Array,
                      shared_v: jax.Array, B: int, theta: float):
    """Per-layer base-KV source for a :class:`PagedHistory`.

    Returns ``base_layer(l) -> (k_l [B, S, KV, hd], v_l)`` assembling
    layer ``l`` from: the group-shared aligned blocks, the paged span
    read straight out of ``pool[l][page_idx]`` (no rotation — the span's
    sources are its targets, the structural condition the collector
    gates on), and the RoPE-realigned dense tail. The full-history
    densify (``[B, L, S, ...]``) of the pre-paged path never happens;
    the per-layer read is the same stream the paged flash kernel issues
    from its BlockSpec index map on TPU.

    ``ph.span_len`` may be traced: the page table then covers at least
    the span (padded rows read any page and are masked off) and the tail
    lands at a traced offset, so one program serves every span length.
    """
    L, _, bt, KV, hd = ph.pool_k.shape
    S = aligned_k.shape[1]
    nbh = ph.page_idx.shape[1]
    T = ph.tail_len
    s0, ts = ph.start, ph.start + ph.span_len
    width = min(nbh * bt, S - s0)         # page rows placed from s0 on
    pos = jnp.arange(S, dtype=jnp.int32)
    in_span = ((pos >= s0) & (pos < ts))[None, :, None, None]
    al_tail_k = None
    if T:
        # the tail is fresh decode content cached at last round's
        # positions — the only part of the paged history that rotates
        tail_tgt = ts + jnp.arange(T, dtype=jnp.int32)
        tail_src = jax.lax.dynamic_slice_in_dim(ph.src, ts, T, axis=1)
        al_tail_k = jax.vmap(  # over batch
            lambda tk, srow: align_cached_keys(tk, srow, tail_tgt, theta)
        )(ph.tail_k, tail_src)

    def read_span(pool):
        x = pool[ph.page_idx].reshape(B, nbh * bt, KV, hd)[:, :width]
        return jnp.pad(x, ((0, 0), (s0, S - s0 - width), (0, 0), (0, 0)))

    def base_layer(l):
        k_l = jnp.broadcast_to(aligned_k[l][None], (B,) + aligned_k.shape[1:])
        v_l = jnp.broadcast_to(shared_v[l][None], k_l.shape)
        k_l = jnp.where(in_span, read_span(ph.pool_k[l]), k_l)
        v_l = jnp.where(in_span, read_span(ph.pool_v[l]), v_l)
        if T:
            k_l = jax.lax.dynamic_update_slice_in_dim(
                k_l, al_tail_k[:, l], ts, axis=1)
            v_l = jax.lax.dynamic_update_slice_in_dim(
                v_l, ph.tail_v[:, l], ts, axis=1)
        return k_l, v_l

    return base_layer


def pic_prefill(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,        # [B, S] int32 — the request group
    shared_k: jax.Array,      # [L, S, KV, hd] — group-shared cached keys
    shared_v: jax.Array,      # [L, S, KV, hd]
    shared_src: jax.Array,    # [S] int32 — source positions of shared values
    shared_mask: jax.Array,   # [S] bool — shared-cached positions
    n_sel: int,               # static: number of recomputed positions
    *,
    priv_k: Optional[jax.Array] = None,    # [B, L, S, KV, hd]
    priv_v: Optional[jax.Array] = None,
    priv_src: Optional[jax.Array] = None,  # [B, S]
    priv_mask: Optional[jax.Array] = None,  # [S] bool
    priv_hist: Optional[PagedHistory] = None,  # paged dual of priv_k/priv_v
    check_layer: int = 1,
    pooled_selection: bool = False,
    block_select: int = 0,
    shard=_noshard,
    length=None,              # real prompt length (int32 scalar) <= S
    n_sel_real=None,          # real budget (int32 scalar) <= n_sel
) -> PICResult:
    """CacheBlend-style recovery for a group of requests (see module doc).

    Selection is per-request but computed in ONE batched pass for the
    whole group (the paper's collective semantics — outputs are identical
    to per-request PIC, only the execution is grouped). The per-request
    baseline calls this with B=1 per agent, paying N passes.

    ``block_select`` > 0 selects whole token blocks of that size instead of
    scattered tokens (EPIC-style). This is the TPU-tile-aligned variant:
    recomputed positions then cluster into contiguous blocks, so the
    Mirror diffs of Diff-Aware Storage stay block-sparse (paper §4.3's
    clustering assumption made structural). ``n_sel`` must be a multiple
    of ``block_select`` and large enough to cover every fresh-token block.

    Private histories arrive either dense (``priv_k``/``priv_v``) or as
    a :class:`PagedHistory` (``priv_hist``). The paged form is consumed
    layer-at-a-time: each layer's base KV reads ``pool[l][page_idx]``
    exactly where that layer's attention/merge consumes it, so the pages
    reach attention without a dense per-request private cache ever being
    materialized. The two forms are bit-identical (pure data movement +
    a skipped identity rotation).

    Bucketed form: ``tokens`` and every per-position input may be
    right-padded past the real prompt, whose ``length`` is then passed
    as an operand, and ``n_sel`` may be an upper bound over the bucket
    with the real budget in ``n_sel_real``. Padded positions are neither
    cached nor selectable; the budget's surplus slots repeat the last
    real position, and their rows are never written back. The first
    ``n_sel_real`` entries of ``sel_idx``, the logits and the recovered
    KV at ``[:length]`` are then those of the unpadded pass.
    """
    assert cfg.has_attention and not cfg.has_ssm, \
        "PIC applies to attention KV caches only (see DESIGN.md §5)"
    assert priv_k is None or priv_hist is None, \
        "pass dense priv_k/priv_v OR a PagedHistory, not both"
    B, S = tokens.shape
    L = cfg.n_layers
    theta = cfg.rope_theta
    tgt_pos = jnp.arange(S, dtype=jnp.int32)
    is_cached = shared_mask if priv_mask is None else (shared_mask | priv_mask)
    last = S - 1 if length is None else length - 1    # last real position
    n_real = n_sel if n_sel_real is None else n_sel_real

    # ---- 1. alignment ------------------------------------------------------
    # shared blocks: ONE rotation for the whole group. ``base_layer(l)``
    # is the single source of each layer's pre-recovery KV; the dense
    # path precomputes all layers at once (unchanged behavior), the
    # paged path assembles one layer at a time from the page pool.
    aligned_k = align_cached_keys(shared_k, shared_src, tgt_pos, theta)
    if priv_hist is not None:
        base_layer = _paged_base_layer(
            priv_hist, aligned_k, shared_v, B, theta)
    else:
        base_k = jnp.broadcast_to(
            aligned_k[:, None], (L, B, S) + aligned_k.shape[-2:])
        base_v = jnp.broadcast_to(shared_v[:, None], base_k.shape)
        if priv_k is not None:
            # private caches: per-request rotation (inherently private)
            al_priv = jax.vmap(  # over batch
                lambda pk, ps: align_cached_keys(pk, ps, tgt_pos, theta)
            )(priv_k, priv_src)
            pm = priv_mask[None, None, :, None, None]
            base_k = jnp.where(pm, jnp.swapaxes(al_priv, 0, 1), base_k)
            base_v = jnp.where(pm, jnp.swapaxes(priv_v, 0, 1), base_v)

        def base_layer(l, _bk=base_k, _bv=base_v):
            return _bk[l], _bv[l]

    # ---- 2. fresh pass over the first check_layer+1 layers ---------------
    h = jnp.take(params["embed"], tokens, axis=0).astype(shared_k.dtype)
    positions = jnp.broadcast_to(tgt_pos[None], (B, S))
    cos, sin = rope_cos_sin(positions, cfg.resolved_head_dim, theta)
    fresh_k, fresh_v = [], []
    for l in range(check_layer + 1):
        h, k, v = _fresh_block(h, _layer(params, l), cfg, positions, cos, sin, shard)
        fresh_k.append(k)
        fresh_v.append(v)

    # ---- 3. importance selection on the check layer -----------------------
    # (the paged path reads the check layer's pages here — a one-layer
    # streamed read feeding a [B, S] reduction, not a cache copy; XLA
    # CSEs it with the identical read in the merge loop below)
    base_chk_k, _ = base_layer(check_layer)
    dk = fresh_k[check_layer].astype(jnp.float32) - \
        base_chk_k.astype(jnp.float32)
    deviation = jnp.sum(dk * dk, axis=(-1, -2))            # [B, S]
    deviation = jnp.where(is_cached[None], deviation, 0.0)
    scores = jnp.where(is_cached[None], deviation, BIG)    # fresh always win
    # padding is neither cached nor fresh: it scores nothing, and the
    # last real token is always selected (its row gives the logits)
    scores = jnp.where(tgt_pos <= last, scores, 0.0)
    scores = scores + jnp.where(tgt_pos == last, 2 * BIG, 0.0)
    if pooled_selection:
        # beyond-paper option: ONE pooled set for the whole group. Aligns
        # every mirror's diff blocks with the master's recomputed blocks
        # (higher compression) at the cost of deviating from per-request
        # PIC output equivalence. Off by default (paper semantics).
        scores = jnp.broadcast_to(
            jnp.mean(scores, axis=0, keepdims=True), scores.shape)
    # surplus slots of a bucketed budget get an index past every real one
    # (so they sort last), then repeat the last real position
    if block_select:
        bt = block_select
        assert n_sel % bt == 0, "n_sel must be a multiple of block_select"
        nb_sel = n_sel // bt
        pad = (-S) % bt
        bscores = jnp.pad(scores, ((0, 0), (0, pad))).reshape(B, -1, bt)
        bscores = jnp.sum(bscores, axis=-1)                # [B, nb]
        nb = bscores.shape[1]
        whole_pad = jnp.arange(nb) * bt > last             # never selected
        _, bidx = jax.lax.top_k(jnp.where(whole_pad, -BIG, bscores), nb_sel)
        bidx = jnp.where(jnp.arange(nb_sel) < n_real // bt, bidx, nb)
        bidx = jnp.sort(bidx, axis=-1)                     # [B, nb_sel]
        idx = (bidx[:, :, None] * bt
               + jnp.arange(bt, dtype=bidx.dtype)[None, None, :])
        # clips a partial last block and the surplus slots
        sel_idx = jnp.minimum(idx.reshape(B, n_sel), last)
    else:
        _, idx = jax.lax.top_k(jnp.where(tgt_pos <= last, scores, -BIG),
                               n_sel)                      # per-request pass
        idx = jnp.where(jnp.arange(n_sel) < n_real, idx, S)
        sel_idx = jnp.minimum(jnp.sort(idx, axis=-1), last)
    # rows written back into the KV: the real slots only
    sel_dst = jnp.where(jnp.arange(n_sel) < n_real, sel_idx, S)

    # ---- 4. selective recomputation through the remaining layers ---------
    # one layer at a time: each layer's base KV comes from base_layer(l)
    # (dense: a precomputed slice; paged: pool pages read at the point of
    # use), the selected rows are overwritten fresh, and the result both
    # feeds that layer's attention and becomes the layer's recovered KV
    rec_ks, rec_vs = [], []

    def scatter_rows(base, vals, idx):
        return jax.vmap(lambda b, v_, i: b.at[i].set(v_, mode="drop"))(
            base, vals, idx)

    # layers <= check: keep aligned values except at selected rows (fresh)
    for l in range(check_layer + 1):
        bk_l, bv_l = base_layer(l)
        sel_k = jnp.take_along_axis(
            fresh_k[l], sel_idx[:, :, None, None], axis=1)
        sel_v = jnp.take_along_axis(
            fresh_v[l], sel_idx[:, :, None, None], axis=1)
        rec_ks.append(scatter_rows(bk_l, sel_k, sel_dst))
        rec_vs.append(scatter_rows(bv_l, sel_v, sel_dst))

    sel_pos = jnp.take_along_axis(positions, sel_idx, axis=1)  # [B, n_sel]
    cos_sel, sin_sel = rope_cos_sin(sel_pos, cfg.resolved_head_dim, theta)
    h_sel = jnp.take_along_axis(h, sel_idx[:, :, None], axis=1)

    for l in range(check_layer + 1, L):
        bk_l, bv_l = base_layer(l)
        h_sel, k_m, v_m = _selective_block(
            h_sel, _layer(params, l), cfg, sel_pos=sel_pos,
            cos_sel=cos_sel, sin_sel=sin_sel,
            k_base=bk_l, v_base=bv_l, sel_dst=sel_dst, shard=shard)
        rec_ks.append(k_m)
        rec_vs.append(v_m)
    rec_k = jnp.stack(rec_ks)
    rec_v = jnp.stack(rec_vs)

    # ---- 5. last-token logits --------------------------------------------
    is_last = sel_idx == last                               # [B, n_sel]
    row = jnp.argmax(is_last, axis=1)
    h_last = jnp.take_along_axis(h_sel, row[:, None, None], axis=1)
    logits = _logits(params, cfg, h_last, shard)[:, 0]

    return PICResult(rec_k, rec_v, deviation, sel_idx, logits, h_sel)


def n_sel_for(layout_fresh: int, n_cached: int, ratio: float) -> int:
    """Static selected-set size: every fresh position + ratio of cached."""
    import math
    return layout_fresh + max(1, int(math.ceil(ratio * n_cached)))


def n_sel_for_blocks(fresh_mask, bt: int, ratio: float,
                     length: int = 0) -> int:
    """Static selected-set size for block-granular selection.

    Counts the blocks containing any fresh token (always selected) plus
    ``ratio`` of the pure-cached blocks, and returns it in tokens. With
    ``length`` past the prompt, the blocks of the padding count as
    cached: the budget of a prompt padded to ``length``, an upper bound
    over every prompt of that bucket with the same fresh blocks.
    """
    import math

    import numpy as np
    fm = np.asarray(fresh_mask, bool).copy()
    S = fm.shape[0]
    pad = max(length - S, (-S) % bt)
    fm = np.pad(fm, (0, pad))
    # block containing the last token is always selected (logits)
    fm[S - 1] = True
    blocks = fm.reshape(-1, bt).any(axis=1)
    n_fresh_blocks = int(blocks.sum())
    n_cached_blocks = int(blocks.size - n_fresh_blocks)
    nb_sel = n_fresh_blocks + max(1, math.ceil(ratio * n_cached_blocks))
    return min(nb_sel, blocks.size) * bt
