"""Diff-Aware Storage — Master-Mirror layout with block-sparse diffs
(paper §4.3, Fig. 8).

After collective reuse, the N recovered caches of a round differ only at
the privately-recomputed positions. Storage keeps ONE dense Master cache
and encodes every sibling as a Mirror: the indices of the 32-token blocks
that differ plus the K/V correction values for exactly those blocks. K and
V share the block-index list (as in the paper's implementation). Reads
return a lightweight :class:`MirrorHandle`; materialization is deferred to
the restore path (core.restore / kernels.diff_restore).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pic import bucket_len
from repro.models.layers import rope_shift

BLOCK_TOKENS = 32


def _pad_to_blocks(x: jax.Array, bt: int) -> jax.Array:
    """Pad the token axis (axis=1 of [L, S, KV, hd]) to a block multiple."""
    S = x.shape[1]
    pad = (-S) % bt
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return x


@jax.jit
def take_blocks(x: jax.Array, idx: jax.Array) -> jax.Array:
    """Blocks ``idx`` (int32 [n], in range) of a blocked array
    ``[L, nb, ...]``: ``x[:, idx]`` as one program per (shape, n). How
    many blocks a mirror's diff holds follows from the data, so each new
    count compiles; eager indexing would compile (and run) several small
    programs for it besides the gather."""
    return x[:, idx]


@dataclass
class MasterCache:
    """The one dense cache kept per round group."""

    rid: str
    k: jax.Array            # [L, S, KV, hd]
    v: jax.Array
    positions: np.ndarray   # int32 [S] absolute positions of entries

    def nbytes(self) -> int:
        return 2 * self.k.size * self.k.dtype.itemsize


@dataclass
class MirrorDiff:
    """Block-sparse correction of one sibling cache against its Master."""

    rid: str
    master_rid: str
    block_idx: np.ndarray    # int32 [nb] touched 32-token blocks (shared K/V)
    k_vals: jax.Array        # [L, nb, bt, KV, hd]
    v_vals: jax.Array        # [L, nb, bt, KV, hd]
    old_pos: np.ndarray      # master frame positions  [S]
    new_pos: np.ndarray      # mirror frame positions  [S]
    seq_len: int
    block_tokens: int = BLOCK_TOKENS

    @property
    def n_blocks(self) -> int:
        return int(self.block_idx.shape[0])

    @property
    def total_blocks(self) -> int:
        return -(-self.seq_len // self.block_tokens)

    def nbytes(self) -> int:
        data = 2 * self.k_vals.size * self.k_vals.dtype.itemsize
        meta = self.block_idx.nbytes + self.old_pos.nbytes + self.new_pos.nbytes
        return data + meta


@dataclass
class MirrorHandle:
    """Lazy read object: Master reference + sparse diff metadata. The dense
    Mirror tensor is never materialized at rest (paper §4.3 'On read')."""

    master: MasterCache
    diff: MirrorDiff

    def nbytes(self) -> int:      # storage cost attributable to this mirror
        return self.diff.nbytes()


# --------------------------------------------------------------------------
# diff construction
# --------------------------------------------------------------------------
def block_diff_mask(
    master_k: jax.Array, master_v: jax.Array,     # [L, S, KV, hd]
    mirror_k: jax.Array, mirror_v: jax.Array,
    *,
    block_tokens: int = BLOCK_TOKENS,
    tol: float = 0.0,
) -> jax.Array:
    """Bool [n_blocks]: True where any position in the 32-token block
    differs (union over layers and K/V planes, matching the shared
    block-index list of the implementation)."""
    mk = _pad_to_blocks(master_k, block_tokens)
    mv = _pad_to_blocks(master_v, block_tokens)
    xk = _pad_to_blocks(mirror_k, block_tokens)
    xv = _pad_to_blocks(mirror_v, block_tokens)
    nb = mk.shape[1] // block_tokens

    def blockify(a):
        L, Sp, KV, hd = a.shape
        return a.reshape(L, nb, block_tokens, KV, hd)

    dk = jnp.abs(blockify(xk) - blockify(mk)).max(axis=(0, 2, 3, 4))
    dv = jnp.abs(blockify(xv) - blockify(mv)).max(axis=(0, 2, 3, 4))
    return jnp.maximum(dk, dv) > tol


def build_mirror(
    rid: str,
    master: MasterCache,
    mirror_k: jax.Array,
    mirror_v: jax.Array,
    new_pos: np.ndarray,
    *,
    block_tokens: int = BLOCK_TOKENS,
    tol: float = 0.0,
) -> MirrorDiff:
    """Encode one sibling cache as a block-sparse diff against the Master.

    If the Mirror lives at different absolute positions than the Master
    (cross-group fallback), the Master's keys are first RoPE-aligned into
    the Mirror's frame so position-induced differences don't inflate the
    diff (the restore path replays the same rotation, Alg. 1 line 9).
    """
    old_pos = np.asarray(master.positions, np.int32)
    new_pos = np.asarray(new_pos, np.int32)
    base_k = master.k
    if not np.array_equal(old_pos, new_pos):
        # theta is read off the rotation period implied by head_dim later;
        # callers pass theta via functools.partial when it differs.
        raise ValueError(
            "build_mirror requires aligned frames; use build_mirror_aligned")
    mask = np.asarray(block_diff_mask(
        base_k, master.v, mirror_k, mirror_v,
        block_tokens=block_tokens, tol=tol))
    idx = np.flatnonzero(mask).astype(np.int32)

    xk = _pad_to_blocks(mirror_k, block_tokens)
    xv = _pad_to_blocks(mirror_v, block_tokens)
    L, Sp, KV, hd = xk.shape
    nb_total = Sp // block_tokens
    kb = xk.reshape(L, nb_total, block_tokens, KV, hd)
    vb = xv.reshape(L, nb_total, block_tokens, KV, hd)
    return MirrorDiff(
        rid=rid, master_rid=master.rid,
        block_idx=idx,
        k_vals=kb[:, idx], v_vals=vb[:, idx],
        old_pos=old_pos, new_pos=new_pos,
        seq_len=int(mirror_k.shape[1]), block_tokens=block_tokens)


def build_round_family(
    request_ids: Sequence[str],
    ks: jax.Array,             # [N, L, S, KV, hd] recovered caches
    vs: jax.Array,
    positions: np.ndarray,     # [S] shared target positions (compatible group)
    master_idx: int,
    *,
    block_tokens: int = BLOCK_TOKENS,
    tol: float = 0.0,
) -> Tuple[MasterCache, List[MirrorHandle]]:
    """Compress a round group's caches into Master + Mirrors.

    The master index comes from the reuse plan (lowest total deviation);
    storage then drops N-1 dense caches. The block-diff masks for ALL
    mirrors are computed in one vectorized pass (store-path perf
    iteration, EXPERIMENTS.md §Perf) rather than once per mirror.
    """
    master = MasterCache(
        rid=request_ids[master_idx], k=ks[master_idx], v=vs[master_idx],
        positions=np.asarray(positions, np.int32))
    N, L, S, KV, hd = ks.shape
    bt = block_tokens
    pad = (-S) % bt
    nb = (S + pad) // bt

    def blockify(a):
        if pad:
            a = jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        return a.reshape(N, L, nb, bt, KV, hd)

    # one mirror's blocks at a time, padded to the round programs' bucket:
    # the diff-row gathers then follow the bucket and the row count alone,
    # not the round length
    grow = ((0, 0), (0, bucket_len(S, bt) // bt - nb), (0, 0), (0, 0), (0, 0))

    def rows(b, idx):
        return take_blocks(jnp.pad(b, grow) if grow[1][1] else b, idx)

    kb, vb = blockify(ks), blockify(vs)
    dk = jnp.abs(kb - kb[master_idx]).max(axis=(1, 3, 4, 5))   # [N, nb]
    dv = jnp.abs(vb - vb[master_idx]).max(axis=(1, 3, 4, 5))
    masks = np.asarray(jnp.maximum(dk, dv) > tol)

    handles = []
    for i, rid in enumerate(request_ids):
        if i == master_idx:
            continue
        idx = np.flatnonzero(masks[i]).astype(np.int32)
        diff = MirrorDiff(
            rid=rid, master_rid=master.rid,
            block_idx=idx,
            k_vals=rows(kb[i], idx), v_vals=rows(vb[i], idx),
            old_pos=master.positions, new_pos=master.positions,
            seq_len=S, block_tokens=bt)
        handles.append(MirrorHandle(master, diff))
    return master, handles


def trim_family(handles: Sequence[MirrorHandle],
                seq_len: int, *, start: int = 0) -> List[MirrorHandle]:
    """Restrict a Master family to the token span ``[start, seq_len)``.

    Restore work then covers only the blocks a consumer will actually
    read: with the default ``start=0`` that is a prefix (e.g. the serving
    engine's history span) — the trimmed Master keeps
    ``ceil(seq_len / bt)`` blocks and each mirror keeps only the diff
    blocks that fall inside them, so the page-sharing restore pool
    shrinks from ``nb + M*ndb`` to ``nbh + M*ndb_h`` pages. A non-zero
    ``start`` (block-aligned) trims to a *delta* span instead: the
    cross-round incremental restore uses this to restore only the
    ``[H_{r-1}, H_r)`` tokens a round appended to each history, with
    block indices re-based so the trimmed family is self-contained.
    Within the kept span the restored values are bit-identical to
    restoring the full family and slicing.
    """
    assert handles, "empty family"
    master = handles[0].master
    bt = handles[0].diff.block_tokens
    full = handles[0].diff.seq_len
    assert 0 <= start < seq_len <= full, (start, seq_len, full)
    assert start % bt == 0, \
        (start, bt, "delta trim must start on a block boundary")
    for h in handles:
        assert h.master is master or h.diff.master_rid == master.rid, \
            "trim_family needs one shared Master"
        assert h.diff.block_tokens == bt and h.diff.seq_len == full, \
            "family mirrors must share block size and length"
    if seq_len == full and start == 0:
        return list(handles)
    b0 = start // bt
    nbh = -(-seq_len // bt)
    tm = MasterCache(
        rid=master.rid, k=master.k[:, start:seq_len],
        v=master.v[:, start:seq_len],
        positions=np.asarray(master.positions[start:seq_len], np.int32))
    out = []
    for h in handles:
        d = h.diff
        bidx = np.asarray(d.block_idx)
        keep = np.flatnonzero((bidx >= b0) & (bidx < nbh))
        out.append(MirrorHandle(tm, MirrorDiff(
            rid=d.rid, master_rid=d.master_rid,
            block_idx=(bidx[keep] - b0).astype(np.int32),
            k_vals=take_blocks(d.k_vals, keep.astype(np.int32)),
            v_vals=take_blocks(d.v_vals, keep.astype(np.int32)),
            old_pos=np.asarray(d.old_pos[start:seq_len], np.int32),
            new_pos=np.asarray(d.new_pos[start:seq_len], np.int32),
            seq_len=seq_len - start, block_tokens=bt)))
    return out


# --------------------------------------------------------------------------
# family packing for the batched restore kernel
# --------------------------------------------------------------------------
@dataclass
class FamilyPack:
    """Stacked per-family diff tensors consumed by the family-batched
    restore kernel (kernels.diff_restore.fused_family_restore_kernel).

    Ragged per-mirror diff counts are padded to the family max ``ndb``;
    padded rows are never addressed because ``diff_slot`` only maps the
    real rows (-1 elsewhere).
    """

    rids: List[str]          # mirror request ids, kernel row order
    diff_k: jax.Array        # [M, L, ndb, bt, KV, hd]
    diff_v: jax.Array
    diff_slot: np.ndarray    # int32 [M, nb]: row into diff_*[m] or -1
    delta_pos: np.ndarray    # int32 [M, nb, bt] RoPE recovery deltas
    nb: int                  # blocks per mirror (padded seq / bt)
    block_tokens: int
    seq_len: int

    @property
    def n_mirrors(self) -> int:
        return len(self.rids)

    def nbytes(self) -> int:
        data = 2 * self.diff_k.size * self.diff_k.dtype.itemsize
        return data + self.diff_slot.nbytes + self.delta_pos.nbytes


def pack_family(handles: Sequence[MirrorHandle]) -> FamilyPack:
    """Stack a Master family's mirror diffs into the dense per-family
    tensors the batched restore kernel consumes (one launch per family).

    All handles must share the same Master and block size. Per-mirror
    diff counts may be ragged; values are padded with zeros to the max.
    """
    assert handles, "empty family"
    master = handles[0].master
    bt = handles[0].diff.block_tokens
    S = handles[0].diff.seq_len
    for h in handles:
        assert h.master is master or h.diff.master_rid == master.rid, \
            "pack_family needs one shared Master"
        assert h.diff.block_tokens == bt and h.diff.seq_len == S, \
            "family mirrors must share block size and length"
    nb = -(-S // bt)
    Sp = nb * bt
    L, _, KV, hd = master.k.shape
    ndb = max(1, max(h.diff.n_blocks for h in handles))
    M = len(handles)

    slot = np.full((M, nb), -1, np.int32)
    dpos = np.zeros((M, Sp), np.int32)
    ks, vs = [], []
    for m, h in enumerate(handles):
        d = h.diff
        slot[m, np.asarray(d.block_idx)] = np.arange(d.n_blocks)
        delta = np.asarray(d.new_pos, np.int64) - np.asarray(d.old_pos,
                                                             np.int64)
        dpos[m, : delta.shape[0]] = delta.astype(np.int32)
        pad = ndb - d.n_blocks
        kv, vv = d.k_vals, d.v_vals
        if pad:
            kv = jnp.pad(kv, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
            vv = jnp.pad(vv, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        ks.append(kv)
        vs.append(vv)
    return FamilyPack(
        rids=[h.diff.rid for h in handles],
        diff_k=jnp.stack(ks), diff_v=jnp.stack(vs),
        diff_slot=slot, delta_pos=dpos.reshape(M, nb, bt),
        nb=nb, block_tokens=bt, seq_len=S)


# --------------------------------------------------------------------------
# fallback master selection (no reuse plan available, paper §5)
# --------------------------------------------------------------------------
def similarity_master(token_lists: Sequence[np.ndarray]) -> int:
    """Token-similarity heuristic: pick the entry with the highest mean
    pairwise token overlap (Jaccard over token multisets)."""
    n = len(token_lists)
    if n == 1:
        return 0
    sets = [set(map(int, t)) for t in token_lists]
    scores = []
    for i in range(n):
        s = 0.0
        for j in range(n):
            if i == j:
                continue
            inter = len(sets[i] & sets[j])
            union = len(sets[i] | sets[j]) or 1
            s += inter / union
        scores.append(s)
    return int(np.argmax(scores))


# --------------------------------------------------------------------------
# accounting (feeds paper Fig. 12)
# --------------------------------------------------------------------------
def compression_stats(master: MasterCache,
                      handles: Sequence[MirrorHandle]) -> dict:
    dense_one = master.nbytes()
    n = 1 + len(handles)
    dense_total = dense_one * n
    stored = dense_one + sum(h.nbytes() for h in handles)
    changed = [h.diff.n_blocks for h in handles]
    return {
        "n_caches": n,
        "dense_bytes": dense_total,
        "stored_bytes": stored,
        "compression_ratio": dense_total / stored,
        "per_mirror_ratio": (dense_one / (sum(h.nbytes() for h in handles) / max(1, len(handles))))
        if handles else float("inf"),
        "avg_changed_blocks": float(np.mean(changed)) if changed else 0.0,
        "total_blocks": handles[0].diff.total_blocks if handles else 0,
    }
