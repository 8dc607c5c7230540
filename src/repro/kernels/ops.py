"""Jit'd wrappers around the Pallas kernels.

On a CPU backend the kernels execute with ``interpret=True``; on a TPU
backend they compile to Mosaic. ``use_kernel=False`` dispatches to the
pure-jnp oracle in :mod:`repro.kernels.ref`, which the tests sweep the
kernels against. The serving engine calls none of these wrappers yet: its
restore is the XLA scatter of ``core.restore.fused_restore_family_shared``
and its decode and recovery attention are XLA gathers over the page pool
(``models.transformer._decode_attention_paged``, ``core.pic``).
``chip_smoke.py`` runs the kernels on the chip against the oracles.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.block_diff import block_diff_kernel
from repro.kernels.diff_restore import (
    fused_diff_restore_kernel,
    fused_family_restore_kernel,
)
from repro.kernels.flash_decode import (
    Q_ROWS,
    flash_decode_kernel,
    flash_decode_paged_kernel,
)
from repro.kernels.flash_prefill import (
    flash_prefill_kernel,
    flash_prefill_paged_kernel,
)
from repro.kernels.rope_align import rope_align_kernel


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("theta", "use_kernel"))
def rope_align(k, src_pos, tgt_pos, theta: float, use_kernel: bool = True):
    """Re-rotate cached keys [S, KV, hd] from src to tgt positions."""
    if not use_kernel:
        return ref.rope_align_ref(k, src_pos, tgt_pos, theta)
    return rope_align_kernel(k, src_pos, tgt_pos, theta,
                             interpret=_interpret())


# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("bt", "use_kernel"))
def block_diff(master, mirror, bt: int = 32, use_kernel: bool = True):
    """Per-block max-abs difference [nb] between two [L, S, KV, hd] caches."""
    if not use_kernel:
        return ref.block_diff_ref(master, mirror, bt)
    return block_diff_kernel(master, mirror, bt, interpret=_interpret())


# --------------------------------------------------------------------------
def _pad_axis(x, axis: int, target: int):
    """Zero-pad ``x`` along ``axis`` up to ``target`` length."""
    if x.shape[axis] == target:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - x.shape[axis])
    return jnp.pad(x, pad)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "use_kernel"))
def flash_prefill(q, k, v, *, causal: bool = True, window: int = 0,
                  block_q: int = 128, block_k: int = 128,
                  use_kernel: bool = True):
    """Flash attention over [H, S, hd] q and [KV, S, hd] k/v.

    Ragged S is handled HERE, once: the kernel hard-asserts tile-aligned
    S, so this wrapper zero-pads q/k/v to the tile, masks the padded KV
    columns inside the kernel (``kv_len``), and slices the padded query
    rows off the output. Callers never reimplement the padding. Padding
    is bit-exact: masked columns score ``-inf`` and contribute exact
    zeros to the online softmax.
    """
    if not use_kernel:
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    S = q.shape[1]
    bq, bk = min(block_q, S), min(block_k, S)
    Sp = -(-S // math.lcm(bq, bk)) * math.lcm(bq, bk)
    out = flash_prefill_kernel(
        _pad_axis(q, 1, Sp), _pad_axis(k, 1, Sp), _pad_axis(v, 1, Sp),
        causal=causal, window=window, block_q=bq, block_k=bk,
        kv_len=S if Sp != S else None, interpret=_interpret())
    return out[:, :S]


# --------------------------------------------------------------------------
def paged_prefill_input_bytes(pool_k, tail_len: int) -> int:
    """Dense KV bytes :func:`flash_prefill_paged` materializes before its
    launch: the tail zero-padded to the page tile (k + v), nothing else —
    the span stays in the pool. Kept NEXT TO the wrapper whose padding
    rule it mirrors so the two cannot drift silently; the
    ``prefill_paged.json`` benchmark counts with this, and the
    zero-densify property itself is pinned by the monkeypatch-spy test
    in tests/test_paged_collector.py."""
    P, bt, KV, hd = pool_k.shape
    t_pad = max(bt, -(-tail_len // bt) * bt)
    return 2 * t_pad * KV * hd * pool_k.dtype.itemsize


@functools.partial(jax.jit, static_argnames=(
    "span_len", "causal", "window", "block_q", "use_kernel"))
def flash_prefill_paged(q, pool_k, pool_v, page_idx, tail_k=None, tail_v=None,
                        *, span_len: int, causal: bool = True, window: int = 0,
                        block_q: int = 128, use_kernel: bool = True):
    """Paged flash attention: q [H, S, hd] over KV read straight from a
    family page pool ([P, bt, KV, hd] + int32 page table [nbh]) with an
    optional dense decode tail ([T, KV, hd]) as the trailing segment.

    S must equal ``span_len + T``. The KV tile size is the page size
    ``bt`` (tiles and pages are the same object — that is what lets the
    BlockSpec index map resolve tile ``j`` to ``pool[page_idx[j]]``).
    Only the tail (O(T) bytes) and q-row padding are materialized; the
    span's O(S) bytes stay in the pool and are streamed by the kernel.
    ``use_kernel=False`` dispatches to the gather-then-attend oracle.
    """
    if not use_kernel:
        return ref.flash_attention_paged_ref(
            q, pool_k, pool_v, page_idx, tail_k, tail_v,
            span_len=span_len, causal=causal, window=window)
    bt = pool_k.shape[1]
    T = 0 if tail_k is None else tail_k.shape[0]
    S = q.shape[1]
    assert S == span_len + T, (S, span_len, T)
    Tp = max(bt, -(-T // bt) * bt)      # >= one tile so the specs are valid
    if tail_k is None:
        tail_k = jnp.zeros((Tp,) + pool_k.shape[2:], pool_k.dtype)
        tail_v = jnp.zeros((Tp,) + pool_v.shape[2:], pool_v.dtype)
    else:
        tail_k = _pad_axis(tail_k, 0, Tp)
        tail_v = _pad_axis(tail_v, 0, Tp)
    bq = min(block_q, S)
    Sp = -(-S // bq) * bq
    out = flash_prefill_paged_kernel(
        _pad_axis(q, 1, Sp), pool_k, pool_v, page_idx, tail_k, tail_v,
        span_len=span_len, tail_len=T, causal=causal, window=window,
        block_q=bq, interpret=_interpret())
    return out[:, :S]


# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("window", "block_k", "use_kernel"))
def flash_decode(q, k, v, *, window: int = 0, block_k: int = 128,
                 use_kernel: bool = True):
    """Single-token decode attention: q [H, 1, hd] at position ``Sk - 1``
    over k/v [KV, Sk, hd].

    Ragged Sk is handled HERE, once: the kernel hard-asserts tile-aligned
    KV, so this wrapper zero-pads k/v to the tile, masks the padded
    columns inside the kernel (``kv_len``), pads the length-1 query to
    the f32 sublane tile, and slices both paddings off the output.
    Padding is bit-exact: masked columns score ``-inf`` and contribute
    exact zeros to the online softmax.
    """
    if not use_kernel:
        return ref.flash_decode_ref(q, k, v, window=window)
    Sk = k.shape[1]
    bk = min(block_k, Sk)
    Skp = -(-Sk // bk) * bk
    out = flash_decode_kernel(
        _pad_axis(q, 1, Q_ROWS), _pad_axis(k, 1, Skp), _pad_axis(v, 1, Skp),
        kv_len=Sk, window=window, block_k=bk, interpret=_interpret())
    return out[:, :1]


def paged_decode_input_bytes(pool_k, tail_len: int) -> int:
    """Dense KV bytes :func:`flash_decode_paged` materializes before its
    launch: the current round's generated tail zero-padded to the page
    tile (k + v), nothing else — the history span and every sealed round
    page stay in the pool, so the per-step decode input is
    O(tail + 1 page) and independent of the history span. Kept NEXT TO
    the wrapper whose padding rule it mirrors (the same contract as
    :func:`paged_prefill_input_bytes`); the ``decode_paged.json``
    benchmark counts with this."""
    P, bt, KV, hd = pool_k.shape
    t_pad = max(bt, -(-tail_len // bt) * bt)
    return 2 * t_pad * KV * hd * pool_k.dtype.itemsize


@functools.partial(jax.jit, static_argnames=(
    "span_len", "window", "use_kernel"))
def flash_decode_paged(q, pool_k, pool_v, page_idx, tail_k=None, tail_v=None,
                       *, span_len: int, window: int = 0,
                       use_kernel: bool = True):
    """Paged single-token decode attention: q [H, 1, hd] over KV read
    straight from a round page pool ([P, bt, KV, hd] + int32 page table
    [nbh]) plus the dense tail ([T, KV, hd]) holding this round's
    freshly generated tokens — the only content with no sealed page yet.
    The query sits at position ``span_len + T - 1``.

    Only the padded tail and the q-row padding are materialized —
    O(tail + 1 page) per step, flat in the history span; the span's
    O(S) bytes stay in the pool and are streamed by the kernel.
    ``use_kernel=False`` dispatches to the gather-then-attend oracle.
    """
    if not use_kernel:
        return ref.flash_decode_paged_ref(
            q, pool_k, pool_v, page_idx, tail_k, tail_v,
            span_len=span_len, window=window)
    bt = pool_k.shape[1]
    T = 0 if tail_k is None else tail_k.shape[0]
    Tp = max(bt, -(-T // bt) * bt)      # >= one tile so the specs are valid
    if tail_k is None:
        tail_k = jnp.zeros((Tp,) + pool_k.shape[2:], pool_k.dtype)
        tail_v = jnp.zeros((Tp,) + pool_v.shape[2:], pool_v.dtype)
    else:
        tail_k = _pad_axis(tail_k, 0, Tp)
        tail_v = _pad_axis(tail_v, 0, Tp)
    # the G query heads of each KV head become the rows of one tile,
    # padded to a whole number of sublane tiles
    H, _, hd = q.shape
    KV = pool_k.shape[2]
    G = H // KV
    Gp = -(-G // Q_ROWS) * Q_ROWS
    out = flash_decode_paged_kernel(
        _pad_axis(q.reshape(KV, G, hd), 1, Gp), pool_k, pool_v, page_idx,
        tail_k, tail_v, span_len=span_len, tail_len=T, window=window,
        interpret=_interpret())
    return out[:, :G].reshape(H, 1, hd)


# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("theta", "use_kernel"))
def fused_diff_restore(master_k, master_v, diff_k, diff_v, diff_slot,
                       slot_map, delta_pos, theta: float,
                       pool_k, pool_v, use_kernel: bool = True):
    """Algorithm 1: block-sparse diff apply + RoPE recovery + paged write.

    master_k/v: [L, nb, bt, KV, hd]; diff_k/v: [L, ndb, bt, KV, hd];
    diff_slot/slot_map: [nb] int32; delta_pos: [nb, bt] int32;
    pools: [L, n_pages, bt, KV, hd]. Returns updated pools.
    """
    if diff_k.shape[1] == 0:  # keep index maps total: pad one zero row
        zshape = (diff_k.shape[0], 1) + diff_k.shape[2:]
        diff_k = jnp.zeros(zshape, diff_k.dtype)
        diff_v = jnp.zeros(zshape, diff_v.dtype)
    if not use_kernel:
        return ref.fused_diff_restore_ref(
            master_k, master_v, diff_k, diff_v, diff_slot, slot_map,
            delta_pos, theta, pool_k, pool_v)
    return fused_diff_restore_kernel(
        master_k, master_v, diff_k, diff_v, diff_slot, slot_map,
        delta_pos, theta, pool_k, pool_v, interpret=_interpret())


# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("theta", "use_kernel"))
def fused_family_restore(master_k, master_v, diff_k, diff_v, diff_slot,
                         slot_map, delta_pos, theta: float,
                         pool_k, pool_v, use_kernel: bool = True):
    """Family-batched Algorithm 1: one launch restores every mirror of a
    Master family; each Master block is streamed once and corrected for
    all M consumers while resident.

    master_k/v: [L, nb, bt, KV, hd]; diff_k/v: [M, L, ndb, bt, KV, hd];
    diff_slot/slot_map: [M, nb] int32 (slot maps disjoint across mirrors);
    delta_pos: [M, nb, bt] int32; pools: [L, n_pages, bt, KV, hd].
    Returns updated pools.
    """
    if diff_k.shape[2] == 0:  # keep index maps total: pad one zero row
        zshape = diff_k.shape[:2] + (1,) + diff_k.shape[3:]
        diff_k = jnp.zeros(zshape, diff_k.dtype)
        diff_v = jnp.zeros(zshape, diff_v.dtype)
    if not use_kernel:
        return ref.fused_family_restore_ref(
            master_k, master_v, diff_k, diff_v, diff_slot, slot_map,
            delta_pos, theta, pool_k, pool_v)
    return fused_family_restore_kernel(
        master_k, master_v, diff_k, diff_v, diff_slot, slot_map,
        delta_pos, theta, pool_k, pool_v, interpret=_interpret())
