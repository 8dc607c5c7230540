"""Causal / sliding-window flash attention prefill — Pallas TPU kernels.

The perf-critical compute layer of prefill (the phase TokenDance's
collective reuse accelerates). Online-softmax over KV tiles with VMEM
scratch for the running (max, sum, accumulator); GQA is handled by mapping
each query head to its KV head in the BlockSpec index map, so no repeated
K/V materialization. Block shapes are MXU-aligned (q/k tiles x head_dim).

Two variants share the same tile math:

* :func:`flash_prefill_kernel` — dense ``[KV, S, hd]`` K/V.
* :func:`flash_prefill_paged_kernel` — the paged consumer (ROADMAP
  "paged attention consumer"): K/V live in a family page pool
  ``[P, bt, KV, hd]`` (the output of §4.4's page-sharing restore) and a
  per-request page table resolves each KV tile in the BlockSpec index
  map (tile ``j`` → ``pool[page_idx[j]]``, scalar-prefetched so the
  HBM→VMEM stream reads pool pages in place). The request's dense
  decode tail — the only content with no pages yet — is handled as a
  trailing dense segment of the same tile size. On identical tile
  boundaries the two variants are bit-exact: paging changes where a
  tile is fetched from, never what is computed on it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
NEG_INF = -2.0 ** 30


def _init_scratch(j, m_scr, l_scr, acc_scr):
    """Reset the online-softmax state at each output tile's first step."""
    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)


def _softmax_update(s, v, o_ref, m_scr, l_scr, acc_scr):
    """One online-softmax step: fold scores ``s`` [bq, bk] and values
    ``v`` [bk, hd] (both f32) into the running (max, sum, accumulator)
    scratch and rewrite the output tile. Shared VERBATIM by the dense
    and paged kernels — the bit-exactness contract between them lives
    here (paging changes where a tile is fetched from, never this
    recurrence)."""
    m_prev = m_scr[:, :1]                               # [bq, 1]
    l_prev = l_scr[:, :1]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    m_scr[...] = jnp.broadcast_to(m_cur, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
    acc_scr[...] = acc
    o_ref[0] = (acc / jnp.maximum(l_new, 1e-30)).astype(o_ref.dtype)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, causal, window, bq, bk, nk, kv_len=None):
    i, j = pl.program_id(1), pl.program_id(2)
    row0 = i * bq
    col0 = j * bk
    _init_scratch(j, m_scr, l_scr, acc_scr)

    run = jnp.asarray(True)
    if causal:
        run = run & (col0 <= row0 + bq - 1)
    if window:
        run = run & (col0 + bk - 1 >= row0 - window + 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale            # [bq, hd]
        k = k_ref[0].astype(jnp.float32)                    # [bk, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bq, bk]
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= cols <= rows
        if window:
            mask &= (rows - cols) < window
        if kv_len is not None and kv_len < nk * bk:
            mask &= cols < kv_len                # pad-and-slice wrapper
        s = jnp.where(mask, s, NEG_INF)
        _softmax_update(s, v_ref[0].astype(jnp.float32),
                        o_ref, m_scr, l_scr, acc_scr)


def flash_prefill_kernel(
    q: jax.Array,        # [H, S, hd]
    k: jax.Array,        # [KV, S, hd]
    v: jax.Array,        # [KV, S, hd]
    *,
    causal: bool = True,
    window: int = 0,     # 0 = unbounded
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    kv_len: int | None = None,   # valid KV prefix (< S when S is padded)
    interpret: bool = False,
) -> jax.Array:
    H, S, hd = q.shape
    KV = k.shape[0]
    G = H // KV
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0, \
        "pad S to the attention tile (see ops.flash_prefill for the " \
        "pad-and-slice wrapper callers should use instead)"
    nq, nk = S // bq, S // bk
    scale = scale if scale is not None else hd ** -0.5

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, nk=nk, kv_len=kv_len)
    return pl.pallas_call(
        kernel,
        grid=(H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, i, j: (h // G, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, i, j: (h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# --------------------------------------------------------------------------
# paged variant: KV tiles resolved through a page table
# --------------------------------------------------------------------------
def kv_head(tile: jax.Array, g) -> jax.Array:
    """KV head ``g`` of a ``[bt, KV, hd]`` tile, as ``[bt, hd]``.

    A page tile carries every KV head: Mosaic cannot block a single head
    out of the second-minor axis, so the whole page is fetched and the
    head picked in registers. ``g`` may be traced (a grid index); the
    select chain keeps every slice static.
    """
    out = tile[:, 0, :]
    for i in range(1, tile.shape[1]):
        out = jnp.where(g == i, tile[:, i, :], out)
    return out


def _paged_kernel(pidx_ref, q_ref, pk_ref, pv_ref, tk_ref, tv_ref, o_ref,
                  m_scr, l_scr, acc_scr, *,
                  scale, causal, window, bq, bt, nbh, span_len, skv):
    kvh, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    row0 = i * bq
    is_page = j < nbh
    # dense-equivalent position of this tile's first KV token: page tiles
    # sit at j*bt, tail tiles start right after the (possibly ragged) span
    col0 = jnp.where(is_page, j * bt, span_len + (j - nbh) * bt)
    _init_scratch(j, m_scr, l_scr, acc_scr)

    run = jnp.asarray(True)
    if causal:
        run = run & (col0 <= row0 + bq - 1)
    if window:
        run = run & (col0 + bt - 1 >= row0 - window + 1)

    @pl.when(run)
    def _compute():
        k_all = jnp.where(is_page, pk_ref[0].astype(jnp.float32),
                          tk_ref[...].astype(jnp.float32))  # [bt, KV, hd]
        v_all = jnp.where(is_page, pv_ref[0].astype(jnp.float32),
                          tv_ref[...].astype(jnp.float32))
        k, v = kv_head(k_all, kvh), kv_head(v_all, kvh)     # [bt, hd]
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bt), 0)
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bt), 1)
        # a ragged last page carries slots past span_len; padded tail rows
        # sit past skv — both are masked out, never re-laid-out
        mask = cols < jnp.where(is_page, span_len, skv)
        if causal:
            mask &= cols <= rows
        if window:
            mask &= (rows - cols) < window
        # the G query heads sharing this KV head, one [bq, hd] tile each
        for g in range(q_ref.shape[0]):
            q = q_ref[g].astype(jnp.float32) * scale        # [bq, hd]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)         # [bq, bt]
            s = jnp.where(mask, s, NEG_INF)
            _softmax_update(s, v, o_ref.at[pl.ds(g, 1)], m_scr.at[g],
                            l_scr.at[g], acc_scr.at[g])


def flash_prefill_paged_kernel(
    q: jax.Array,          # [H, Sq, hd] — Sq a multiple of block_q
    pool_k: jax.Array,     # [P, bt, KV, hd] family page pool (one layer)
    pool_v: jax.Array,
    page_idx: jax.Array,   # int32 [nbh] — KV tile j lives in pool[page_idx[j]]
    tail_k: jax.Array,     # [Tp, KV, hd] dense decode tail, Tp % bt == 0
    tail_v: jax.Array,
    *,
    span_len: int,         # tokens valid from pages (nbh = ceil(span_len/bt))
    tail_len: int,         # tokens valid in the tail (<= Tp)
    causal: bool = True,
    window: int = 0,       # 0 = unbounded
    scale: float | None = None,
    block_q: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Flash prefill whose KV stream reads pool pages in place.

    Dense-equivalent contract (pinned bit-for-bit in tests when the tile
    boundaries coincide, i.e. ``span_len % bt == 0``)::

        kd = concat(pool_k[page_idx].reshape(-1, KV, hd)[:span_len],
                    tail_k[:tail_len])            # then axes -> [KV, S, hd]
        flash_prefill_kernel(q, kd, vd, block_k=bt) == paged(q, pool, ...)

    except that ``kd`` is never materialized: the page table is a
    scalar-prefetch operand, so each KV tile's HBM→VMEM copy is issued
    straight against ``pool[page_idx[j]]`` (the tail rides as trailing
    tiles). The q length must cover the full KV span
    (``Sq >= span_len + tail_len``, padded rows are sliced by the
    caller — see ``ops.flash_prefill_paged``).

    The grid is ``(KV, nq, nk)``: one step serves the G query heads of one
    KV head, so a page crosses HBM→VMEM once per KV head and q tile, not
    once per query head.
    """
    H, Sq, hd = q.shape
    P, bt, KV, _ = pool_k.shape
    G = H // KV
    nbh = int(page_idx.shape[0])
    assert span_len > 0 and nbh == -(-span_len // bt), (span_len, bt, nbh)
    assert tail_k.shape[0] % bt == 0 and tail_k.shape[0] >= tail_len
    bq = min(block_q, Sq)
    assert Sq % bq == 0, "pad Sq to the attention tile (ops.flash_prefill_paged)"
    skv = span_len + tail_len
    assert Sq >= skv, (Sq, skv)
    nt = -(-tail_len // bt)
    nq, nk = Sq // bq, nbh + nt
    scale = scale if scale is not None else hd ** -0.5

    kernel = functools.partial(
        _paged_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bt=bt, nbh=nbh, span_len=span_len, skv=skv)

    def qmap(kvh, i, j, pidx):
        return (kvh, i, 0)

    def pmap(kvh, i, j, pidx):
        # page tiles resolve through the prefetched table; clamped for
        # tail steps (the fetched page is ignored there)
        return (pidx[jnp.minimum(j, nbh - 1)], 0, 0, 0)

    def tmap(kvh, i, j, pidx):
        return (jnp.clip(j - nbh, 0, max(nt - 1, 0)), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(KV, nq, nk),
        in_specs=[
            pl.BlockSpec((G, bq, hd), qmap),
            pl.BlockSpec((1, bt, KV, hd), pmap),
            pl.BlockSpec((1, bt, KV, hd), pmap),
            pl.BlockSpec((bt, KV, hd), tmap),
            pl.BlockSpec((bt, KV, hd), tmap),
        ],
        out_specs=pl.BlockSpec((G, bq, hd), qmap),
        scratch_shapes=[
            pltpu.VMEM((G, bq, LANES), jnp.float32),
            pltpu.VMEM((G, bq, LANES), jnp.float32),
            pltpu.VMEM((G, bq, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, Sq, hd), q.dtype),
        interpret=interpret,
    )(page_idx, q, pool_k, pool_v, tail_k, tail_v)
