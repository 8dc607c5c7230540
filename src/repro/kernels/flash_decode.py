"""Single-token flash decode attention — Pallas TPU kernels.

The decode half of the paged-attention story (ROADMAP "paged decode
attention — close the last dense consumer"): one query token against the
full accumulated KV. Two variants share the prefill kernels'
online-softmax recurrence (:func:`~repro.kernels.flash_prefill._softmax_update`,
imported rather than copied — the bit-exactness contract between the
dense and paged paths lives in that one function):

* :func:`flash_decode_kernel` — dense ``[KV, Sk, hd]`` K/V.
* :func:`flash_decode_paged_kernel` — K/V live in a round page pool
  ``[P, bt, KV, hd]``; each KV tile resolves through the
  scalar-prefetched page table in the BlockSpec index map (tile ``j`` →
  ``pool[page_idx[j]]``), with the current round's freshly generated
  tokens riding as a growing dense tail, exactly as in
  :func:`~repro.kernels.flash_prefill.flash_prefill_paged_kernel`.

The single query always sits at position ``skv - 1`` — the just-written
token attends over everything before it — so causality is carried
entirely by the validity mask ``cols < skv``; there is no per-row causal
triangle. The q operand arrives zero-padded to the f32 sublane tile from
the ops wrapper — the dense kernel takes one head's query as row 0 of 8,
the paged kernel one KV head's G query heads as its rows — and the
wrapper slices the padding back off; padded KV tiles past ``skv`` are
fully masked and contribute
exact zeros to the online softmax, so dense and paged runs stay
bit-identical even when their tile counts differ.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_prefill import (
    LANES,
    NEG_INF,
    _init_scratch,
    _softmax_update,
)

#: f32 sublane tile: the length-1 query is padded to this many rows
Q_ROWS = 8


def _decode_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                   scale, window, bq, bk, skv):
    j = pl.program_id(1)
    col0 = j * bk
    _init_scratch(j, m_scr, l_scr, acc_scr)
    qpos = skv - 1

    run = jnp.asarray(True)
    if window:
        run = run & (col0 + bk - 1 >= qpos - window + 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale            # [bq, hd]
        k = k_ref[0].astype(jnp.float32)                    # [bk, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bq, bk]
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        # validity by mask only (no run-skip), matching the prefill
        # kernel's kv_len convention: padded trailing tiles execute as
        # exact no-ops, keeping dense/paged tile sequences bit-identical
        mask = cols < skv
        if window:
            mask &= (qpos - cols) < window
        s = jnp.where(mask, s, NEG_INF)
        _softmax_update(s, v_ref[0].astype(jnp.float32),
                        o_ref, m_scr, l_scr, acc_scr)


def flash_decode_kernel(
    q: jax.Array,        # [H, Bq, hd] — Bq rows all carry the one query
    k: jax.Array,        # [KV, Skp, hd], Skp % block_k == 0
    v: jax.Array,
    *,
    kv_len: int | None = None,   # valid KV prefix; query sits at kv_len - 1
    window: int = 0,             # 0 = unbounded
    scale: float | None = None,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    H, Bq, hd = q.shape
    KV, Skp, _ = k.shape
    G = H // KV
    bk = min(block_k, Skp)
    assert Skp % bk == 0, \
        "pad Sk to the KV tile (see ops.flash_decode for the " \
        "pad-and-slice wrapper callers should use instead)"
    nk = Skp // bk
    skv = kv_len if kv_len is not None else Skp
    scale = scale if scale is not None else hd ** -0.5

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, bq=Bq, bk=bk, skv=skv)
    return pl.pallas_call(
        kernel,
        grid=(H, nk),
        in_specs=[
            pl.BlockSpec((1, Bq, hd), lambda h, j: (h, 0, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, j: (h // G, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, j: (h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, Bq, hd), lambda h, j: (h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((H, Bq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((Bq, LANES), jnp.float32),
            pltpu.VMEM((Bq, LANES), jnp.float32),
            pltpu.VMEM((Bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# --------------------------------------------------------------------------
# paged variant: KV tiles resolved through a page table
# --------------------------------------------------------------------------
def _paged_decode_kernel(pidx_ref, q_ref, pk_ref, pv_ref, tk_ref, tv_ref,
                         o_ref, m_scr, l_scr, acc_scr, *,
                         scale, window, bq, bt, nbh, span_len, skv):
    j = pl.program_id(0)
    is_page = j < nbh
    # dense-equivalent position of this tile's first KV token: page tiles
    # sit at j*bt, tail tiles start right after the (possibly ragged) span
    col0 = jnp.where(is_page, j * bt, span_len + (j - nbh) * bt)
    _init_scratch(j, m_scr, l_scr, acc_scr)
    qpos = skv - 1

    run = jnp.asarray(True)
    if window:
        run = run & (col0 + bt - 1 >= qpos - window + 1)

    @pl.when(run)
    def _compute():
        k_all = jnp.where(is_page, pk_ref[0].astype(jnp.float32),
                          tk_ref[...].astype(jnp.float32))  # [bt, KV, hd]
        v_all = jnp.where(is_page, pv_ref[0].astype(jnp.float32),
                          tv_ref[...].astype(jnp.float32))
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bt), 1)
        # a ragged last page carries slots past span_len; padded tail rows
        # sit past skv — both are masked out, never re-laid-out
        mask = cols < jnp.where(is_page, span_len, skv)
        if window:
            mask &= (qpos - cols) < window
        # each KV head's query heads are the rows of one [bq, hd] tile
        for g in range(k_all.shape[1]):
            q = q_ref[g].astype(jnp.float32) * scale        # [bq, hd]
            s = jax.lax.dot_general(
                q, k_all[:, g, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)         # [bq, bt]
            s = jnp.where(mask, s, NEG_INF)
            _softmax_update(s, v_all[:, g, :], o_ref.at[pl.ds(g, 1)],
                            m_scr.at[g], l_scr.at[g], acc_scr.at[g])


def flash_decode_paged_kernel(
    q: jax.Array,          # [KV, Bq, hd] — row r of KV head g is query head g*G+r
    pool_k: jax.Array,     # [P, bt, KV, hd] round page pool (one layer)
    pool_v: jax.Array,
    page_idx: jax.Array,   # int32 [nbh] — KV tile j lives in pool[page_idx[j]]
    tail_k: jax.Array,     # [Tp, KV, hd] dense generated tail, Tp % bt == 0
    tail_v: jax.Array,
    *,
    span_len: int,         # tokens valid from pages (nbh = ceil(span_len/bt))
    tail_len: int,         # tokens valid in the tail (<= Tp)
    window: int = 0,       # 0 = unbounded
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Flash decode whose KV stream reads pool pages in place.

    Dense-equivalent contract (pinned bit-for-bit in tests when the tile
    boundaries coincide, i.e. ``span_len % bt == 0``)::

        kd = concat(pool_k[page_idx].reshape(-1, KV, hd)[:span_len],
                    tail_k[:tail_len])            # then axes -> [KV, S, hd]
        flash_decode_kernel(q, kd, vd, block_k=bt) == paged(q, pool, ...)

    except that ``kd`` is never materialized: the page table is a
    scalar-prefetch operand, so each KV tile's HBM→VMEM copy is issued
    straight against ``pool[page_idx[j]]`` (the tail rides as trailing
    tiles). The query sits at position ``span_len + tail_len - 1``.

    The grid is ``(nk,)``: a page tile carries every KV head, so each
    page crosses HBM→VMEM exactly once per step, and the G query heads
    of a KV head ride as the rows of one ``[Bq, hd]`` tile (``Bq`` is G
    padded to the sublane minimum; see ``ops.flash_decode_paged``).
    """
    KV, Bq, hd = q.shape
    P, bt, _, _ = pool_k.shape
    assert pool_k.shape[2] == KV, (pool_k.shape, q.shape)
    nbh = int(page_idx.shape[0])
    assert span_len > 0 and nbh == -(-span_len // bt), (span_len, bt, nbh)
    assert tail_k.shape[0] % bt == 0 and tail_k.shape[0] >= tail_len
    skv = span_len + tail_len
    nt = -(-tail_len // bt)
    nk = nbh + nt
    scale = scale if scale is not None else hd ** -0.5

    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, window=window,
        bq=Bq, bt=bt, nbh=nbh, span_len=span_len, skv=skv)

    def qmap(j, pidx):
        return (0, 0, 0)

    def pmap(j, pidx):
        # page tiles resolve through the prefetched table; clamped for
        # tail steps (the fetched page is ignored there)
        return (pidx[jnp.minimum(j, nbh - 1)], 0, 0, 0)

    def tmap(j, pidx):
        return (jnp.clip(j - nbh, 0, max(nt - 1, 0)), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nk,),
        in_specs=[
            pl.BlockSpec((KV, Bq, hd), qmap),
            pl.BlockSpec((1, bt, KV, hd), pmap),
            pl.BlockSpec((1, bt, KV, hd), pmap),
            pl.BlockSpec((bt, KV, hd), tmap),
            pl.BlockSpec((bt, KV, hd), tmap),
        ],
        out_specs=pl.BlockSpec((KV, Bq, hd), qmap),
        scratch_shapes=[
            pltpu.VMEM((KV, Bq, LANES), jnp.float32),
            pltpu.VMEM((KV, Bq, LANES), jnp.float32),
            pltpu.VMEM((KV, Bq, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((KV, Bq, hd), q.dtype),
        interpret=interpret,
    )(page_idx, q, pool_k, pool_v, tail_k, tail_v)
