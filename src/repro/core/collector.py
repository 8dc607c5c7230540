"""KV Collector — collective KV cache reuse over an All-Gather round
(paper §4.2, Fig. 7).

Instead of N per-request reuse passes, the collector groups compatible
requests and performs ONE shared RoPE alignment and ONE pooled
important-position selection for the whole group; only the per-position
refresh remains request-specific. The reuse plan it emits (group
membership, per-request deviations, Master choice) is the bridge into
Diff-Aware Storage (§4.3).

Private histories may arrive PAGED (:class:`PagedPrivate`): a
family-shared page pool from the §4.4 restore plus per-request page
tables, consumed by the recovery pass WITHOUT densification — each
layer's attention reads its pages at the point of use (the XLA form of
``kernels.flash_prefill.flash_prefill_paged_kernel``'s page-table
BlockSpec). That keeps the "shared block restored once" property alive
through the attention launch itself; ``_densify_paged`` survives only
as the parity oracle (and the serial baseline's input form).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.pic import PagedHistory, PICResult, pic_prefill


@dataclass
class ReusePlan:
    """Metadata bridging collective reuse to Diff-Aware Storage."""

    request_ids: List[str]
    master: int                  # index into request_ids
    sel_idx: np.ndarray          # [n_sel] shared recomputed positions
    deviations: np.ndarray       # [N] total per-request deviation
    prompt_len: int
    n_sel: int
    #: [N, n_sel] per-request recomputed positions — the store path reads
    #: this to know exactly which blocks of each recovered cache differ
    #: from what the previous round's restore produced (the cross-round
    #: incremental restore's dirty set); None on the serial path
    sel_idx_all: Optional[np.ndarray] = None

    def mirror_indices(self) -> List[int]:
        return [i for i in range(len(self.request_ids)) if i != self.master]


@dataclass
class CollectiveResult:
    plan: ReusePlan
    pic: PICResult               # batched over the group
    priv_mode: str = "none"      # the _runner path that consumed ``priv``


@dataclass
class PagedPrivate:
    """Per-request private history handed to the collector in PAGED form.

    This is the bridge that keeps §4.4's page sharing alive end-to-end:
    the serving engine restores a Master family with
    ``fused_restore_family_shared`` (Master pages written once, mirror
    diff pages only) and hands the resulting pool + per-request page
    tables straight to :meth:`KVCollector.collective_reuse`, which
    passes them into the recovery pass as a
    :class:`~repro.core.pic.PagedHistory` — each layer's attention reads
    ``pool[l][page_idx]`` where it consumes it, so no dense
    ``[L, S, KV, hd]`` private cache is ever materialized, on the host
    or as a jit intermediate.

    Shape/dtype contracts (N requests, prompt length S):
      pool_k/pool_v: float [L, P, bt, KV, hd] — family-shared page pools.
      page_idx:      int32 [N, nbh] — request n's logical block b lives in
                     pool page ``page_idx[n, b]``; covers the first
                     ``span_len`` tokens (``nbh = ceil(span_len / bt)``).
      tail_k/tail_v: optional float [N, L, T, KV, hd] — dense suffix
                     placed right after the paged span (per-agent output
                     blocks that have no pages yet). May be None (T=0).
      src:           int32 [N, S] — absolute source positions of every
                     cached value (identity outside the private span).
      mask:          bool [S] — True on the private-history span
                     ``[start, start + span_len + T)``.
      start/span_len: static ints — placement of the paged span in the
                     prompt. They key the collector's jit cache.
    """

    pool_k: jax.Array
    pool_v: jax.Array
    page_idx: jax.Array          # int32 [N, nbh]
    src: jax.Array               # int32 [N, S]
    mask: jax.Array              # bool [S]
    start: int
    span_len: int
    tail_k: Optional[jax.Array] = None   # [N, L, T, KV, hd]
    tail_v: Optional[jax.Array] = None

    @property
    def tail_len(self) -> int:
        return 0 if self.tail_k is None else int(self.tail_k.shape[2])

    @property
    def n_requests(self) -> int:
        return int(self.page_idx.shape[0])

    def identity_span_src(self) -> bool:
        """True iff the paged span's source positions equal its target
        positions (``src[:, start+i] == start+i``) — the condition under
        which pool pages need no RoPE realignment. The serving engine's
        history layout satisfies this by construction (histories are
        compressed and restored in-place at prompt position 0)."""
        span = np.asarray(self.src[:, self.start : self.start + self.span_len])
        want = np.arange(self.start, self.start + self.span_len,
                         dtype=span.dtype)
        return bool(np.array_equal(span, np.broadcast_to(want, span.shape)))

    def fast_path_ok(self) -> bool:
        """Structural gate for the zero-densify fast path: the span needs
        no realignment (:meth:`identity_span_src`) AND ``mask`` is True
        exactly on the span+tail region the fast path writes — the dense
        oracle applies private values wherever ``mask`` says, the fast
        path writes ``[start, start + span_len + T)`` unconditionally, so
        the two are bit-identical only when those coincide. A bundle that
        fails either check falls back to the jit-level densify oracle
        (same results, extra data movement). Host-side check on the
        (host-built) ``src``/``mask`` tables, computed once per bundle —
        ``collective_reuse`` may be called repeatedly (warm-up + timed)
        without re-paying the device sync."""
        cached = self.__dict__.get("_fast_ok")
        if cached is None:
            region = np.zeros(np.asarray(self.mask).shape[0], bool)
            region[self.start : self.start + self.span_len + self.tail_len] \
                = True
            cached = (self.span_len > 0
                      and bool(np.array_equal(np.asarray(self.mask), region))
                      and self.identity_span_src())
            self.__dict__["_fast_ok"] = cached
        return cached

    def materialize(self, S: int) -> tuple:
        """Dense parity oracle: ``(pk, pv, psrc, pmask)`` exactly as the
        pre-paged collector consumed them ([N, L, S, KV, hd] etc.).
        Used by :meth:`KVCollector.serial_reuse` (the per-request
        baseline) and by parity tests; the collective fast path performs
        the same gather inside jit instead."""
        pk, pv = _densify_paged(
            self.pool_k, self.pool_v, self.page_idx, self.tail_k,
            self.tail_v, S=S, start=self.start, span_len=self.span_len)
        return pk, pv, self.src, self.mask


def _densify_paged(pool_k, pool_v, page_idx, tail_k, tail_v, *,
                   S: int, start: int, span_len: int):
    """Gather paged private histories into the dense per-request layout
    ``[N, L, S, KV, hd]`` (zeros outside the private span). Pure data
    movement — no arithmetic — so it is bit-identical to the per-layer
    page reads of the fast path. THE PARITY ORACLE, not the fast path:
    the collective runner only calls this in ``paged_densify`` mode
    (``paged_attention=False`` or a span that needs realignment);
    :meth:`PagedPrivate.materialize` and the serial baseline also go
    through it. The gather itself is
    :func:`repro.core.restore.gather_pages`, vmapped over requests —
    one definition of the page→dense layout for every consumer."""
    from repro.core.restore import gather_pages

    L, _, bt, KV, hd = pool_k.shape
    N, nbh = page_idx.shape
    gk, gv = jax.vmap(
        lambda row: gather_pages(pool_k, pool_v, row, span_len))(page_idx)
    pk = jnp.zeros((N, L, S, KV, hd), pool_k.dtype)
    pv = jnp.zeros((N, L, S, KV, hd), pool_v.dtype)
    pk = pk.at[:, :, start : start + span_len].set(gk)
    pv = pv.at[:, :, start : start + span_len].set(gv)
    if tail_k is not None:
        T = tail_k.shape[2]
        pk = pk.at[:, :, start + span_len : start + span_len + T].set(tail_k)
        pv = pv.at[:, :, start + span_len : start + span_len + T].set(tail_v)
    return pk, pv


@dataclass(frozen=True)
class GroupKey:
    """Compatibility key: same active prompt length + same cached-span
    layout (the execution constraints from §4.2), plus — when a gather
    topology is in play — the same gather-source set (agents receiving
    different output subsets share no block content, so they can never
    share one collective pass)."""

    prompt_len: int
    layout: Tuple[bool, ...]     # is_cached mask
    sources: Tuple[int, ...] = ()

    @classmethod
    def of(cls, prompt_len: int, is_cached: np.ndarray,
           sources: Tuple[int, ...] = ()) -> "GroupKey":
        return cls(prompt_len, tuple(bool(b) for b in is_cached), sources)


def group_compatible(
    requests: Sequence[Tuple[str, int, np.ndarray]],
    topology=None,
) -> List[List[str]]:
    """Group (request_id, prompt_len, is_cached) triples into compatible
    sets; incompatible requests fall into their own group (single-request
    fallback path). With a :class:`repro.core.rounds.GatherTopology`,
    requests additionally split by gather-source set — the reuse-plan
    grouping consumes the declared topology instead of assuming
    all-to-all."""
    src = ({} if topology is None
           else topology.sources([rid for rid, _, _ in requests]))
    groups: Dict[GroupKey, List[str]] = {}
    for rid, plen, mask in requests:
        key = GroupKey.of(plen, mask, src.get(rid, ()))
        groups.setdefault(key, []).append(rid)
    return list(groups.values())


class KVCollector:
    """Drives collective (or serial baseline) PIC recovery for round groups.

    Public API: :meth:`collective_reuse` (one shared pass per group, the
    paper's T3 path) and :meth:`serial_reuse` (N per-request passes, the
    T2 baseline). Both accept private histories either pre-densified or
    as a :class:`PagedPrivate` page-pool reference; in the collective
    case the page gather is part of the jitted recovery computation.

    Constructor knobs: ``check_layer`` (deviation-measurement layer),
    ``recompute_ratio`` (fraction of cached positions recomputed),
    ``block_select`` (>0 selects whole token blocks of that size —
    the TPU tile-aligned variant that keeps Mirror diffs block-sparse),
    ``pooled_selection`` (one pooled selected set per group — a
    beyond-paper option, off by default), ``shard`` (layer-output
    sharding hook for the multi-device path).
    """

    def __init__(self, params: dict, cfg: ModelConfig, *, check_layer: int = 1,
                 recompute_ratio: float = 0.15, block_select: int = 0,
                 pooled_selection: bool = False, shard=None):
        from repro.models.layers import _noshard
        self.params = params
        self.cfg = cfg
        self.check_layer = min(check_layer, cfg.n_layers - 1)
        self.recompute_ratio = recompute_ratio
        self.block_select = block_select
        self.pooled_selection = pooled_selection
        self.shard = shard or _noshard
        # jit caches keyed by (S, n_sel, share)
        self._jit_cache: dict = {}
        # counted work: one unit per RoPE-align + selection pass launched.
        # Wall-clock is CI-contention-flaky; tests assert on this instead.
        self.align_passes = 0

    # ------------------------------------------------------------------
    def _runner(self, S: int, n_sel: int, share: bool, priv_mode: str,
                paged_meta: tuple = ()):
        """Jitted recovery pass for one (shape, mode) signature.

        ``priv_mode`` is one of:
          "none"  — no private caches
          "dense" — trailing args (pk [N,L,S,KV,hd], pv, psrc [N,S],
                    pmask [S]) as pre-densified tensors
          "paged" — the zero-densify fast path: same trailing args as
                    below, but the pool + page tables flow into
                    ``pic_prefill`` as a :class:`PagedHistory` and each
                    layer's attention reads its pages at the point of
                    use — no ``_densify_paged``, no dense per-request
                    private cache anywhere in the jit
          "paged_densify" — the parity oracle: identical inputs, but the
                    pages are gathered into dense ``[N, L, S, KV, hd]``
                    tensors up front (``_densify_paged``) and recovery
                    runs the dense path. Selected when the fast path's
                    structural gate fails or ``paged_attention=False``.

        For both paged modes the trailing args are (pool_k
        [L,P,bt,KV,hd], pool_v, page_idx [N,nbh], [tail_k, tail_v,]
        psrc, pmask) and ``paged_meta = (start, span_len, has_tail)``
        are the static placement params.
        """
        key = (S, n_sel, share, priv_mode, paged_meta)
        if key not in self._jit_cache:
            def run(params, tokens, ck, cv, src, shared_mask, *args):
                pk = pv = psrc = pmask = None
                hist = None
                if priv_mode == "dense":
                    pk, pv, psrc, pmask = args
                elif priv_mode in ("paged", "paged_densify"):
                    start, span_len, has_tail = paged_meta
                    pool_k, pool_v, page_idx = args[:3]
                    tail_k, tail_v = args[3:5] if has_tail else (None, None)
                    psrc, pmask = args[5:] if has_tail else args[3:]
                    if priv_mode == "paged":
                        hist = PagedHistory(
                            pool_k=pool_k, pool_v=pool_v, page_idx=page_idx,
                            src=psrc, start=start, span_len=span_len,
                            tail_k=tail_k, tail_v=tail_v)
                        psrc = None
                    else:
                        pk, pv = _densify_paged(
                            pool_k, pool_v, page_idx, tail_k, tail_v,
                            S=tokens.shape[1], start=start, span_len=span_len)
                return pic_prefill(
                    params, self.cfg, tokens, ck, cv, src, shared_mask,
                    n_sel, priv_k=pk, priv_v=pv, priv_src=psrc,
                    priv_mask=pmask, priv_hist=hist,
                    check_layer=self.check_layer,
                    pooled_selection=share and self.pooled_selection,
                    block_select=self.block_select, shard=self.shard)
            self._jit_cache[key] = jax.jit(run)
        return self._jit_cache[key]

    @staticmethod
    def _priv_args(priv, paged_attention: bool = True) -> Tuple[str, tuple, tuple]:
        """(priv_mode, runner args, static paged_meta) for a ``priv`` that
        is None, a dense tuple, or a :class:`PagedPrivate`.

        A ``PagedPrivate`` selects the zero-densify fast path ("paged")
        when ``paged_attention`` is on AND its structure supports it
        (:meth:`PagedPrivate.fast_path_ok`); otherwise the jit-level
        densify oracle ("paged_densify") — bit-identical output either
        way."""
        if priv is None:
            return "none", (), ()
        if isinstance(priv, PagedPrivate):
            has_tail = priv.tail_k is not None
            args = (priv.pool_k, priv.pool_v, priv.page_idx)
            if has_tail:
                args += (priv.tail_k, priv.tail_v)
            args += (priv.src, priv.mask)
            fast = paged_attention and priv.fast_path_ok()
            return ("paged" if fast else "paged_densify", args,
                    (priv.start, priv.span_len, has_tail))
        return "dense", tuple(priv), ()

    # ------------------------------------------------------------------
    def collective_reuse(
        self,
        request_ids: List[str],
        tokens: jax.Array,          # [N, S]
        cached_k: jax.Array,        # [L, S, KV, hd]
        cached_v: jax.Array,
        src_pos: jax.Array,         # [S]
        shared_mask: jax.Array,     # [S]
        n_sel: int,
        priv=None,
        paged_attention: bool = True,
    ) -> CollectiveResult:
        """One collective recovery pass for the whole round group (the T3
        path of Fig. 7): ONE RoPE alignment of the group-shared blocks and
        ONE batched important-position selection, instead of N per-request
        passes.

        Shape/dtype contracts (N requests, prompt length S, model dims
        L layers × KV kv-heads × hd head-dim):
          tokens:      int32 [N, S] — the group's (equal-length) prompts.
          cached_k/v:  float [L, S, KV, hd] — group-SHARED cached KV laid
                       out at prompt positions; zeros where uncached.
          src_pos:     int32 [S] — source positions the shared values were
                       computed at (identity where uncached).
          shared_mask: bool [S] — True on shared-cached positions.
          n_sel:       static int — recomputed-position budget (tokens);
                       must be a multiple of ``block_select`` when block
                       selection is on (see ``pic.n_sel_for_blocks``).
          priv:        per-request private caches, one of
                         * None — no private history,
                         * dense tuple ``(pk [N,L,S,KV,hd], pv,
                           psrc [N,S], pmask [S])``,
                         * :class:`PagedPrivate` — pool + page tables,
                           consumed WITHOUT densification: the recovery
                           pass reads ``pool[l][page_idx]`` per layer at
                           the point each layer's attention needs it
                           (the XLA form of the paged flash kernel's
                           page-table BlockSpec), so §4.4's page sharing
                           survives through the attention launch itself.
          paged_attention: opt-out knob for the paged fast path. With
                       ``False`` — or when the span needs realignment
                       (``identity_span_src`` fails) — a ``PagedPrivate``
                       is gathered dense inside the jit instead
                       (``_densify_paged``, the parity oracle).

        Returns a :class:`CollectiveResult` whose ``pic`` holds the
        recovered caches ``[L, N, S, KV, hd]`` and last-token logits, and
        whose ``plan`` carries the Master choice + per-request deviations
        into Diff-Aware Storage. Outputs are bit-identical across the
        dense and paged ``priv`` forms (pure data movement either way)
        and to per-request :meth:`serial_reuse` (paper §6.6).
        """
        N, S = tokens.shape
        self.align_passes += 1
        priv_mode, args, paged_meta = self._priv_args(priv, paged_attention)
        res = self._runner(S, n_sel, True, priv_mode, paged_meta)(
            self.params, tokens, cached_k, cached_v, src_pos, shared_mask,
            *args)
        dev = np.asarray(jnp.sum(
            jnp.where(shared_mask[None], res.deviation, 0.0), axis=1))
        master = int(np.argmin(dev))  # closest to the group's common structure
        plan = ReusePlan(list(request_ids), master,
                         np.asarray(res.sel_idx[0]), dev, S, n_sel,
                         sel_idx_all=np.asarray(res.sel_idx))
        return CollectiveResult(plan, res, priv_mode)

    # ------------------------------------------------------------------
    def serial_reuse(
        self,
        request_ids: List[str],
        tokens: jax.Array,
        cached_k: jax.Array,
        cached_v: jax.Array,
        src_pos: jax.Array,
        shared_mask: jax.Array,
        n_sel: int,
        priv=None,
    ) -> List[PICResult]:
        """Per-request baseline (T2 path): N independent reuse passes, each
        repeating RoPE alignment and important-position selection.

        Same contracts as :meth:`collective_reuse`; returns one
        :class:`PICResult` per request (each with B=1 leading axes). A
        :class:`PagedPrivate` ``priv`` is densified up front via its
        oracle — the baseline deliberately pays the full per-request
        materialization the collective paged path avoids."""
        if isinstance(priv, PagedPrivate):
            priv = priv.materialize(tokens.shape[1])
        out = []
        run = self._runner(tokens.shape[1], n_sel, False,
                           "none" if priv is None else "dense")
        self.align_passes += tokens.shape[0]
        for i in range(tokens.shape[0]):
            args = ()
            if priv is not None:
                pk, pv, psrc, pmask = priv
                args = (pk[i : i + 1], pv[i : i + 1], psrc[i : i + 1], pmask)
            out.append(run(self.params, tokens[i : i + 1], cached_k, cached_v,
                           src_pos, shared_mask, *args))
        return out
