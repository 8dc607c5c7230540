"""CacheBlend-style per-request PIC recovery, plus the cached-prompt
assembly shared with the collective TokenDance policy.

``PICPolicy`` is the serial baseline (T2 in the paper's Fig. 7): N
independent RoPE-align + selection passes per round. Its ``plan`` /
``_assemble_cached`` machinery — shared segment lookup, private-history
entries, dense-vs-paged ``priv`` construction — is what
``TokenDancePolicy`` inherits and drives collectively.
"""
from __future__ import annotations

import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.collector import PagedPrivate
from repro.core.pic import n_sel_for_blocks
from repro.core.segments import (
    SHARED,
    PagedSegmentCacheEntry,
    SegmentCacheEntry,
    segment_hash,
)
from repro.serving.policies.base import (
    RecoveryPlan,
    RecoveryResult,
    ReusePolicy,
    RoundContext,
    entry_spillable,
    register_policy,
)
from repro.serving.round_kv import round_kv


@register_policy("pic")
class PICPolicy(ReusePolicy):
    """Per-request position-independent cache recovery (CacheBlend)."""

    requires_attention = True
    #: subclasses flip this to drive ONE grouped pass per round
    collective = False
    #: collective paged histories reach attention without densification
    #: (see KVCollector.collective_reuse); TokenDancePolicy exposes the
    #: oracle opt-out for parity testing
    paged_attention = True

    # ------------------------------------------------------------- plan
    def plan(self, ctx: RoundContext) -> RecoveryPlan:
        if ctx.round_idx == 0:
            return RecoveryPlan(kind="recompute", ctx=ctx)
        t_restore, restore_info = self._restore_histories(ctx)
        assembled = self._assemble_cached(ctx)
        (sk, sv, src, smask, priv, pmask, is_cached) = assembled
        if not bool(np.asarray(smask).any() or np.asarray(pmask).any()):
            return RecoveryPlan(kind="recompute", ctx=ctx,
                                t_restore=t_restore,
                                restore_info=restore_info)
        fresh = ~np.asarray(is_cached)
        n_sel = n_sel_for_blocks(fresh, self.rt.block_select, self.rt.ratio)
        return RecoveryPlan(kind="reuse", ctx=ctx, n_sel=n_sel,
                            assembled=assembled, t_restore=t_restore,
                            restore_info=restore_info)

    def _restore_histories(self, ctx: RoundContext):
        """Hook for policies whose history caches live compressed between
        rounds (TokenDance). The serial baseline keeps dense entries."""
        return 0.0, None

    def _assemble_cached(self, ctx: RoundContext):
        """Build the shared cached arrays + per-agent history caches."""
        rt = self.rt
        cfg = rt.cfg
        layouts, aids = ctx.layouts, ctx.agent_ids
        L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.resolved_head_dim
        S = layouts[0].length
        # cached KV in the model's dtype: the recovery pass embeds tokens
        # in shared_k's dtype, so a float32 buffer would lift a bf16 model
        # (and every cache it hands to decode) to float32
        shared_k = jnp.zeros((L, S, KV, hd), cfg.dtype)
        shared_v = jnp.zeros_like(shared_k)
        src = np.arange(S, dtype=np.int32)
        shared_mask = np.zeros(S, bool)
        for span in layouts[0].spans:
            if span.kind != SHARED:
                continue
            e = rt.segment_index.get(span.sid)
            if e is None:
                continue
            # the shared block is some agent's output segment — pull it
            # back from the host tier if the manager spilled it
            if getattr(e, "producer", None) is not None:
                rt.ensure_resident(f"out:{e.producer}")
            shared_k = shared_k.at[:, span.start : span.end].set(e.k)
            shared_v = shared_v.at[:, span.start : span.end].set(e.v)
            src[span.start : span.end] = e.src_pos
            shared_mask[span.start : span.end] = True

        # per-agent history caches (span 0 = private history). Entries are
        # either dense SegmentCacheEntry (pic / dense oracle) or
        # PagedSegmentCacheEntry referencing the family restore's page
        # pool — the latter flow to the collector WITHOUT densification.
        hspan = layouts[0].spans[0]
        priv_mask = np.zeros(S, bool)
        priv = None
        for a in aids:                 # reload spilled dense histories
            rt.ensure_resident(f"hist:{a}")
        entries = [rt.sessions[a].hist_entry for a in aids]
        if all(e is not None for e in entries) and hspan.end > hspan.start:
            priv_mask[hspan.start : hspan.end] = True
            paged = [isinstance(e, PagedSegmentCacheEntry) for e in entries]
            if all(paged) and all(e.pool_k is entries[0].pool_k
                                  for e in entries):
                priv = self._paged_priv(entries, hspan, S, priv_mask)
            else:
                if any(paged):   # mixed family: fall back to the oracle
                    entries = [e.materialize() if isinstance(
                        e, PagedSegmentCacheEntry) else e for e in entries]
                priv = self._dense_priv(entries, hspan, S, priv_mask)
        is_cached = shared_mask | priv_mask
        return (shared_k, shared_v, jnp.asarray(src), jnp.asarray(shared_mask),
                priv, jnp.asarray(priv_mask), is_cached)

    def _dense_priv(self, entries, hspan, S: int, priv_mask) -> tuple:
        """Pre-densified private caches: the collector's dense ``priv``
        tuple ``(pk [N,L,S,KV,hd], pv, psrc [N,S], pmask [S])``."""
        cfg = self.rt.cfg
        L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.resolved_head_dim
        pks, pvs, srcs = [], [], []
        for e in entries:
            assert e.k.shape[1] == len(hspan), (e.k.shape, len(hspan))
            full_k = jnp.zeros((L, S, KV, hd), cfg.dtype)
            full_v = jnp.zeros_like(full_k)
            full_k = full_k.at[:, hspan.start : hspan.end].set(e.k)
            full_v = full_v.at[:, hspan.start : hspan.end].set(e.v)
            s_ = np.arange(S, dtype=np.int32)
            s_[hspan.start : hspan.end] = e.src_pos
            pks.append(full_k)
            pvs.append(full_v)
            srcs.append(s_)
        return (jnp.stack(pks), jnp.stack(pvs),
                jnp.asarray(np.stack(srcs)), jnp.asarray(priv_mask))

    def _paged_priv(self, entries, hspan, S: int, priv_mask):
        """Paged private caches: ONE family page pool + per-agent page
        tables (plus each agent's dense output tail), gathered inside the
        collector's jitted pass instead of here."""
        e0 = entries[0]
        span_len, T = e0.seq_len, e0.tail_len
        assert span_len + T == len(hspan), (span_len, T, len(hspan))
        for e in entries:
            assert e.seq_len == span_len and e.tail_len == T, \
                "family entries must share the span layout"
        rows = np.stack([np.asarray(e.page_idx) for e in entries])
        srcs = []
        for e in entries:
            s_ = np.arange(S, dtype=np.int32)
            s_[hspan.start : hspan.end] = e.src_pos
            srcs.append(s_)
        tail_k = tail_v = None
        if T:
            tail_k = jnp.stack([e.tail_k for e in entries])
            tail_v = jnp.stack([e.tail_v for e in entries])
        return PagedPrivate(
            pool_k=e0.pool_k, pool_v=e0.pool_v,
            page_idx=jnp.asarray(rows), src=jnp.asarray(np.stack(srcs)),
            mask=jnp.asarray(priv_mask), start=hspan.start,
            span_len=span_len, tail_k=tail_k, tail_v=tail_v)

    # ---------------------------------------------------------- recover
    def recover(self, plan: RecoveryPlan, tokens: jax.Array) -> RecoveryResult:
        if plan.kind == "recompute":
            return self._recover_recompute(tokens)
        rt = self.rt
        aids, n_sel = plan.ctx.agent_ids, plan.n_sel
        (sk, sv, src, smask, priv, pmask, _) = plan.assembled
        N, S = tokens.shape
        if not self.collective and isinstance(priv, PagedPrivate):
            # the serial baseline consumes dense priv tuples only
            priv = priv.materialize(S)

        if self.collective:
            key = ("coll", N, S, n_sel, self.paged_attention)
            if key not in rt.warm:
                rt.collector.collective_reuse(
                    aids, tokens, sk, sv, src, smask, n_sel, priv,
                    paged_attention=self.paged_attention)
                rt.warm.add(key)
            p0 = rt.collector.align_passes
            t0 = time.perf_counter()
            res = rt.collector.collective_reuse(
                aids, tokens, sk, sv, src, smask, n_sel, priv,
                paged_attention=self.paged_attention)
            jax.block_until_ready(res.pic.recovered_k)
            dt = time.perf_counter() - t0
            k = res.pic.recovered_k                        # [L, N, S, KV, hd]
            v = res.pic.recovered_v
            logits = res.pic.logits
            info = {"n_sel": n_sel, "plan": res.plan,
                    "align_passes": rt.collector.align_passes - p0,
                    "priv_mode": res.priv_mode}
        else:
            key = ("serial", S, n_sel)
            if key not in rt.warm:
                rt.collector.serial_reuse(
                    aids[:1], tokens[:1], sk, sv, src, smask, n_sel,
                    None if priv is None else tuple(
                        x[:1] if i < 3 else x for i, x in enumerate(priv)))
                rt.warm.add(key)
            p0 = rt.collector.align_passes
            t0 = time.perf_counter()
            results = rt.collector.serial_reuse(
                aids, tokens, sk, sv, src, smask, n_sel, priv)
            jax.block_until_ready([r.recovered_k for r in results])
            dt = time.perf_counter() - t0
            k = jnp.concatenate([r.recovered_k for r in results], axis=1)
            v = jnp.concatenate([r.recovered_v for r in results], axis=1)
            logits = jnp.concatenate([r.logits for r in results], axis=0)
            info = {"n_sel": n_sel,
                    "align_passes": rt.collector.align_passes - p0}
        return RecoveryResult(logits, {"k": k, "v": v}, dt, info)

    # ------------------------------------------------------------- store
    def _store_output_segments(self, ctx: RoundContext, kv,
                               outputs: np.ndarray) -> None:
        """Each agent's output block O_i, shared next round (§4.1).
        ``kv`` is a round-KV view — the output-block slice is a page
        gather when the decode ran paged, a plain slice when dense."""
        rt = self.rt
        S, G = ctx.prompt_len, rt.gen_len
        ok, ov = kv.slice(S, S + G)       # [L, N, G, KV, hd]
        for i, a in enumerate(ctx.agent_ids):
            sid = segment_hash(outputs[i])
            rt.segment_index.put(SegmentCacheEntry(
                sid=sid, k=ok[:, i], v=ov[:, i],
                src_pos=np.arange(S, S + G, dtype=np.int32),
                producer=a, round_idx=ctx.round_idx))

    def store(self, ctx: RoundContext, cache: dict, outputs: np.ndarray,
              result: RecoveryResult, stats) -> None:
        kv = round_kv(cache)
        if kv is None:
            return
        rt = self.rt
        S, G = ctx.prompt_len, rt.gen_len
        hspan = ctx.layouts[0].spans[0]
        self._store_output_segments(ctx, kv, outputs)
        # CacheBlend keeps dense segment entries per agent; only the kept
        # regions (history span + output block) are ever gathered dense
        hk_all, hv_all = kv.slice(hspan.start, hspan.end)
        ok_all, ov_all = kv.slice(S, S + G)
        for i, a in enumerate(ctx.agent_ids):
            hk = jnp.concatenate([hk_all[:, i], ok_all[:, i]], axis=1)
            hv = jnp.concatenate([hv_all[:, i], ov_all[:, i]], axis=1)
            sp = np.concatenate([
                np.arange(hspan.start, hspan.end, dtype=np.int32),
                np.arange(S, S + G, dtype=np.int32)])
            rt.sessions[a].hist_entry = SegmentCacheEntry(
                sid=f"hist:{a}:{ctx.round_idx}", k=hk, v=hv, src_pos=sp,
                producer=a, round_idx=ctx.round_idx)
            rt.pool_free(f"hist:{a}")
            rt.pool_alloc_tokens(f"hist:{a}", hk.shape[1], persistent=True,
                                 spillable=entry_spillable(
                                     rt.sessions[a].hist_entry))
            rt.pool_free(f"out:{a}")
            rt.pool_alloc_tokens(f"out:{a}", G, persistent=True,
                                 spillable=entry_spillable(
                                     rt.segment_index.get(
                                         segment_hash(outputs[i]))))
