"""CacheBlend-style per-request PIC recovery, plus the cached-prompt
assembly shared with the collective TokenDance policy.

``PICPolicy`` is the serial baseline (T2 in the paper's Fig. 7): N
independent RoPE-align + selection passes per round. Its ``plan`` /
``_assemble_cached`` machinery — shared segment lookup, private-history
entries, dense-vs-paged ``priv`` construction — is what
``TokenDancePolicy`` inherits and drives collectively.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.collector import PagedPrivate
from repro.core.pic import bucket_len, n_sel_for_blocks
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
        restore_info = self._restore_histories(ctx)
        assembled = self._assemble_cached(ctx)
        (sk, sv, src, smask, priv, pmask, is_cached) = assembled
        if not bool(np.asarray(smask).any() or np.asarray(pmask).any()):
            return RecoveryPlan(kind="recompute", ctx=ctx,
                                restore_info=restore_info)
        S, bt, ratio = ctx.prompt_len, self.rt.block_select, self.rt.ratio
        fresh = ~is_cached[:S]
        return RecoveryPlan(
            kind="reuse", ctx=ctx,
            n_sel=n_sel_for_blocks(fresh, bt, ratio),
            n_sel_padded=n_sel_for_blocks(fresh, bt, ratio,
                                          length=bucket_len(S, bt)),
            assembled=assembled, restore_info=restore_info)

    def _restore_histories(self, ctx: RoundContext):
        """Hook for policies whose history caches live compressed between
        rounds (TokenDance); returns the restore ledger, or None. The
        serial baseline keeps dense entries."""
        return None

    def _assemble_cached(self, ctx: RoundContext):
        """Build the shared cached arrays + per-agent history caches, at
        the bucketed prompt length: the padding is neither shared nor
        private (``is_cached`` False, identity source positions)."""
        rt = self.rt
        cfg = rt.cfg
        layouts, aids = ctx.layouts, ctx.agent_ids
        L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.resolved_head_dim
        S_real = layouts[0].length
        S = bucket_len(S_real, rt.block_select)
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
                priv = self._paged_priv(entries, hspan, S, S_real,
                                        priv_mask)
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

    def history_cols(self, S_real: int, span_len: int, bt: int) -> int:
        """Page-table columns of a history span of ``span_len`` tokens in
        a prompt of ``S_real``: the pages the span could fill at the
        prompt's bucketed length, beside the prompt's other tokens. One
        number per bucket, so the recovery program's page tables (and
        the family pools sized by it) do not follow the history."""
        S = bucket_len(S_real, self.rt.block_select)
        return -(-(S - S_real + span_len) // bt)

    def _paged_priv(self, entries, hspan, S: int, S_real: int, priv_mask):
        """Paged private caches: ONE family page pool + per-agent page
        tables (plus each agent's dense output tail), gathered inside the
        collector's jitted pass instead of here.

        The recovery program takes the span length as an operand, so its
        shapes follow the bucket, not the history: every table gets
        :meth:`history_cols` columns (padded columns read page 0 and are
        masked off), and the family pool's page count is sized by the
        same rule where the policy builds it (TokenDance)."""
        e0 = entries[0]
        span_len, T = e0.seq_len, e0.tail_len
        assert span_len + T == len(hspan), (span_len, T, len(hspan))
        for e in entries:
            assert e.seq_len == span_len and e.tail_len == T, \
                "family entries must share the span layout"
        n_cols = self.history_cols(S_real, span_len, e0.block_tokens)
        rows = np.zeros((len(entries), n_cols), np.int32)
        for i, e in enumerate(entries):
            rows[i, : len(e.page_idx)] = np.asarray(e.page_idx)
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
        # the prompts right-padded to the bucketed length of the arrays
        Sp = sk.shape[1]
        padded = np.zeros((N, Sp), np.int32)
        padded[:, :S] = plan.ctx.tokens
        tokens = jnp.asarray(padded)
        bucket = {"length": S, "n_sel_padded": plan.n_sel_padded}
        if not self.collective and isinstance(priv, PagedPrivate):
            # the serial baseline consumes dense priv tuples only
            priv = priv.materialize(Sp)

        # one pass, and back once the recovered KV and the first-token
        # logits are on the device: that is the time to first token. The
        # recovered KV keeps the padded length; decode runs at it too.
        p0 = rt.collector.align_passes
        if self.collective:
            res = rt.collector.collective_reuse(
                aids, tokens, sk, sv, src, smask, n_sel, priv,
                paged_attention=self.paged_attention, **bucket)
            jax.block_until_ready((res.pic.recovered_k, res.pic.logits))
            k = res.pic.recovered_k                        # [L, N, S, KV, hd]
            v = res.pic.recovered_v
            logits = res.pic.logits
            info = {"n_sel": n_sel, "plan": res.plan,
                    "align_passes": rt.collector.align_passes - p0,
                    "priv_mode": res.priv_mode}
        else:
            results = rt.collector.serial_reuse(
                aids, tokens, sk, sv, src, smask, n_sel, priv, **bucket)
            jax.block_until_ready([(r.recovered_k, r.logits)
                                   for r in results])
            k = jnp.concatenate([r.recovered_k for r in results], axis=1)
            v = jnp.concatenate([r.recovered_v for r in results], axis=1)
            logits = jnp.concatenate([r.logits for r in results], axis=0)
            info = {"n_sel": n_sel,
                    "align_passes": rt.collector.align_passes - p0}
        return RecoveryResult(logits, {"k": k, "v": v}, info)

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
