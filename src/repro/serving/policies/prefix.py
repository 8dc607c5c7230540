"""vLLM + automatic prefix caching: exact reuse of each agent's own
history prefix, fresh compute for everything after it."""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.transformer import extend
from repro.serving.policies.base import (
    RecoveryPlan,
    RecoveryResult,
    ReusePolicy,
    RoundContext,
    register_policy,
)
from repro.serving.pool import Spillable
from repro.serving.round_kv import round_kv


def _common_prefix(a: np.ndarray, b: np.ndarray) -> int:
    n = min(a.shape[0], b.shape[0])
    neq = np.nonzero(a[:n] != b[:n])[0]
    return int(neq[0]) if neq.size else n


def _session_spillable(s) -> Spillable:
    """Move a session's dense prefix cache between tiers, in place."""
    def get():
        return (s.dense_k, s.dense_v)

    def put(arrs):
        s.dense_k, s.dense_v = arrs
    return Spillable(get, put)


@register_policy("prefix")
class PrefixCachePolicy(ReusePolicy):
    """Exact own-prefix reuse over dense per-session caches.

    ``plan`` computes (host-side) the longest prompt prefix every group
    member still has cached; ``recover`` left-pads the stacked prefix
    caches and extends over the suffix; ``store`` persists each agent's
    full dense cache for the next round."""

    def plan(self, ctx: RoundContext) -> RecoveryPlan:
        if ctx.round_idx == 0:
            return RecoveryPlan(kind="recompute", ctx=ctx)
        plens = []
        for i, aid in enumerate(ctx.agent_ids):
            self.rt.ensure_resident(f"sess:{aid}")
            s = self.rt.sessions[aid]
            if s.prompt_tokens is None or s.dense_k is None:
                plens.append(0)
            else:
                plens.append(min(_common_prefix(ctx.tokens[i], s.prompt_tokens),
                                 s.dense_k.shape[1]))
        p = min(plens)  # equal-length sessions give equal p; be safe
        if p == 0:
            return RecoveryPlan(kind="recompute", ctx=ctx)
        return RecoveryPlan(kind="extend", ctx=ctx, prefix_len=p)

    def recover(self, plan: RecoveryPlan, tokens: jax.Array) -> RecoveryResult:
        if plan.kind == "recompute":
            return self._recover_recompute(tokens)
        rt, p = self.rt, plan.prefix_len
        cfg, step_fn = rt.cfg, extend
        aids = plan.ctx.agent_ids
        N, S = tokens.shape
        kpre = jnp.stack([rt.sessions[a].dense_k[:, :p] for a in aids], axis=1)
        vpre = jnp.stack([rt.sessions[a].dense_v[:, :p] for a in aids], axis=1)

        def build():
            def f(params, toks, kp, vp):
                pad = S - p
                cache = {
                    "k": jnp.pad(kp, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
                    "v": jnp.pad(vp, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
                    "kv_pos": jnp.broadcast_to(
                        jnp.arange(S, dtype=jnp.int32)[None], (N, S)),
                    "kv_valid": jnp.broadcast_to(
                        jnp.arange(S)[None] < p, (N, S)),
                    "length": jnp.full((N,), p, jnp.int32),
                }
                logits, cache = step_fn(params, cfg, toks[:, p:], cache)
                return logits[:, -1], {"k": cache["k"], "v": cache["v"]}
            return f
        run = rt.programs.get_jit("prefix_extend", (N, S, p, cfg, step_fn),
                                  build)
        logits, cache = jax.block_until_ready(
            run(rt.params, tokens, kpre, vpre))
        return RecoveryResult(logits, cache, {"prefix_len": p})

    def store(self, ctx: RoundContext, cache: dict, outputs: np.ndarray,
              result: RecoveryResult, stats) -> None:
        kv = round_kv(cache)
        if kv is None:
            return
        rt = self.rt
        # dense session caches ARE this policy's storage design: keep the
        # prompt and its generated tokens (a decode at a bucketed length
        # leaves padding past them)
        S, G = ctx.prompt_len, rt.gen_len
        kc, vc = kv.slice(0, S + G)       # [L, N, S+G, KV, hd]
        for i, a in enumerate(ctx.agent_ids):
            s = rt.sessions[a]
            s.dense_k = kc[:, i]
            s.dense_v = vc[:, i]
            s.prompt_tokens = np.concatenate(
                [np.asarray(ctx.layouts[i].tokens), outputs[i]])
            rt.pool_free(f"sess:{a}")
            rt.pool_alloc_tokens(f"sess:{a}", S + G, persistent=True,
                                 spillable=_session_spillable(s))
