"""Round-synchronous multi-agent serving engine: a thin round loop over
pluggable :class:`~repro.serving.policies.ReusePolicy` objects and
declarative gather topologies.

The four registered policies share the same model substrate, decode loop
and accounting, so measured differences are attributable to the reuse
strategy:

  RecomputePolicy    — vLLM without reuse: full batched prefill/round
  PrefixCachePolicy  — vLLM + prefix caching: exact own-prefix reuse
  PICPolicy          — CacheBlend: per-request PIC recovery passes
  TokenDancePolicy   — the paper: collective recovery (one shared
                       pass/group) + Master-Mirror diffs + fused restore

Each round the engine (1) partitions agents into gather groups from the
:class:`~repro.core.rounds.GatherTopology` (All-Gather = one group), then
per group (2) asks the policy to ``plan`` (host-side; includes restores),
(3) ``recover`` (jitted), (4) runs the shared greedy decode, and (5) asks
the policy to ``store``. ``serve(trace, planner)`` adds per-round SLO
admission via :class:`~repro.serving.planner.RoundPlanner`.

``MultiAgentEngine(mode=...)`` remains as a deprecated string-keyed shim
with bit-exact behavior.
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.collector import KVCollector
from repro.core.rounds import (
    AgentState,
    AllGather,
    AllGatherTrace,
    GatherTopology,
    Round,
    round_prompt,
)
from repro.core.segments import PromptLayout, SegmentIndex
from repro.models import decode_step, decode_step_paged
from repro.serving.kvpool import PagedKVPool
from repro.serving.planner import RoundPlan, RoundPlanner
from repro.serving.pool import HostTier, PoolManager, parse_owner
from repro.serving.policies import (
    PolicyRuntime,
    ReusePolicy,
    RoundContext,
    get_policy,
)
from repro.serving.state import RoundStats, Session

MODES = ("recompute", "prefix", "pic", "tokendance")


@dataclass
class DecodeState:
    """An in-flight greedy decode for one equal-length batch, advanced
    one model step at a time.

    The synchronized engine runs begin → advance×(G-1) → finish in a
    tight loop (:meth:`ServingEngine._decode_dense` /
    :meth:`ServingEngine._decode_paged`); the continuous engine
    (``serving/loop``) holds several of these open at once and advances
    each on its scheduler tick. Both paths share the jit cache keyed by
    (kind, N, S+G), so an interleaved decode compiles and computes
    exactly what the synchronized loop does — this is the mechanism
    behind the bit-exact oracle relationship.
    """

    step: Callable                 # jitted (params, tok, cache) -> (tok, cache)
    tok: jax.Array                 # last greedy token, [N]
    cache: dict                    # dense or paged decode cache
    outs: list = field(default_factory=list)   # per-step tokens, [N] each
    gaids: List[str] = field(default_factory=list)
    S: int = 0                     # prompt length
    G: int = 0                     # gen_len
    bt: int = 0                    # block_tokens (paged page tile)
    paged: bool = False
    t: int = 0                     # decode steps taken (of G-1)
    t0: float = 0.0

    @property
    def done(self) -> bool:
        return self.t >= self.G - 1


class ServingEngine:
    """Thin round loop over one bound :class:`ReusePolicy`."""

    def __init__(
        self,
        params: dict,
        cfg: ModelConfig,
        policy: Union[ReusePolicy, str] = "tokendance",
        *,
        topology: Optional[GatherTopology] = None,
        # default gen_len must satisfy the block-alignment assert below
        # with the default block_select — ServingEngine(params, cfg) with
        # zero kwargs has to construct (regression-pinned in tests)
        gen_len: int = 32,
        recompute_ratio: float = 0.15,
        block_select: int = 32,
        check_layer: int = 1,
        pool_pages: int = 1 << 16,
        eviction="family",
        host_offload: bool = True,
        paged_decode: bool = True,
        keep_recovered: bool = False,
        keep_logits: bool = False,
    ):
        if isinstance(policy, str):
            policy = get_policy(policy)
        if policy.requires_attention and (not cfg.has_attention or cfg.has_ssm):
            # PIC-style reuse is inapplicable to SSM/hybrid state
            # (DESIGN.md §5); those archs serve via full recompute.
            policy = get_policy("recompute")
        assert block_select == 0 or gen_len % block_select == 0, \
            "gen_len must be block-aligned so histories stay aligned"
        self.cfg = cfg
        self.params = params
        self.gen_len = gen_len
        self.block_select = block_select
        self.sep_id = cfg.vocab_size - 1
        self.topology = topology or AllGather()
        self.sessions: Dict[str, Session] = {}
        self.segment_index = SegmentIndex()
        self.pool = PagedKVPool(cfg, pool_pages)
        # tiered layer over the pool: family-aware eviction + host
        # offload + restore-ahead prefetch. host_offload=False disables
        # the host tier (capacity 0), reproducing the hard-wall
        # PoolExhausted behavior of a plain pool.
        self.manager = PoolManager(
            self.pool, eviction=eviction,
            host=HostTier(None if host_offload else 0))
        # decode over round pool pages (the KV-never-densifies fast
        # path); False keeps the dense [L, N, S+G] decode loop, the
        # bit-exact oracle the paged path is pinned against
        self.paged_decode = paged_decode
        self.keep_recovered = keep_recovered
        # record per-round first-token logits on RoundStats (host copy of
        # [N, vocab] per round — parity-test food, off by default)
        self.keep_logits = keep_logits
        self.last_recovered: Optional[tuple] = None
        self._recovered_parts: list = []
        self.collector = KVCollector(
            params, cfg, check_layer=check_layer,
            recompute_ratio=recompute_ratio, block_select=block_select)
        self.rt = PolicyRuntime(
            params=params, cfg=cfg, gen_len=gen_len, ratio=recompute_ratio,
            block_select=block_select, sep_id=self.sep_id,
            sessions=self.sessions, segment_index=self.segment_index,
            pool=self.pool, manager=self.manager, collector=self.collector)
        policy.bind(self.rt)
        self.policy = policy
        self.mode = policy.name          # legacy-facing alias
        self.round_idx = 0
        self.last_outputs: Dict[str, np.ndarray] = {}
        self._prefetch_pending: List[str] = []

    # ------------------------------------------------------------------
    def init_agents(self, trace: AllGatherTrace) -> None:
        for aid in trace.agent_ids:
            self.sessions[aid] = Session(
                aid, AgentState(aid, np.asarray(trace.init_histories[aid])))

    # ------------------------------------------------------------------
    def _build_prompts(
        self, rnd: Round, gaids: List[str],
        sources: Dict[str, Tuple[int, ...]],
    ) -> List[Tuple[List[str], np.ndarray, List[PromptLayout]]]:
        """Prompts for one gather group, partitioned into equal-length
        batches. Group members share a source set, hence a layout — but
        histories can differ in length when admission deferred an agent
        for some rounds (its history did not grow), so the group is
        further split by built prompt length and each partition serves as
        its own batch. The uniform case (every serve without deferrals)
        is a single partition."""
        shared = rnd.shared_blocks
        layouts, rows = [], []
        for aid in gaids:
            if shared:
                bad = [j for j in sources[aid] if j >= len(shared)]
                assert not bad, (
                    f"topology sources {bad} for {aid} out of range for "
                    f"{len(shared)} shared blocks")
                order = list(sources[aid])
            else:
                order = []      # replay round 0: no output blocks yet
            lay = round_prompt(self.sessions[aid].state, shared,
                               rnd.tasks[aid], self.sep_id,
                               layout_order=order,
                               align_blocks=self.block_select)
            layouts.append(lay)
            rows.append(lay.tokens)
        parts: Dict[int, list] = {}
        for aid, lay, row in zip(gaids, layouts, rows):
            parts.setdefault(row.shape[0], []).append((aid, lay, row))
        return [([a for a, _, _ in p], np.stack([r for _, _, r in p]),
                 [l for _, l, _ in p]) for p in parts.values()]

    # ------------------------------------------------------------------
    def _decode_begin(self, first_logits, prefill_cache: dict, N: int,
                      S: int, gaids: List[str], use_paged: bool
                      ) -> DecodeState:
        """Build the decode cache, jit the step function (shared cache
        keyed by (kind, N, S+G)), take the first greedy token from the
        recovery logits, and warm the step — everything up to (but not
        including) the first decode step. The returned
        :class:`DecodeState` is then advanced by :meth:`_decode_advance`
        one model step at a time and closed by :meth:`_decode_finish`."""
        cfg, G = self.cfg, self.gen_len
        total = S + G
        bt = self.block_select
        if use_paged:
            # the recovered prefill KV becomes each agent's sealed pages;
            # gen pages start zeroed (the dense loop's jnp.pad by G,
            # page-shaped)
            nb_s, nb_g = S // bt, G // bt
            nbt = nb_s + nb_g
            k, v = prefill_cache["k"], prefill_cache["v"]
            L, _, _, KV, hd = k.shape

            def to_pool(x):
                x = x.reshape(L, N, nb_s, bt, KV, hd)
                x = jnp.pad(x, ((0, 0), (0, 0), (0, nb_g),
                                (0, 0), (0, 0), (0, 0)))
                return x.reshape(L, N * nbt, bt, KV, hd)

            cache = {
                "pk": to_pool(k),
                "pv": to_pool(v),
                "page_idx": jnp.arange(N * nbt,
                                       dtype=jnp.int32).reshape(N, nbt),
                "kv_pos": jnp.pad(jnp.broadcast_to(
                    jnp.arange(S, dtype=jnp.int32)[None], (N, S)),
                    ((0, 0), (0, G))),
                "kv_valid": jnp.pad(jnp.ones((N, S), bool),
                                    ((0, 0), (0, G))),
                "length": jnp.full((N,), S, jnp.int32),
            }
            key = ("decode_paged", N, total)
            if key not in self.rt.jit:
                def f(params, tok, cache):
                    logits, cache = decode_step_paged(params, cfg, tok, cache)
                    return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                            cache)
                self.rt.jit[key] = jax.jit(f)
        else:
            cache = {"length": jnp.full((N,), S, jnp.int32)}
            if "k" in prefill_cache:
                k, v = prefill_cache["k"], prefill_cache["v"]
                cache.update({
                    "k": jnp.pad(k, ((0, 0), (0, 0), (0, G),
                                     (0, 0), (0, 0))),
                    "v": jnp.pad(v, ((0, 0), (0, 0), (0, G),
                                     (0, 0), (0, 0))),
                    "kv_pos": jnp.pad(jnp.broadcast_to(
                        jnp.arange(S, dtype=jnp.int32)[None], (N, S)),
                        ((0, 0), (0, G))),
                    "kv_valid": jnp.pad(jnp.ones((N, S), bool),
                                        ((0, 0), (0, G))),
                })
            for key_ in ("ssm", "conv"):
                if key_ in prefill_cache:
                    cache[key_] = prefill_cache[key_]
            key = ("decode", N, total)
            if key not in self.rt.jit:
                def f(params, tok, cache):
                    logits, cache = decode_step(params, cfg, tok, cache)
                    return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                            cache)
                self.rt.jit[key] = jax.jit(f)
        step = self.rt.jit[key]
        tok = jnp.argmax(first_logits, axis=-1).astype(jnp.int32)
        if key not in self.rt.warm:
            jax.block_until_ready(step(self.params, tok, cache))
            self.rt.warm.add(key)
        return DecodeState(step=step, tok=tok, cache=cache, outs=[tok],
                           gaids=list(gaids), S=S, G=G, bt=bt,
                           paged=use_paged, t0=time.perf_counter())

    def _decode_advance(self, st: DecodeState) -> None:
        """One greedy decode step. On the paged path, the write at
        position S+t opens a fresh gen page each time generation crosses
        a block boundary: claim it in the ledger before the step fills
        its first slot (the previous page is sealed from here on)."""
        if st.paged and (st.S + st.t) % st.bt == 0:
            for a in st.gaids:
                self.manager.append_page(f"round:{a}")
        st.tok, st.cache = st.step(self.params, st.tok, st.cache)
        st.outs.append(st.tok)
        st.t += 1

    def _decode_finish(self, st: DecodeState):
        """Materialize the decode: outputs [N, G] on host, the final
        cache, and the wall-clock spent since :meth:`_decode_begin`
        (reported, never gated — CI gates counted work only)."""
        jax.block_until_ready(st.tok)
        dt = time.perf_counter() - st.t0
        return (np.stack([np.asarray(t) for t in st.outs], axis=1),
                st.cache, dt)

    def _decode_dense(self, first_logits, prefill_cache: dict, N: int, S: int):
        """Greedy decode gen_len tokens for the group over a dense padded
        [L, N, S+G] cache (attention KV, SSM state, or both) — the
        fallback for SSM/hybrid state and the bit-exact oracle the paged
        loop is pinned against."""
        st = self._decode_begin(first_logits, prefill_cache, N, S,
                                gaids=[], use_paged=False)
        while not st.done:
            self._decode_advance(st)
        return self._decode_finish(st)

    # ------------------------------------------------------------------
    def _paged_decode_ok(self, prefill_cache: dict, S: int) -> bool:
        """The paged loop carries attention KV only and needs the page
        tile to line up with the prompt and generation lengths (both are
        block-aligned by construction: ``round_prompt`` aligns S, the
        ctor asserts gen_len)."""
        bt = self.block_select
        return (self.paged_decode and bt > 0
                and "k" in prefill_cache
                and "ssm" not in prefill_cache
                and "conv" not in prefill_cache
                and S % bt == 0 and self.gen_len % bt == 0)

    def _decode_paged(self, first_logits, prefill_cache: dict, N: int,
                      S: int, gaids: List[str]):
        """Greedy decode whose attention KV lives in round pool pages —
        the recovered prefill KV becomes each agent's sealed pages and
        every generated token is scatter-written into the open gen page,
        so the dense [L, N, S+G] cache of :meth:`_decode_dense` is never
        built. The in-step gather of the SAME pages reconstructs the
        dense KV stream exactly, making outputs bit-identical to the
        dense loop (pinned in tests), and ledger page claims land on the
        same end-of-round totals as the dense loop's up-front S+G
        allocation."""
        st = self._decode_begin(first_logits, prefill_cache, N, S,
                                gaids=gaids, use_paged=True)
        while not st.done:
            self._decode_advance(st)
        return self._decode_finish(st)

    # ------------------------------------------------------------------
    def run_round(self, rnd: Round, plan: Optional[RoundPlan] = None,
                  next_plan: Optional[RoundPlan] = None) -> RoundStats:
        # generate mode: use previous outputs as this round's shared blocks.
        # Agents that have not produced yet (deferred by admission since
        # round 0) contribute their trace replay block instead.
        if self.round_idx > 0 and self.last_outputs:
            fallback = self._replay_fallback_blocks(rnd)
            shared = []
            for a in self.sessions:
                prev = self.last_outputs.get(a, fallback.get(a))
                assert prev is not None, f"no output block for agent {a}"
                shared.append(prev)
            rnd = Round(rnd.index, shared, rnd.tasks)
        all_ids = list(self.sessions)
        admitted = (all_ids if plan is None
                    else [a for a in plan.admitted if a in self.sessions])
        topology = (plan.topology if plan is not None and plan.topology
                    else self.topology)
        self.manager.begin_round(self.round_idx)
        ledger_before = self.manager.ledger.snapshot()
        scoped_before = self.manager.ledger.scoped_snapshot()
        # restore-ahead: round r+1's admission plan names the owners its
        # restores will read; reload them while round r decodes. Agents
        # admitted THIS round are excluded — their family state is
        # re-formed by this round's store() anyway.
        self._prefetch_pending = (
            [] if next_plan is None else
            self.manager.prefetch_planner.owners_for(
                self.sessions, next_plan.admitted, exclude=admitted))
        stats = RoundStats(self.round_idx, self.policy.name, len(admitted), 0)
        if plan is not None:
            stats.admission = {
                "max_agents": plan.max_agents,
                "admitted": list(plan.admitted),
                "deferred": list(plan.deferred),
            }
        groups = (topology.gather_groups(all_ids, admitted)
                  if admitted else [])
        out_rows: Dict[str, np.ndarray] = {}
        logit_rows: Dict[str, np.ndarray] = {}
        sources = topology.sources(all_ids)
        if self.keep_recovered:
            self._recovered_parts = []
        for gi, gaids in enumerate(groups):
            parts = self._build_prompts(rnd, gaids, sources)
            for pj, (paids, tokens_np, layouts) in enumerate(parts):
                gid = f"g{gi}" if len(parts) == 1 else f"g{gi}.{pj}"
                for a, row, lg in self._run_group(
                        gid, paids, tokens_np, layouts, stats):
                    out_rows[a] = row
                    logit_rows[a] = lg
        if admitted:
            stats.outputs = np.stack([out_rows[a] for a in admitted])
            if self.keep_logits:
                stats.first_logits = np.stack(
                    [logit_rows[a] for a in admitted])
        if self.keep_recovered and self._recovered_parts:
            # single batch (the All-Gather norm): the familiar (k, v,
            # layouts) tuple; multiple batches: one tuple per batch
            self.last_recovered = (self._recovered_parts[0]
                                   if len(self._recovered_parts) == 1
                                   else self._recovered_parts)
        stats.transient_peak_bytes = self.pool.peak_bytes()
        self.manager.free_transient()
        if self._prefetch_pending:   # retry now that transients are free
            self.manager.prefetch(self._prefetch_pending)
            self._prefetch_pending = []
        dev_bytes, host_bytes, cache_bytes = self._persistent_split()
        stats.persistent_bytes = dev_bytes + host_bytes
        pool_delta = self.manager.ledger.delta(ledger_before)
        # per-committee breakdown of the same counters (scope = gather
        # group id; traffic outside any group books to "engine") — so
        # multi-committee rounds don't blend into one aggregate
        by_committee = self.manager.ledger.scoped_delta(scoped_before)
        if by_committee:
            pool_delta["by_committee"] = by_committee
        pool_delta["persistent_device_bytes"] = dev_bytes
        pool_delta["persistent_host_bytes"] = host_bytes
        pool_delta["restore_cache_bytes"] = cache_bytes
        stats.merge_reuse("pool", pool_delta)
        self.round_idx += 1
        return stats

    def _run_group(self, gid: str, gaids: List[str],
                   tokens_np: np.ndarray, layouts: List[PromptLayout],
                   stats: RoundStats):
        """plan -> recover -> decode -> store for one equal-length batch
        of a gather group, with ledger traffic attributed to the group's
        committee scope (``g<i>``, partition suffix stripped)."""
        with self.manager.scoped(gid.split(".")[0]):
            return self._run_group_scoped(gid, gaids, tokens_np, layouts,
                                          stats)

    def _run_group_scoped(self, gid: str, gaids: List[str],
                          tokens_np: np.ndarray,
                          layouts: List[PromptLayout], stats: RoundStats):
        tokens = jnp.asarray(tokens_np)
        N, S = tokens.shape
        if stats.prompt_len == 0:
            stats.prompt_len = S

        ctx = RoundContext(round_idx=self.round_idx, gid=gid,
                           agent_ids=list(gaids), layouts=layouts,
                           tokens=tokens_np)

        # ---- phase A: plan (host) + recover (jitted) --------------------
        rplan = self.policy.plan(ctx)
        res = self.policy.recover(rplan, tokens)
        stats.t_recover += res.t_recover
        stats.t_restore += rplan.t_restore
        for k_, v_ in res.info.items():
            if k_ != "plan":
                stats.merge_reuse(k_, v_)
        if rplan.restore_info is not None:
            stats.merge_reuse("restore", rplan.restore_info)
        if self.keep_recovered and "k" in res.cache:
            self._recovered_parts.append(
                (np.asarray(res.cache["k"]),
                 np.asarray(res.cache["v"]), list(layouts)))

        # transient working set (the restore pool allocated during plan()
        # is reclaimed here, after its peak registered — same accounting
        # order as the pre-policy engine). Dense decode claims the full
        # S+G tokens up front; paged decode claims only the S prefill
        # tokens and grows one page per block boundary via append_page,
        # reaching the same S+G total by round end.
        use_paged = self._paged_decode_ok(res.cache, S)
        stats.merge_reuse("decode", {"paged": use_paged})
        self.manager.free_transient()
        for a in gaids:
            self.manager.free(f"round:{a}")
            self.manager.alloc_tokens(
                f"round:{a}", S if use_paged else S + self.gen_len,
                persistent=False)

        # restore-ahead prefetch for round r+1, overlapped with decode
        # (fires once per round, on the first group to reach this point;
        # owners that don't fit beside the live transients stay pending
        # and are retried at round end, after free_transient)
        if self._prefetch_pending:
            self._prefetch_pending = self.manager.prefetch(
                self._prefetch_pending)

        # ---- phase C: decode --------------------------------------------
        if use_paged:
            outputs, cache, dt_dec = self._decode_paged(
                res.logits, res.cache, N, S, gaids)
        else:
            outputs, cache, dt_dec = self._decode_dense(
                res.logits, res.cache, N, S)
        stats.t_decode += dt_dec

        # ---- phase D: bookkeeping / storage -----------------------------
        t0 = time.perf_counter()
        for i, a in enumerate(gaids):
            self.sessions[a].state.extend_history(outputs[i])
            self.last_outputs[a] = outputs[i]
        self.policy.store(ctx, cache, outputs, res, stats)
        stats.t_store += time.perf_counter() - t0
        logits_np = (np.asarray(res.logits) if self.keep_logits
                     else [None] * N)
        return [(a, outputs[i], logits_np[i]) for i, a in enumerate(gaids)]

    # ------------------------------------------------------------------
    def _replay_fallback_blocks(self, rnd: Round) -> Dict[str, np.ndarray]:
        """Trace replay blocks keyed by agent id, for agents with no
        output yet in generate mode. ``rnd.tasks`` preserves the trace's
        agent order, so block j belongs to agent_ids[j] — keying by id
        (rather than by position in ``self.sessions`` iteration order)
        keeps the pairing correct however the engine enumerates
        sessions."""
        return dict(zip(rnd.tasks, list(rnd.shared_blocks)))

    # ------------------------------------------------------------------
    def _persistent_split(self) -> Tuple[int, int, int]:
        """Footprint per class: (device_bytes, host_bytes, cache_bytes).
        Spilled persistent entries still hold the round's reusable state
        — the spill moved bytes, it didn't drop them — so both tiers
        count toward the total the admission planner reasons about.
        ``hist:family:`` (histpool) owners are carved out into
        cache_bytes: the cross-round restore pool is RECONSTRUCTIBLE —
        dropping it costs one full family restore, never correctness —
        so it is a resident accelerator cache, not part of the storage
        the compression claim is about (both tiers, same rationale)."""
        dev = 0
        cache = 0
        pb = self.pool.page_bytes()
        for owner in self.pool.owners():
            a = self.pool._allocs[owner]
            if not a.persistent:
                continue
            if parse_owner(owner).kind == "histpool":
                cache += a.n_pages * pb
            else:
                dev += a.n_pages * pb
        host = 0
        for owner, e in self.manager.host._entries.items():
            if not e.persistent:
                continue
            if parse_owner(owner).kind == "histpool":
                cache += e.n_pages * pb
            else:
                host += e.n_pages * pb
        return dev, host, cache

    def _persistent_bytes(self) -> int:
        dev, host, _ = self._persistent_split()
        return dev + host

    # ------------------------------------------------------------------
    def serve(self, trace: AllGatherTrace,
              planner: Optional[RoundPlanner] = None,
              n_rounds: Optional[int] = None) -> List[RoundStats]:
        """Serve a trace: one :meth:`run_round` per round, each preceded
        by the planner's admission decision (admit-all when absent).

        The plan for round r+1 is computed while round r is still
        current (one ``plan_round`` call per round, in round order — the
        admission rotation is identical to planning lazily) and handed
        to :meth:`run_round` as ``next_plan`` so the pool manager can
        prefetch the owners round r+1's restores will read. Observed
        round stats feed :meth:`RoundPlanner.observe` *after* the
        lookahead plan for that round exists, so a measurement refit
        takes effect two rounds later.
        """
        if not self.sessions:
            self.init_agents(trace)
        rounds = trace.rounds[: n_rounds or len(trace.rounds)]
        out = []
        plan = (None if planner is None or not rounds else
                planner.plan_round(self.round_idx, list(self.sessions)))
        for i, rnd in enumerate(rounds):
            next_plan = (None if planner is None or i + 1 >= len(rounds) else
                         planner.plan_round(self.round_idx + 1,
                                            list(self.sessions)))
            stats = self.run_round(rnd, plan, next_plan=next_plan)
            out.append(stats)
            if planner is not None:
                planner.observe(
                    stats, collective=getattr(self.policy, "collective",
                                              self.policy.name == "tokendance"))
            plan = next_plan
        return out

    def run_trace(self, trace: AllGatherTrace,
                  n_rounds: Optional[int] = None) -> List[RoundStats]:
        """Legacy alias for :meth:`serve` without a planner."""
        return self.serve(trace, n_rounds=n_rounds)


class MultiAgentEngine(ServingEngine):
    """Deprecated mode-string front door, kept for compatibility.

    ``MultiAgentEngine(params, cfg, "tokendance")`` resolves the mode
    string through the policy registry and behaves bit-exactly like
    ``ServingEngine(params, cfg, TokenDancePolicy())`` (the golden-parity
    suite in ``tests/test_policy_parity.py`` pins this). New code should
    construct a policy object."""

    def __init__(self, params: dict, cfg: ModelConfig, mode: str, *,
                 paged_history: bool = True, paged_attention: bool = True,
                 incremental: bool = True, **kw):
        warnings.warn(
            "MultiAgentEngine(mode=...) is deprecated; pass a ReusePolicy "
            "to ServingEngine (e.g. ServingEngine(params, cfg, "
            "TokenDancePolicy())) instead.",
            DeprecationWarning, stacklevel=2)
        assert mode in MODES, mode
        policy_kw = ({"paged_history": paged_history,
                      "paged_attention": paged_attention,
                      "incremental": incremental}
                     if mode == "tokendance" else {})
        super().__init__(params, cfg, get_policy(mode, **policy_kw), **kw)
