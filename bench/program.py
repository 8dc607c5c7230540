"""The system under test, as the benchmark drives it: the only module of
the benchmark that imports the program.

A :class:`Server` is one session: a fresh ``ServingEngine`` with a
``TokenDancePolicy`` and every engine knob at the program's default
except the ones the traffic file fixes (topology, ``gen_len``,
``recompute_ratio``), stepped one round at a time with ``run_round``.
The policy's ``plan``, ``recover`` and ``store`` are wrapped in the
recorder's spans; decode is the interval from the return of ``recover``
to the call of ``store``.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench.record import Batch, Recorder, Round
from bench.traffic.generator import SessionTraffic


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a Hugging Face style Qwen2
    config."""
    from repro.configs.base import ModelConfig

    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], arch_type="dense",
        n_layers=cfg["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=cfg["num_key_value_heads"], head_dim=d // h,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        attn_bias=True, rope_theta=float(cfg["rope_theta"]),
        rmsnorm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["torch_dtype"], source=cfg["source"])


def _plan_arrays(plan) -> list:
    """Device arrays a recovery plan hands to ``recover``."""
    out = []
    for x in plan.assembled or ():
        if isinstance(x, jax.Array):
            out.append(x)
        elif hasattr(x, "__dict__"):
            out.extend(v for v in vars(x).values()
                       if isinstance(v, jax.Array))
    return out


class Server:
    """One session of the cell's traffic on a fresh engine."""

    def __init__(self, cfg: dict, traffic: dict, weights: dict,
                 session: SessionTraffic, rec: Recorder, index: int,
                 sync_plan: bool = False):
        from repro.core.rounds import AllGatherTrace, SubsetGather
        from repro.serving import ServingEngine, TokenDancePolicy

        self.traffic, self.session, self.rec = traffic, session, rec
        self.index = index
        self.round_idx = 0
        topo = traffic["topology"]
        topology = (None if topo["kind"] == "all_gather" else
                    SubsetGather.neighborhood(session.agent_ids, topo["k"]))
        self.policy = TokenDancePolicy()
        self.engine = ServingEngine(
            weights, program_config(cfg), self.policy, topology=topology,
            gen_len=traffic["gen_len"],
            recompute_ratio=traffic["recompute_ratio"])
        self.engine.init_agents(AllGatherTrace(
            "bench", list(session.agent_ids), [], session.vocab_size,
            session.vocab_size - 1, dict(session.init_histories),
            traffic["gen_len"]))
        self._wrap(sync_plan)

    @property
    def done(self) -> bool:
        return self.round_idx >= self.traffic["rounds_per_session"]

    def _wrap(self, sync_plan: bool) -> None:
        rec, pol, eng = self.rec, self.policy, self.engine
        plan, recover, store = pol.plan, pol.recover, pol.store
        state = {}

        def plan_(ctx):
            b = Batch(self.index, ctx.round_idx, ctx.gid,
                      list(ctx.agent_ids), np.array(ctx.tokens),
                      state["t_round"])
            rec.batches.append(b)
            with rec.span("plan"):
                p = plan(ctx)
                if sync_plan:
                    jax.block_until_ready(_plan_arrays(p))
            b.kind, b.n_sel = p.kind, int(p.n_sel)
            return p

        def recover_(p, tokens):
            b = rec.batches[-1]
            passes = eng.collector.align_passes
            with rec.span("recover"):
                res = recover(p, tokens)
            b.t_recover_end = time.perf_counter()
            b.passes = eng.collector.align_passes - passes
            plan = res.info.get("plan")
            if plan is not None and plan.sel_idx_all is not None:
                b.selected = np.asarray(plan.sel_idx_all)
            rec.open("decode")
            return res

        def store_(ctx, cache, outputs, res, stats):
            rec.close("decode")
            rec.batches[-1].outputs = np.array(outputs)
            with rec.span("store"):
                return store(ctx, cache, outputs, res, stats)

        pol.plan, pol.recover, pol.store = plan_, recover_, store_
        self._state = state

    def run_round(self):
        """Serve the next round; return the program's ``RoundStats``."""
        from repro.core.rounds import Round as ProgramRound

        r = self.round_idx
        s = self.session
        rnd = ProgramRound(r, list(s.shared[r]), dict(s.tasks[r]))
        self.rec.session, self.rec.round = self.index, r
        t0 = time.perf_counter()
        self._state["t_round"] = t0
        with self.rec.span("round"):
            stats = self.engine.run_round(rnd)
        comp = stats.reuse.get("compression") or []
        comp = comp if isinstance(comp, list) else [comp]
        self.rec.rounds.append(Round(
            self.index, r, t0, time.perf_counter(),
            [float(c["compression_ratio"]) for c in comp]))
        self.round_idx += 1
        return stats

    def close(self) -> None:
        """Drop every reference to the engine's device state."""
        self.engine = self.policy = None
