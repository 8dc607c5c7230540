"""The system under test, as the benchmark drives it: with the
architecture adapters (``bench/adapters/<architecture>.py``), the only
modules of the benchmark that import the program.

A :class:`Server` is one session: a fresh ``ServingEngine`` with a
``TokenDancePolicy`` and every engine knob at the program's default
except the ones the traffic file fixes (topology, ``gen_len``,
``recompute_ratio``), stepped one round at a time with ``run_round``.
The policy's ``plan``, ``recover`` and ``store`` are wrapped in the
recorder's spans; decode is the interval from the return of ``recover``
to the call of ``store``. Each round's reuse ledgers go to the record as
plain values. A traced session (``trace=True``) ends the ``plan`` span on
the plan's device arrays, and its engine records its own spans, which go
to the record after each round.
"""
from __future__ import annotations

import importlib.util
import json
import time
from pathlib import Path

import jax
import numpy as np

from bench.record import Batch, Recorder, Round
from bench.traffic.generator import SessionTraffic

ADAPTERS = Path(__file__).resolve().parent / "adapters"


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def program_config(cfg: dict, adapters: Path = ADAPTERS):
    """The program's ``ModelConfig`` for ``cfg``, made by the adapter of
    its architecture, ``<adapters>/<architecture>.py``."""
    arch = cfg["architecture"]
    path = Path(adapters) / f"{arch}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"no adapter for architecture {arch!r}: {path} is missing")
    return load_module(path, f"bench_adapter_{arch}").program_config(cfg)


def _plain(x):
    """``x`` as JSON values: numpy scalars and arrays become numbers and
    lists, anything else that JSON cannot hold its ``repr``."""
    def default(o):
        if isinstance(o, (np.generic, np.ndarray)):
            return o.tolist()
        return repr(o)
    return json.loads(json.dumps(x, default=default))


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
                 trace: bool = False, adapters: Path = ADAPTERS):
        from repro.core.rounds import AllGatherTrace, SubsetGather
        from repro.serving import ServingEngine, TokenDancePolicy

        self.traffic, self.session, self.rec = traffic, session, rec
        self.index = index
        self.round_idx = 0
        topo = traffic["topology"]
        topology = (None if topo["kind"] == "all_gather" else
                    SubsetGather.neighborhood(session.agent_ids, topo["k"]))
        self.policy = TokenDancePolicy()
        self.tracer = _tracer() if trace else None
        kw = {} if self.tracer is None else {"tracer": self.tracer}
        self.engine = ServingEngine(
            weights, program_config(cfg, adapters), self.policy,
            topology=topology, gen_len=traffic["gen_len"],
            recompute_ratio=traffic["recompute_ratio"], **kw)
        self.engine.init_agents(AllGatherTrace(
            "bench", list(session.agent_ids), [], session.vocab_size,
            session.vocab_size - 1, dict(session.init_histories),
            traffic["gen_len"]))
        self._wrap(trace)

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
        t1 = time.perf_counter()
        comp = stats.reuse.get("compression") or []
        comp = comp if isinstance(comp, list) else [comp]
        self.rec.rounds.append(Round(
            self.index, r, t0, t1,
            [float(c["compression_ratio"]) for c in comp],
            _plain(stats.reuse)))
        if self.tracer is not None:
            self.rec.program_spans.extend(self.tracer.drain())
        self.round_idx += 1
        return stats

    def close(self) -> None:
        """Put the policy's own ``plan``, ``recover`` and ``store`` back,
        which breaks the cycle the wrappers close over the policy and the
        engine, and drop every reference to the engine's device state, so
        that it is freed here and not at Python's next cycle collection."""
        for name in ("plan", "recover", "store"):
            vars(self.policy).pop(name, None)
        self.engine = self.policy = None


def _tracer():
    """A recording ``repro.serving.trace.Tracer``, or ``None`` for a
    program that has no spans of its own."""
    try:
        from repro.serving.trace import Tracer
    except ImportError:
        return None
    return Tracer()
