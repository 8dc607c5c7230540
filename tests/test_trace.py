"""The serving engine's spans and first-call counters
(``repro.serving.trace``).

Pinned here: spans nest under the right parents and carry their round
and gather group; a disabled tracer keeps nothing; ``RoundStats.t_*``
are the round's sums of their spans in both engines; every program is
built once per process and bucketed shape key and counted once in
``reuse["jit"]``, and a second engine builds none; the process's program
table keeps no engine alive; one collective recovery pass runs per group
and round; and tracing changes no served token or logit.
"""
import gc
import weakref
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.rounds import SubsetGather, generate_trace
from repro.models import init_params
from repro.serving import (ContinuousEngine, ServingEngine,
                           TokenDancePolicy, Tracer)
from repro.core.pic import bucket_len
import repro.serving.trace as tracemod
from repro.serving.trace import JitCache, clear_programs

N_AGENTS = 4
N_ROUNDS = 3
GEN = 32
GROUPS = ("g0", "g1")          # two committees of two, equal prompt lengths


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen2.5-7b").replace(dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _trace(cfg):
    return generate_trace("generative_agents", N_AGENTS, N_ROUNDS,
                          cfg.vocab_size, seed=11, jitter_hist=False)


def _engine(params, cfg, tracer=None):
    topo = SubsetGather.grouped([f"agent{i}" for i in range(N_AGENTS)], 2)
    return ServingEngine(params, cfg, TokenDancePolicy(), topology=topo,
                         gen_len=GEN, recompute_ratio=0.1, keep_logits=True,
                         tracer=tracer)


def _serve(eng, trace):
    """Serve round by round; per round the stats, the change of the
    collector's pass counter and the drained span records."""
    eng.init_agents(trace)
    out = []
    for rnd in trace.rounds:
        p0 = eng.collector.align_passes
        st = eng.run_round(rnd)
        out.append((st, eng.collector.align_passes - p0, eng.tracer.drain()))
    return out


@pytest.fixture
def empty_program_table():
    """Program counts start from an empty process table, whatever other
    tests of this worker built."""
    clear_programs()


@pytest.fixture(scope="module")
def served(setup):
    cfg, params = setup
    clear_programs()
    on = _engine(params, cfg, Tracer())
    rounds = _serve(on, _trace(cfg))
    off = _engine(params, cfg)
    return on, rounds, off, _serve(off, _trace(cfg))


def test_spans_nest_under_their_parents_with_round_and_gid(served):
    _, rounds, _, _ = served
    parent_of = {"round": None, "prompts": "round", "plan": "round",
                 "restore": "plan", "recover": "round", "decode": "round",
                 "decode.step": "decode", "store": "round",
                 "store.family": "store"}
    for r, (_, _, recs) in enumerate(rounds):
        by_id = {x.id: x for x in recs}
        assert [x.name for x in recs].count("round") == 1
        for x in recs:
            assert x.round == r, x
            assert x.t0 <= x.t1
            if x.name.startswith("jit:"):
                assert x.parent in by_id, x
                continue
            want = parent_of[x.name]
            if want is None:
                assert x.parent is None and x.gid is None
                continue
            p = by_id[x.parent]
            assert p.name == want, (x, p)
            assert p.t0 <= x.t0 and x.t1 <= p.t1
            assert x.gid in GROUPS, x
        names = [x.name for x in recs]
        for name in ("plan", "recover", "decode", "store", "prompts"):
            assert names.count(name) == len(GROUPS), (r, name)
        assert names.count("decode.step") == len(GROUPS) * (GEN - 1)
        # the Master-Mirror restore runs from round 1 on, one per group
        assert names.count("restore") == (len(GROUPS) if r else 0)
        assert names.count("store.family") == len(GROUPS)
        kinds = {x.attrs["kind"] for x in recs if x.name == "recover"}
        assert kinds == {"recompute" if r == 0 else "reuse"}


def test_disabled_tracer_keeps_nothing(served):
    _, _, off, rounds = served
    assert not off.tracer.enabled
    assert all(recs == [] for _, _, recs in rounds)
    assert off.tracer.totals["recover"] > 0     # still timed
    tr = Tracer(enabled=False)
    with tr.span("x", round=0, gid="g0", a=1) as sp:
        pass
    assert sp.dt >= 0 and tr.drain() == [] and tr.total("x") == sp.dt


@pytest.mark.parametrize("field,span", [("t_recover", "recover"),
                                        ("t_restore", "restore"),
                                        ("t_decode", "decode"),
                                        ("t_store", "store")])
def test_round_stats_are_sums_of_their_spans(served, field, span):
    _, rounds, _, _ = served
    for st, _, recs in rounds:
        spans = [x.dur for x in recs if x.name == span]
        assert getattr(st, field) == pytest.approx(sum(spans), rel=1e-9,
                                                   abs=1e-12)
    assert sum(getattr(st, field) for st, _, _ in rounds) > 0


def test_new_programs_count_each_shape_once(served):
    """Recovery and decode are keyed by the prompt length bucketed to 8
    blocks: a session whose prompts grow by a block a round inside one
    bucket builds each once, and the second group of a round reuses it."""
    _, rounds, _, _ = served
    news = [st.reuse["jit"]["new_programs"] for st, _, _ in rounds]
    assert len({st.prompt_len for st, _, _ in rounds}) == N_ROUNDS
    for st, _, _ in rounds:
        assert [b["S_padded"] for b in st.reuse["bucket"]] == \
            [bucket_len(st.prompt_len, 32)] * len(GROUPS) == [256, 256]
    assert news[0] == {"prefill": 1, "decode_step_paged": 1}, news[0]
    assert news[1] == {"collective_recover": 1}, news[1]
    assert news[2:] == [{}] * (N_ROUNDS - 2), news
    # first calls ran inside jit:<name> spans, once per new program
    for (st, _, recs) in rounds:
        firsts = defaultdict(int)
        for x in recs:
            if x.name.startswith("jit:"):
                firsts[x.name[4:]] += 1
        assert dict(firsts) == st.reuse["jit"]["new_programs"]


def test_second_engine_builds_no_program(setup, served):
    """A fresh engine in the same process runs what the first one built:
    no new program and no ``jit:`` span in any round."""
    cfg, params = setup
    again = _serve(_engine(params, cfg, Tracer()), _trace(cfg))
    for st, _, recs in again:
        assert st.reuse["jit"]["new_programs"] == {}
        assert not [x for x in recs if x.name.startswith("jit:")]
        assert [x.name for x in recs].count("recover") == len(GROUPS)


def test_program_table_keeps_no_engine(setup, empty_program_table):
    cfg, params = setup
    eng = _engine(params, cfg, Tracer())
    rounds = _serve(eng, generate_trace(
        "generative_agents", N_AGENTS, 2, cfg.vocab_size, seed=11,
        jitter_hist=False))
    # it built its programs (prefill, decode, recovery) into the table
    assert {k for st, _, _ in rounds
            for k in st.reuse["jit"]["new_programs"]} == {
        "prefill", "decode_step_paged", "collective_recover"}
    ref = weakref.ref(eng)
    del eng
    gc.collect()
    assert ref() is None


def test_jit_cache_names_and_counts_programs(empty_program_table):
    tr = Tracer()
    cache = JitCache(tr)
    built = []

    def make():
        built.append(1)

        def f(x):
            return x * 2
        return f

    a = cache.get_jit("double", (4,), make)
    assert cache.get_jit("double", (4,), make) is a
    assert cache.take_new_programs() == {"double": 1}
    x = jnp.ones(4)
    np.testing.assert_array_equal(a(x), 2 * np.ones(4))
    a(x)
    assert [r.name for r in tr.drain()] == ["jit:double"]
    assert "jit_double" in a.fn.lower(x).as_text()
    # a repeated shape counts nothing; a new key counts again
    cache.get_jit("double", (4,), make)
    assert cache.take_new_programs() == {}
    cache.get_jit("double", (8,), make)
    assert cache.take_new_programs() == {"double": 1}
    assert len(built) == 2
    # another cache of the process finds both built: nothing new, no span
    other = JitCache(tr)
    b = other.get_jit("double", (4,), make)
    b(x)
    assert other.take_new_programs() == {} and len(built) == 2
    assert tr.drain() == []


def test_program_table_stays_bounded(empty_program_table):
    """A prompt that grows across many buckets keeps at most
    ``MAX_PROGRAMS`` programs in the process table: the least recently
    used goes first, the newest buckets stay."""
    def make():
        return lambda x: x + 1

    cache = JitCache()
    top = 256 * (tracemod.MAX_PROGRAMS + 8)
    buckets = sorted({bucket_len(S, 32) for S in range(32, top, 32)})
    assert len(buckets) > tracemod.MAX_PROGRAMS
    for b in buckets:
        cache.get_jit("grow", (b,), make)
    assert cache.take_new_programs() == {"grow": len(buckets)}
    assert len(tracemod._PROGRAMS) == tracemod.MAX_PROGRAMS
    fresh = JitCache()
    fresh.get_jit("grow", (buckets[-1],), make)      # kept
    assert fresh.take_new_programs() == {}
    fresh.get_jit("grow", (buckets[0],), make)       # dropped: built again
    assert fresh.take_new_programs() == {"grow": 1}
    assert len(tracemod._PROGRAMS) == tracemod.MAX_PROGRAMS


def test_one_recovery_pass_per_collective_group_and_round(served):
    _, rounds, _, _ = served
    assert rounds[0][1] == 0                  # round 0 is a full prefill
    for st, passes, _ in rounds[1:]:
        assert passes == len(GROUPS)
        assert st.reuse["align_passes"] == [1] * len(GROUPS)


def test_tracing_changes_no_token_or_logit(served):
    _, on, _, off = served
    for (a, _, _), (b, _, _) in zip(on, off):
        np.testing.assert_array_equal(a.outputs, b.outputs)
        np.testing.assert_array_equal(a.first_logits, b.first_logits)


def test_continuous_engine_stats_are_sums_of_spans(setup):
    cfg, params = setup
    topo = SubsetGather.grouped([f"agent{i}" for i in range(N_AGENTS)], 2)
    tr = Tracer()
    res = ContinuousEngine(params, cfg, "tokendance", topology=topo,
                           gen_len=GEN, recompute_ratio=0.1,
                           tracer=tr).serve(_trace(cfg), stagger=[0, 3])
    recs = tr.drain()
    for c, stats in res.stats.items():
        gid = f"g{c}"
        for st in stats:
            mine = [x for x in recs if x.gid == gid and x.round == st.round_idx]

            def total(*names):
                return sum(x.dur for x in mine if x.name in names)
            assert st.t_recover == pytest.approx(total("recover"))
            assert st.t_restore == pytest.approx(total("restore"))
            assert st.t_decode == pytest.approx(total("decode", "decode.step"))
            assert st.t_store == pytest.approx(total("store"))
            assert sum(1 for x in mine if x.name == "decode.step") == GEN - 1


def test_pool_counts_pages_at_the_model_dtype(setup):
    cfg, params = setup
    f32 = ServingEngine(params, cfg).pool.page_bytes()
    bf16 = ServingEngine(params, cfg.replace(dtype="bfloat16")).pool
    assert bf16.dtype == jnp.bfloat16
    assert 2 * bf16.page_bytes() == f32
