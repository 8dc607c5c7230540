"""End-to-end behaviour tests for the serving system: the four reuse modes
agree where the paper says they must, reuse actually reduces work, and
diff-aware storage actually reduces persistent memory."""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.rounds import generate_trace
from repro.models import init_params
from repro.serving import MultiAgentEngine, simulate_round_latency, ServiceTimes
from repro.serving.trace import clear_programs

N_AGENTS = 4
N_ROUNDS = 3
GEN = 32


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen2.5-7b").replace(dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _run(cfg, params, mode, **kw):
    trace = generate_trace("generative_agents", N_AGENTS, N_ROUNDS,
                           cfg.vocab_size, seed=11, jitter_hist=False)
    eng = MultiAgentEngine(params, cfg, mode, gen_len=GEN,
                           recompute_ratio=0.1, **kw)
    return eng, eng.run_trace(trace)


@pytest.fixture(scope="module")
def all_modes(setup):
    cfg, params = setup
    out = {}
    for mode in ["recompute", "prefix", "pic", "tokendance"]:
        out[mode] = _run(cfg, params, mode)
    return out


def test_exact_modes_agree(all_modes):
    """prefix caching is exact: outputs must equal full recompute."""
    _, rec = all_modes["recompute"]
    _, pre = all_modes["prefix"]
    for r in range(N_ROUNDS):
        np.testing.assert_array_equal(rec[r].outputs, pre[r].outputs)


def test_collective_equals_per_request(all_modes):
    """Paper §6.6: TokenDance output == per-request PIC output."""
    _, pic = all_modes["pic"]
    _, td = all_modes["tokendance"]
    for r in range(N_ROUNDS):
        np.testing.assert_array_equal(pic[r].outputs, td[r].outputs)


def test_pic_approximation_is_bounded(all_modes):
    """PIC may flip greedy tokens eventually but round 0 (no reuse yet)
    must be identical to recompute."""
    _, rec = all_modes["recompute"]
    _, pic = all_modes["pic"]
    np.testing.assert_array_equal(rec[0].outputs, pic[0].outputs)


def test_tokendance_compresses_storage(all_modes):
    """Persistent bytes: tokendance << prefix (the paper's memory claim).
    persistent_bytes is the must-keep store (masters + mirror diffs +
    outputs); the cross-round incremental-restore pool is a droppable
    accelerator cache reported separately (restore_cache_bytes) — it
    trades resident memory for O(round delta) restore work and is not
    part of the compression claim."""
    _, pre = all_modes["prefix"]
    _, td = all_modes["tokendance"]
    last_pre = pre[-1].persistent_bytes
    last_td = td[-1].persistent_bytes
    assert last_td < last_pre, (last_td, last_pre)
    comp = td[-1].reuse["compression"]
    assert comp["per_mirror_ratio"] > 1.0
    assert comp["avg_changed_blocks"] < comp["total_blocks"]
    # the restore cache is resident (incremental default) and visible
    assert td[-1].reuse["pool"]["restore_cache_bytes"] > 0


def test_collective_is_faster_than_serial(all_modes):
    """The collective pass does O(1) RoPE-align + selection passes per
    round where serial PIC does N. Asserts on counted work (the
    collector's align_passes ledger) — wall-clock on shared CI is
    contention-flaky and proves nothing about the algorithm."""
    _, pic = all_modes["pic"]
    _, td = all_modes["tokendance"]
    # round 0 is a plain prefill for every mode; reuse starts at round 1
    for s in pic[1:]:
        assert s.reuse["align_passes"] == N_AGENTS, s.reuse
    for s in td[1:]:
        assert s.reuse["align_passes"] == 1, s.reuse
    p_serial = sum(s.reuse["align_passes"] for s in pic[1:])
    p_coll = sum(s.reuse["align_passes"] for s in td[1:])
    assert p_coll < p_serial, (p_coll, p_serial)


def test_round_latency_reported(all_modes):
    for mode, (_, stats) in all_modes.items():
        for s in stats:
            assert s.t_round > 0
            assert s.outputs.shape == (N_AGENTS, GEN)


def test_histories_grow_by_outputs(all_modes):
    eng, stats = all_modes["recompute"]
    h0 = 64  # generative_agents initial history
    for aid, sess in eng.sessions.items():
        assert sess.state.history.shape[0] == h0 + N_ROUNDS * GEN


def test_ssm_arch_falls_back_to_recompute(setup):
    """PIC reuse is inapplicable to SSM state (DESIGN §5) — the engine
    must still serve mamba2 via full recompute."""
    cfg = get_smoke_config("mamba2-2.7b").replace(dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    trace = generate_trace("generative_agents", 2, 2, cfg.vocab_size,
                           seed=3, jitter_hist=False)
    eng = MultiAgentEngine(params, cfg, "tokendance", gen_len=32)
    assert eng.mode == "recompute"
    stats = eng.run_trace(trace)
    assert all(s.outputs is not None for s in stats)


def test_queueing_simulator_monotone():
    """Round latency grows with agent count and offered load for serial
    service; the collective mode amortizes both."""
    serial = ServiceTimes(per_request_recover=0.1, collective_recover=0.15,
                          decode=0.05, collective=False)
    coll = ServiceTimes(per_request_recover=0.1, collective_recover=0.15,
                        decode=0.05, collective=True)
    lat_s = [simulate_round_latency(serial, n, qps=2) for n in (2, 4, 8)]
    lat_c = [simulate_round_latency(coll, n, qps=2) for n in (2, 4, 8)]
    assert lat_s[0] < lat_s[1] < lat_s[2]
    assert lat_c[2] < lat_s[2]
    # load monotonicity + saturation
    assert (simulate_round_latency(serial, 4, qps=1)
            < simulate_round_latency(serial, 4, qps=4))
    assert simulate_round_latency(serial, 8, qps=100) == float("inf")


def test_memory_fallback_degrades_service():
    """Over the pool budget, evicted agents pay the recompute round."""
    st = ServiceTimes(per_request_recover=0.01, collective_recover=0.02,
                      decode=0.01, collective=True,
                      persistent_per_agent=100.0, recompute_round=1.0)
    fits = simulate_round_latency(st, 4, qps=1, pool_budget_bytes=1000)
    over = simulate_round_latency(st, 4, qps=1, pool_budget_bytes=200)
    assert over > fits


@pytest.fixture
def empty_program_table():
    """The test builds every program it inspects, whatever other tests of
    this worker built, and leaves none of its spied ones behind."""
    clear_programs()
    yield
    clear_programs()


@pytest.mark.parametrize("mode,kw", [("prefix", {}),
                                     ("tokendance", {"paged_decode": False})])
def test_jitted_steps_take_weights_as_arguments(setup, monkeypatch, mode, kw,
                                                empty_program_table):
    """Every model step the engine jits receives the weights as an
    argument. A closed-over weight is lowered into the program as a dense
    constant — at full width on a chip, gigabytes of them per program."""
    cfg, params = setup
    real_jit = jax.jit
    calls = {}

    def spy_jit(fn, *a, **k):
        jitted = real_jit(fn, *a, **k)
        if not fn.__module__.startswith("repro."):
            return jitted

        def call(*args):
            calls.setdefault(jitted, args)
            return jitted(*args)
        return call

    monkeypatch.setattr(jax, "jit", spy_jit)
    _run(cfg, params, mode, **kw)
    monkeypatch.undo()
    # prefix: prefill + extend + paged decode; tokendance: prefill +
    # collective recovery + dense decode
    assert len(calls) >= 3, calls
    for jitted, args in calls.items():
        assert any(a is params for a in args), jitted
        text = jitted.lower(*args).as_text()
        assert max(map(len, text.splitlines())) < 1 << 16, jitted
