"""Golden parity for the policy-object serving API (the refactor's
safety net): a frozen trace served under every legacy mode string must be
indistinguishable — outputs, recovery logits, reuse ledgers, byte
ledgers — from the same trace served through the corresponding policy
object, and the ``mode=`` shim must say it is deprecated."""
import warnings

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.rounds import generate_trace
from repro.models import init_params
from repro.serving import (
    MODES,
    MultiAgentEngine,
    PICPolicy,
    PrefixCachePolicy,
    RecomputePolicy,
    ServingEngine,
    TokenDancePolicy,
    get_policy,
)
from repro.serving.trace import clear_programs

N_AGENTS = 3
N_ROUNDS = 3
GEN = 32

POLICY_CLASSES = {
    "recompute": RecomputePolicy,
    "prefix": PrefixCachePolicy,
    "pic": PICPolicy,
    "tokendance": TokenDancePolicy,
}


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen2.5-7b").replace(dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _trace(cfg):
    return generate_trace("generative_agents", N_AGENTS, N_ROUNDS,
                          cfg.vocab_size, seed=11, jitter_hist=False)


@pytest.fixture(scope="module")
def served(setup):
    """Every mode served twice: legacy shim vs explicit policy object.
    Each engine starts from an empty program table, so both build (and
    count in ``reuse["jit"]``) the programs of the trace."""
    cfg, params = setup
    out = {}
    for mode in MODES:
        clear_programs()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            legacy = MultiAgentEngine(params, cfg, mode, gen_len=GEN,
                                      recompute_ratio=0.1, keep_logits=True)
        ls = legacy.run_trace(_trace(cfg))
        clear_programs()
        modern = ServingEngine(params, cfg, POLICY_CLASSES[mode](),
                               gen_len=GEN, recompute_ratio=0.1,
                               keep_logits=True)
        ms = modern.serve(_trace(cfg))
        out[mode] = (ls, ms)
    return out


def _assert_ledgers_equal(a: dict, b: dict, where):
    assert set(a) == set(b), (where, set(a), set(b))
    for k in a:
        if isinstance(a[k], dict):
            _assert_ledgers_equal(a[k], b[k], (*where, k))
        else:
            assert np.all(np.asarray(a[k]) == np.asarray(b[k])), (*where, k)


@pytest.mark.parametrize("mode", MODES)
def test_policy_matches_legacy_mode(served, mode):
    ls, ms = served[mode]
    for r in range(N_ROUNDS):
        np.testing.assert_array_equal(ls[r].outputs, ms[r].outputs)
        np.testing.assert_array_equal(ls[r].first_logits, ms[r].first_logits)
        _assert_ledgers_equal(
            {k: v for k, v in ls[r].reuse.items() if k != "plan"},
            {k: v for k, v in ms[r].reuse.items() if k != "plan"},
            (mode, r))
        assert ls[r].persistent_bytes == ms[r].persistent_bytes, (mode, r)
        assert ls[r].transient_peak_bytes == ms[r].transient_peak_bytes, (mode, r)
        assert ls[r].mode == ms[r].mode == mode


def test_tokendance_dense_oracle_parity(setup):
    """The paged_history plumbing survives the lift: dense oracle ==
    paged default through the policy object, and the shim forwards the
    flag."""
    cfg, params = setup
    paged = ServingEngine(params, cfg, TokenDancePolicy(paged_history=True),
                          gen_len=GEN, recompute_ratio=0.1,
                          keep_logits=True).serve(_trace(cfg))
    dense = ServingEngine(params, cfg, TokenDancePolicy(paged_history=False),
                          gen_len=GEN, recompute_ratio=0.1,
                          keep_logits=True).serve(_trace(cfg))
    for r in range(N_ROUNDS):
        np.testing.assert_array_equal(paged[r].outputs, dense[r].outputs)
        np.testing.assert_array_equal(paged[r].first_logits,
                                      dense[r].first_logits)
    assert paged[-1].reuse["restore"]["paged"]
    assert not dense[-1].reuse["restore"]["paged"]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        shim = MultiAgentEngine(params, cfg, "tokendance",
                                paged_history=False, gen_len=GEN)
    assert shim.policy.paged_history is False


def test_mode_shim_emits_deprecation_warning(setup):
    cfg, params = setup
    with pytest.warns(DeprecationWarning, match="MultiAgentEngine"):
        eng = MultiAgentEngine(params, cfg, "recompute", gen_len=GEN)
    assert eng.mode == "recompute"
    assert isinstance(eng.policy, RecomputePolicy)


def test_registry_round_trips_every_mode():
    for mode in MODES:
        p = get_policy(mode)
        assert p.name == mode
        assert isinstance(p, POLICY_CLASSES[mode])
    with pytest.raises(KeyError):
        get_policy("no-such-policy")


# ------------------------------------------------- cross-round (ISSUE 8)
def test_four_round_committee_parity(setup):
    """Golden multi-round regression for the cross-round incremental
    restore: a 4-round committee trace (grouped committees of 2, so one
    two-agent family AND one singleton family run side by side) served
    by all four policies; the TokenDance engine with incremental restore
    must be bit-exact — outputs and logits — against the full-restore
    and dense-oracle engines EVERY round, and the restore ledgers must
    agree on everything except the counted restore work."""
    from repro.core.rounds import SubsetGather

    cfg, params = setup
    rounds = 4
    aids = [f"agent{i}" for i in range(N_AGENTS)]
    topo = SubsetGather.grouped(aids, 2)
    trace = generate_trace("generative_agents", N_AGENTS, rounds,
                           cfg.vocab_size, seed=11, jitter_hist=False)

    def run(policy):
        return ServingEngine(params, cfg, policy, topology=topo,
                             gen_len=GEN, recompute_ratio=0.1,
                             keep_logits=True).serve(trace)

    # every policy must complete the committee trace (baselines are not
    # parity-checked against each other — they answer differently by
    # design — but none may crash or drop a round under regrouped input)
    for mode in MODES:
        if mode == "tokendance":
            continue
        s = run(POLICY_CLASSES[mode]())
        assert len(s) == rounds
        assert all(st.outputs is not None for st in s), mode

    inc = run(TokenDancePolicy())                      # cross-round delta
    full = run(TokenDancePolicy(incremental=False))    # rebuild each round
    dense = run(TokenDancePolicy(paged_history=False))  # oracle
    shared_keys = ("paged", "n_restored", "n_mirrors", "nb",
                   "full_write_pages", "page_bytes", "dense_equiv_bytes")
    for r in range(rounds):
        np.testing.assert_array_equal(inc[r].outputs, full[r].outputs)
        np.testing.assert_array_equal(inc[r].outputs, dense[r].outputs)
        np.testing.assert_array_equal(inc[r].first_logits,
                                      full[r].first_logits)
        np.testing.assert_array_equal(inc[r].first_logits,
                                      dense[r].first_logits)
        if r == 0:
            continue                # recompute round: no restore ledger
        ri, rf = inc[r].reuse["restore"], full[r].reuse["restore"]
        ri = ri if isinstance(ri, list) else [ri]
        rf = rf if isinstance(rf, list) else [rf]
        assert len(ri) == len(rf) == 2          # one ledger per committee
        for a, b in zip(ri, rf):
            for k in shared_keys:   # identical work described...
                assert a[k] == b[k], (r, k, a, b)
            if r == 1:              # pool bootstrap IS the full restore
                assert a == b, (r, a, b)
            else:                   # ...but only the delta is re-done
                assert a["incremental"] and not b["incremental"], (r, a, b)
                assert a["pool_pages"] < b["pool_pages"], (r, a, b)
                assert a["pages_reused"] > 0, (r, a)
