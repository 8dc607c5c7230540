"""Paged-vs-dense collector parity (ISSUE 3 tentpole).

The collector may consume private histories either pre-densified
([N, L, S, KV, hd] tensors) or PAGED (a family page pool from
``fused_restore_family_shared`` + per-request page tables, gathered
inside the jitted recovery pass). The two forms are pure data-movement
duals, so everything downstream — logits, recovered caches, selected
positions — must agree BIT-FOR-BIT, including M=1 families, ragged
per-mirror diff counts, and zero-diff mirrors whose pages all alias the
Master's.

Engine level: a ``tokendance`` engine with ``paged_history=True`` (the
default) must produce the same outputs and recovered caches as the dense
oracle engine, while handing the collector a ``PagedPrivate`` (never a
densified mirror) and accounting the family's shared pages once.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.collector import KVCollector, PagedPrivate
from repro.core.diff_store import build_round_family
from repro.core.pic import n_sel_for_blocks
from repro.core.restore import fused_restore_family_shared
from repro.core.rounds import generate_trace
from repro.core.segments import PagedSegmentCacheEntry, SegmentCacheEntry
from repro.models import init_params
from repro.serving import MultiAgentEngine

BT = 32


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen2.5-7b").replace(dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _paged_group(cfg, N, *, priv_blocks=2, shared_blocks=1, task_blocks=1,
                 tail_blocks=1, diff_counts=None, seed=0):
    """A synthetic round group whose private histories live in a shared
    family page pool: [paged private | dense tail | shared cached | task].

    The pool comes from the real page-sharing restore of a synthetic
    Master family (``diff_counts[i]`` touched blocks for mirror i; the
    first request is the Master, whose page row is the identity map)."""
    rng = np.random.default_rng(seed)
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.resolved_head_dim
    span_len = priv_blocks * BT
    T = tail_blocks * BT
    sh_len = shared_blocks * BT
    S = span_len + T + sh_len + task_blocks * BT
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size - 1, (N, S)), jnp.int32)

    # family: master cache + per-mirror block perturbations
    base = rng.normal(size=(L, span_len, KV, hd)).astype(np.float32)
    caches = [base]
    counts = diff_counts if diff_counts is not None \
        else [int(c) for c in rng.integers(0, priv_blocks + 1, N - 1)]
    assert len(counts) == N - 1
    for c in counts:
        x = base.copy()
        for b in rng.choice(priv_blocks, c, replace=False):
            x[:, b * BT : (b + 1) * BT] += 0.1 * rng.normal(
                size=(L, BT, KV, hd)).astype(np.float32)
        caches.append(x)
    ks = jnp.asarray(np.stack(caches))
    vs = jnp.asarray(np.stack(caches)[..., ::-1].copy())
    _, handles = build_round_family(
        [f"r{i}" for i in range(N)], ks, vs, np.arange(span_len), 0,
        block_tokens=BT)
    if handles:
        pool_k, pool_v, page_idx = fused_restore_family_shared(handles)
        rows = np.concatenate([np.arange(priv_blocks, dtype=np.int32)[None],
                               page_idx])
    else:   # N == 1: master-only family
        pool_k = ks[0].reshape(L, priv_blocks, BT, KV, hd)
        pool_v = vs[0].reshape(L, priv_blocks, BT, KV, hd)
        rows = np.arange(priv_blocks, dtype=np.int32)[None]

    tail_k = jnp.asarray(rng.normal(size=(N, L, T, KV, hd)), jnp.float32)
    tail_v = jnp.asarray(rng.normal(size=(N, L, T, KV, hd)), jnp.float32)
    psrc = np.broadcast_to(np.arange(S, dtype=np.int32), (N, S)).copy()
    pmask = np.zeros(S, bool)
    pmask[: span_len + T] = True

    priv = PagedPrivate(
        pool_k=pool_k, pool_v=pool_v, page_idx=jnp.asarray(rows),
        src=jnp.asarray(psrc), mask=jnp.asarray(pmask),
        start=0, span_len=span_len, tail_k=tail_k, tail_v=tail_v)

    # group-shared cached span, fresh task span
    sk = jnp.zeros((L, S, KV, hd), jnp.float32)
    sv = jnp.zeros_like(sk)
    s0 = span_len + T
    sk = sk.at[:, s0 : s0 + sh_len].set(
        jnp.asarray(rng.normal(size=(L, sh_len, KV, hd)), jnp.float32))
    sv = sv.at[:, s0 : s0 + sh_len].set(
        jnp.asarray(rng.normal(size=(L, sh_len, KV, hd)), jnp.float32))
    src = np.arange(S, dtype=np.int32)
    src[s0 : s0 + sh_len] = np.arange(sh_len)   # shared values from pos 0..
    smask = np.zeros(S, bool)
    smask[s0 : s0 + sh_len] = True

    fresh = ~(smask | pmask)
    n_sel = n_sel_for_blocks(fresh, BT, 0.15)
    return (tokens, sk, sv, jnp.asarray(src), jnp.asarray(smask), n_sel,
            priv, S)


def _assert_results_equal(a, b):
    for name in ("logits", "recovered_k", "recovered_v", "sel_idx",
                 "deviation"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=f"paged/dense mismatch: {name}")


# ----------------------------------------------------------- collector level
@pytest.mark.parametrize("case", [
    dict(N=2, diff_counts=[1]),            # M=1 family
    dict(N=4, diff_counts=[0, 2, 1]),      # ragged counts + zero-diff mirror
    dict(N=3, diff_counts=[2, 2]),         # every private block diffed
])
def test_collective_paged_equals_dense(setup, case):
    """collective_reuse(PagedPrivate) == collective_reuse(dense tuple),
    bit-for-bit on logits, caches, deviations and selections."""
    cfg, params = setup
    (tokens, sk, sv, src, smask, n_sel, priv, S) = _paged_group(
        cfg, case["N"], diff_counts=case["diff_counts"], seed=case["N"])
    ids = [f"a{i}" for i in range(case["N"])]

    coll = KVCollector(params, cfg, block_select=BT, recompute_ratio=0.15)
    res_paged = coll.collective_reuse(ids, tokens, sk, sv, src, smask,
                                      n_sel, priv)
    res_dense = coll.collective_reuse(ids, tokens, sk, sv, src, smask,
                                      n_sel, priv.materialize(S))
    _assert_results_equal(res_paged.pic, res_dense.pic)
    assert res_paged.plan.master == res_dense.plan.master
    np.testing.assert_array_equal(res_paged.plan.deviations,
                                  res_dense.plan.deviations)


def test_collective_paged_no_tail(setup):
    """T=0 (no dense suffix) exercises the tail-less runner signature."""
    cfg, params = setup
    (tokens, sk, sv, src, smask, n_sel, priv, S) = _paged_group(
        cfg, 3, diff_counts=[1, 2], tail_blocks=1, seed=7)
    # rebuild the bundle without its tail: shrink the private span to the
    # paged part only
    pmask = np.zeros(S, bool)
    pmask[: priv.span_len] = True
    priv2 = PagedPrivate(
        pool_k=priv.pool_k, pool_v=priv.pool_v, page_idx=priv.page_idx,
        src=priv.src, mask=jnp.asarray(pmask), start=0,
        span_len=priv.span_len)
    ids = ["a0", "a1", "a2"]
    coll = KVCollector(params, cfg, block_select=BT, recompute_ratio=0.15)
    res_p = coll.collective_reuse(ids, tokens, sk, sv, src, smask, n_sel,
                                  priv2)
    res_d = coll.collective_reuse(ids, tokens, sk, sv, src, smask, n_sel,
                                  priv2.materialize(S))
    _assert_results_equal(res_p.pic, res_d.pic)


def test_fast_path_never_densifies(setup, monkeypatch):
    """THE grep-able acceptance bar of ISSUE 5: on the fast path a
    PagedPrivate reaches attention with NO call to ``_densify_paged`` —
    neither on the host nor inside the jitted recovery pass. The oracle
    opt-out (``paged_attention=False``) must still go through it."""
    import repro.core.collector as collector_mod
    cfg, params = setup
    calls = []
    orig = collector_mod._densify_paged

    def spy(*args, **kw):
        calls.append(1)
        return orig(*args, **kw)

    monkeypatch.setattr(collector_mod, "_densify_paged", spy)
    (tokens, sk, sv, src, smask, n_sel, priv, S) = _paged_group(
        cfg, 3, diff_counts=[1, 2], seed=21)
    ids = ["a0", "a1", "a2"]
    coll = KVCollector(params, cfg, block_select=BT, recompute_ratio=0.15)
    res_fast = coll.collective_reuse(ids, tokens, sk, sv, src, smask,
                                     n_sel, priv)
    assert not calls, "fast path called _densify_paged"
    res_oracle = coll.collective_reuse(ids, tokens, sk, sv, src, smask,
                                       n_sel, priv, paged_attention=False)
    assert calls, "oracle path must keep _densify_paged alive"
    _assert_results_equal(res_fast.pic, res_oracle.pic)


def test_paged_attention_oracle_parity(setup):
    """Three-way bit-exact: zero-densify fast path == jit-level densify
    oracle == pre-densified dense tuple."""
    cfg, params = setup
    (tokens, sk, sv, src, smask, n_sel, priv, S) = _paged_group(
        cfg, 4, diff_counts=[0, 2, 1], seed=23)
    ids = [f"a{i}" for i in range(4)]
    coll = KVCollector(params, cfg, block_select=BT, recompute_ratio=0.15)
    fast = coll.collective_reuse(ids, tokens, sk, sv, src, smask, n_sel,
                                 priv)
    oracle = coll.collective_reuse(ids, tokens, sk, sv, src, smask, n_sel,
                                   priv, paged_attention=False)
    dense = coll.collective_reuse(ids, tokens, sk, sv, src, smask, n_sel,
                                  priv.materialize(S))
    _assert_results_equal(fast.pic, oracle.pic)
    _assert_results_equal(fast.pic, dense.pic)


def test_non_identity_src_falls_back_to_oracle(setup):
    """A PagedPrivate whose span needs RoPE realignment fails the fast
    path's structural gate and is routed through the densify oracle —
    results must still match the dense tuple exactly."""
    cfg, params = setup
    (tokens, sk, sv, src, smask, n_sel, priv, S) = _paged_group(
        cfg, 3, diff_counts=[1, 1], seed=25)
    shifted = np.asarray(priv.src).copy()
    shifted[:, : priv.span_len] += 7          # span cached at other positions
    priv2 = PagedPrivate(
        pool_k=priv.pool_k, pool_v=priv.pool_v, page_idx=priv.page_idx,
        src=jnp.asarray(shifted), mask=priv.mask, start=0,
        span_len=priv.span_len, tail_k=priv.tail_k, tail_v=priv.tail_v)
    assert not priv2.identity_span_src()
    assert KVCollector._priv_args(priv2)[0] == "paged_densify"
    assert KVCollector._priv_args(priv)[0] == "paged"
    # a mask that disagrees with the span+tail placement also fails the
    # gate (the fast path writes the region unconditionally; the oracle
    # honors the mask — they only coincide when the two match)
    short_mask = np.asarray(priv.mask).copy()
    short_mask[priv.span_len :] = False       # drops the tail region
    priv3 = PagedPrivate(
        pool_k=priv.pool_k, pool_v=priv.pool_v, page_idx=priv.page_idx,
        src=priv.src, mask=jnp.asarray(short_mask), start=0,
        span_len=priv.span_len, tail_k=priv.tail_k, tail_v=priv.tail_v)
    assert priv3.identity_span_src() and not priv3.fast_path_ok()
    assert KVCollector._priv_args(priv3)[0] == "paged_densify"
    ids = ["a0", "a1", "a2"]
    coll = KVCollector(params, cfg, block_select=BT, recompute_ratio=0.15)
    res_p = coll.collective_reuse(ids, tokens, sk, sv, src, smask, n_sel,
                                  priv2)
    res_d = coll.collective_reuse(ids, tokens, sk, sv, src, smask, n_sel,
                                  priv2.materialize(S))
    _assert_results_equal(res_p.pic, res_d.pic)


def test_serial_paged_equals_dense(setup):
    """The serial baseline accepts PagedPrivate by densifying up front —
    results must match passing the dense tuple directly."""
    cfg, params = setup
    (tokens, sk, sv, src, smask, n_sel, priv, S) = _paged_group(
        cfg, 2, diff_counts=[1], seed=5)
    ids = ["a0", "a1"]
    coll = KVCollector(params, cfg, block_select=BT, recompute_ratio=0.15)
    out_p = coll.serial_reuse(ids, tokens, sk, sv, src, smask, n_sel, priv)
    out_d = coll.serial_reuse(ids, tokens, sk, sv, src, smask, n_sel,
                              priv.materialize(S))
    for a, b in zip(out_p, out_d):
        _assert_results_equal(a, b)


def test_paged_private_materialize_oracle(setup):
    """materialize() is the documented gather: pool[:, page_idx[n]]
    placed at [start, start+span_len), tail after, zeros elsewhere."""
    cfg, _ = setup
    (_, _, _, _, _, _, priv, S) = _paged_group(cfg, 3, diff_counts=[0, 2],
                                               seed=9)
    pk, pv, psrc, pmask = priv.materialize(S)
    L, P, bt, KV, hd = priv.pool_k.shape
    N, nbh = priv.page_idx.shape
    pool_k = np.asarray(priv.pool_k)
    for n in range(N):
        manual = pool_k[:, np.asarray(priv.page_idx)[n]].reshape(
            L, nbh * bt, KV, hd)[:, : priv.span_len]
        np.testing.assert_array_equal(
            np.asarray(pk)[n][:, : priv.span_len], manual)
        np.testing.assert_array_equal(
            np.asarray(pk)[n][:, priv.span_len : priv.span_len + priv.tail_len],
            np.asarray(priv.tail_k)[n])
    # zeros outside the private span
    assert not np.asarray(pk)[:, :, priv.span_len + priv.tail_len :].any()


# ------------------------------------------------------------- engine level
N_AGENTS = 3
N_ROUNDS = 3
GEN = 32


def _run_engine(cfg, params, *, paged, n_agents=N_AGENTS, n_rounds=N_ROUNDS,
                spy=None, paged_attention=True):
    trace = generate_trace("generative_agents", n_agents, n_rounds,
                           cfg.vocab_size, seed=11, jitter_hist=False)
    eng = MultiAgentEngine(params, cfg, "tokendance", gen_len=GEN,
                           recompute_ratio=0.1, keep_recovered=True,
                           paged_history=paged,
                           paged_attention=paged_attention)
    if spy is not None:
        orig = eng.collector.collective_reuse

        def wrapped(ids, tokens, ck, cv, src, mask, n_sel, priv=None, **kw):
            spy.append(type(priv).__name__)
            return orig(ids, tokens, ck, cv, src, mask, n_sel, priv, **kw)

        eng.collector.collective_reuse = wrapped
    return eng, eng.run_trace(trace)


@pytest.fixture(scope="module")
def engines(setup):
    cfg, params = setup
    seen = []
    eng_p, stats_p = _run_engine(cfg, params, paged=True, spy=seen)
    eng_d, stats_d = _run_engine(cfg, params, paged=False)
    return eng_p, stats_p, eng_d, stats_d, seen


def test_engine_paged_outputs_and_cache_bitexact(engines):
    """Same tokens AND the same recovered cache, bit-for-bit, when the
    collector consumes page_idx vs pre-densified mirrors."""
    eng_p, stats_p, eng_d, stats_d, _ = engines
    for r in range(N_ROUNDS):
        np.testing.assert_array_equal(stats_p[r].outputs, stats_d[r].outputs)
    kp, vp, _ = eng_p.last_recovered
    kd, vd, _ = eng_d.last_recovered
    np.testing.assert_array_equal(kp, kd)
    np.testing.assert_array_equal(vp, vd)


def test_engine_hands_collector_paged_private(engines):
    """The acceptance bar: no dense per-mirror cache before the collector.
    Every reuse round must hand the collector a PagedPrivate."""
    _, stats_p, _, _, seen = engines
    reuse_calls = [s for s in seen]
    assert "PagedPrivate" in reuse_calls, reuse_calls
    # one call per reuse round, all paged
    assert all(t == "PagedPrivate" for t in reuse_calls), reuse_calls
    for s in stats_p[1:]:
        assert s.reuse["restore"]["paged"] is True


def test_engine_accounts_shared_pages_once(engines):
    """Paged restore accounting: ONE family pool of nb + M*ndb_h pages,
    never more than the (M+1)*nb of per-member full writes (equality when
    the history span is fully private, the engine's common case — the
    Master's nb pages are still written and accounted once, not M+1
    times), and end-to-end bytes strictly below the dense oracle branch,
    which pays the same restore launch plus M+1 dense history copies.

    Round 1 is the pool-creating full restore; round 2 onward the
    default engine restores incrementally, so the counted write work
    (``pool_pages``) covers only the round delta while the prefix rides
    on ``pages_reused``."""
    _, stats_p, _, stats_d, _ = engines
    ri = stats_p[1].reuse["restore"]           # full restore creates the pool
    rd = stats_d[1].reuse["restore"]
    assert ri["incremental"] is False
    assert ri["pool_pages"] > 0
    assert ri["pool_pages"] <= ri["full_write_pages"]
    assert ri["pool_pages"] >= ri["nb"]   # master share counted once
    assert ri["bytes_materialized"] < rd["bytes_materialized"]
    inc = stats_p[-1].reuse["restore"]         # round 2: incremental delta
    assert inc["incremental"] is True
    assert inc["pool_pages"] > 0
    assert inc["pool_pages"] < inc["full_write_pages"]
    # every history block is accounted exactly once: either written this
    # round or carried over from the previous round's pool
    assert inc["pool_pages"] + inc["pages_reused"] >= inc["nb"]
    assert inc["bytes_materialized"] < stats_d[-1].reuse["restore"][
        "bytes_materialized"]


def test_engine_paged_attention_on_off_bitexact(setup, engines):
    """ISSUE 5 engine-level check: TokenDancePolicy outputs are unchanged
    with the paged attention fast path on vs off (the off leg keeps
    histories paged to the collector but densifies inside the jit)."""
    cfg, params = setup
    eng_on, stats_on, _, _, _ = engines   # paged_attention=True default
    eng_off, stats_off = _run_engine(cfg, params, paged=True,
                                     paged_attention=False)
    assert eng_on.policy.paged_attention is True
    assert eng_off.policy.paged_attention is False
    for r in range(N_ROUNDS):
        np.testing.assert_array_equal(stats_on[r].outputs,
                                      stats_off[r].outputs)
    np.testing.assert_array_equal(eng_on.last_recovered[0],
                                  eng_off.last_recovered[0])
    np.testing.assert_array_equal(eng_on.last_recovered[1],
                                  eng_off.last_recovered[1])


def test_engine_single_agent_paged(setup):
    """N=1: the master-only family takes the pool-from-Master branch."""
    cfg, params = setup
    _, stats = _run_engine(cfg, params, paged=True, n_agents=1, n_rounds=2)
    assert all(s.outputs is not None for s in stats)
    assert stats[1].reuse["restore"]["n_mirrors"] == 0
    assert stats[1].reuse["restore"]["paged"] is True


def test_engine_m1_family_paged_equals_dense(setup):
    """N=2 (M=1 family) paged == dense, outputs and caches."""
    cfg, params = setup
    eng_p, stats_p = _run_engine(cfg, params, paged=True, n_agents=2,
                                 n_rounds=2)
    eng_d, stats_d = _run_engine(cfg, params, paged=False, n_agents=2,
                                 n_rounds=2)
    for r in range(2):
        np.testing.assert_array_equal(stats_p[r].outputs, stats_d[r].outputs)
    np.testing.assert_array_equal(eng_p.last_recovered[0],
                                  eng_d.last_recovered[0])
    np.testing.assert_array_equal(eng_p.last_recovered[1],
                                  eng_d.last_recovered[1])


def test_paged_entry_materialize_roundtrip(setup):
    """PagedSegmentCacheEntry.materialize is the dense oracle: gathering
    an entry's pages reproduces the dense SegmentCacheEntry layout."""
    cfg, _ = setup
    rng = np.random.default_rng(3)
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.resolved_head_dim
    P, bt = 5, BT
    pool_k = jnp.asarray(rng.normal(size=(L, P, bt, KV, hd)), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(L, P, bt, KV, hd)), jnp.float32)
    tail_k = jnp.asarray(rng.normal(size=(L, bt, KV, hd)), jnp.float32)
    row = np.asarray([3, 1], np.int32)
    seq = 2 * bt - 5      # ragged span
    e = PagedSegmentCacheEntry(
        sid="s", pool_k=pool_k, pool_v=pool_v, page_idx=row,
        src_pos=np.arange(seq + bt, dtype=np.int32), seq_len=seq,
        block_tokens=bt, tail_k=tail_k, tail_v=tail_k)
    d = e.materialize()
    assert isinstance(d, SegmentCacheEntry)
    assert d.k.shape == (L, seq + bt, KV, hd)
    manual = np.asarray(pool_k)[:, row].reshape(L, 2 * bt, KV, hd)[:, :seq]
    np.testing.assert_array_equal(np.asarray(d.k)[:, :seq], manual)
    np.testing.assert_array_equal(np.asarray(d.k)[:, seq:],
                                  np.asarray(tail_k))
    # nbytes: page table + tail only — pool bytes belong to the family
    assert e.nbytes() == row.nbytes + 2 * tail_k.size * 4
