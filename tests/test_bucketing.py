"""Bucketed round programs: recovery and decode at a padded prompt length.

The serving path runs a prompt of S tokens at ``bucket_len(S)`` (S rounded
up to ``BUCKET_BLOCKS`` KV blocks), with the real length and selection
budget as operands. Pinned here against the unpadded programs (float32,
tiny config): the recomputed positions (trimmed to the real budget), the
last-token logits and the recovered KV at ``[:S]`` agree to 1e-5, for
several S inside one bucket and for dense and paged private histories;
and a paged decode at the padded total emits the same greedy tokens as
at the exact one.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.collector import KVCollector, PagedPrivate
from repro.core.pic import BUCKET_BLOCKS, bucket_len, n_sel_for_blocks
from repro.models import decode_step_paged, init_params
from repro.serving import ServingEngine
from repro.serving.trace import clear_programs

BT = 32
N = 3
RATIO = 0.15
TOL = 1e-5


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen2.5-7b").replace(dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_bucket_len_rounds_up_to_whole_buckets():
    unit = BUCKET_BLOCKS * BT
    assert [bucket_len(s, BT) for s in (BT, unit - BT, unit, unit + BT)] \
        == [unit, unit, unit, 2 * unit]
    assert bucket_len(672, 32) == bucket_len(768, 32) == 768
    assert bucket_len(800, 32) == bucket_len(928, 32) == 1024
    assert bucket_len(100, 0) == 100      # token-level selection: exact


def test_padded_budget_bounds_the_bucket():
    """The padded budget is one number for every prompt of a bucket with
    the same fresh blocks, and never below the real one."""
    Sp = 8 * BT
    budgets = set()
    for nb in range(2, 9):
        fresh = np.zeros(nb * BT, bool)
        fresh[-BT:] = True                  # the task block
        n_real = n_sel_for_blocks(fresh, BT, RATIO)
        n_pad = n_sel_for_blocks(fresh, BT, RATIO, length=Sp)
        assert n_real <= n_pad
        budgets.add(n_pad)
    assert len(budgets) == 1


def _group(cfg, nb, seed, hist=2):
    """An exact-length group of ``nb`` blocks: [private history (``hist``
    blocks, paged over a shuffled pool) | shared cached | fresh task
    block]."""
    rng = np.random.default_rng(seed)
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.resolved_head_dim
    S = nb * BT
    span = hist * BT
    tokens = rng.integers(0, cfg.vocab_size - 1, (N, S)).astype(np.int32)
    sk = np.zeros((L, S, KV, hd), np.float32)
    sv = np.zeros_like(sk)
    smask = np.zeros(S, bool)
    smask[span : S - BT] = True
    sk[:, smask] = rng.normal(size=(L, int(smask.sum()), KV, hd))
    sv[:, smask] = rng.normal(size=(L, int(smask.sum()), KV, hd))
    src = np.arange(S, dtype=np.int32)
    src[smask] = np.arange(int(smask.sum()))     # cached at other positions
    pmask = np.zeros(S, bool)
    pmask[:span] = True
    # private histories: N * hist pages of a pool of a bucket's pages
    P = BUCKET_BLOCKS * N
    pool_k = rng.normal(size=(L, P, BT, KV, hd)).astype(np.float32)
    pool_v = rng.normal(size=(L, P, BT, KV, hd)).astype(np.float32)
    rows = rng.permutation(P)[: hist * N].reshape(N, hist).astype(np.int32)
    pk = np.zeros((N, L, S, KV, hd), np.float32)
    pv = np.zeros_like(pk)
    pk[:, :, :span] = pool_k[:, rows].reshape(L, N, span, KV, hd) \
        .transpose(1, 0, 2, 3, 4)
    pv[:, :, :span] = pool_v[:, rows].reshape(L, N, span, KV, hd) \
        .transpose(1, 0, 2, 3, 4)
    psrc = np.broadcast_to(src, (N, S)).copy()
    psrc[:, :span] = np.arange(span)
    fresh = ~(smask | pmask)
    return dict(tokens=tokens, sk=sk, sv=sv, src=src, smask=smask,
                pmask=pmask, pool_k=pool_k, pool_v=pool_v, rows=rows, pk=pk,
                pv=pv, psrc=psrc, span=span, S=S,
                n_sel=n_sel_for_blocks(fresh, BT, RATIO))


def _pad(g, Sp):
    """The group right-padded to ``Sp`` as the serving path pads it."""
    S = g["S"]

    def tail(a, axis, fill=0):
        w = [(0, 0)] * a.ndim
        w[axis] = (0, Sp - S)
        return np.pad(a, w, constant_values=fill)

    src = np.arange(Sp, dtype=np.int32)
    src[:S] = g["src"]
    psrc = np.broadcast_to(src, (N, Sp)).copy()
    psrc[:, :S] = g["psrc"]
    # page tables with the columns a history could fill in the bucket
    rows = np.zeros((N, (Sp - S + g["span"]) // BT), np.int32)
    rows[:, : g["rows"].shape[1]] = g["rows"]
    return dict(g, tokens=tail(g["tokens"], 1), sk=tail(g["sk"], 1),
                sv=tail(g["sv"], 1), src=src, smask=tail(g["smask"], 0),
                pmask=tail(g["pmask"], 0), pk=tail(g["pk"], 2),
                pv=tail(g["pv"], 2), psrc=psrc, rows=rows)


def _priv(g, form):
    if form == "dense":
        return (jnp.asarray(g["pk"]), jnp.asarray(g["pv"]),
                jnp.asarray(g["psrc"]), jnp.asarray(g["pmask"]))
    return PagedPrivate(
        pool_k=jnp.asarray(g["pool_k"]), pool_v=jnp.asarray(g["pool_v"]),
        page_idx=jnp.asarray(g["rows"]), src=jnp.asarray(g["psrc"]),
        mask=jnp.asarray(g["pmask"]), start=0, span_len=g["span"])


def _recover(col, g, form, **kw):
    res = col.collective_reuse(
        [f"a{i}" for i in range(N)], jnp.asarray(g["tokens"]),
        jnp.asarray(g["sk"]), jnp.asarray(g["sv"]), jnp.asarray(g["src"]),
        jnp.asarray(g["smask"]), g["n_sel"], _priv(g, form), **kw)
    assert res.priv_mode == ("dense" if form == "dense" else "paged")
    return res


@pytest.mark.parametrize("form", ["dense", "paged"])
@pytest.mark.parametrize("nb", [5, 6, 8])
def test_bucketed_recovery_matches_exact(setup, form, nb):
    """Prompts of 5, 6 and 8 blocks run in the 8-block bucket."""
    cfg, params = setup
    col = KVCollector(params, cfg, recompute_ratio=RATIO, block_select=BT)
    g = _group(cfg, nb, seed=nb)
    S = g["S"]
    Sp = bucket_len(S, BT)
    assert Sp == 8 * BT
    fresh = ~(g["smask"] | g["pmask"])
    n_pad = n_sel_for_blocks(fresh, BT, RATIO, length=Sp)
    exact = _recover(col, g, form)
    pad = _recover(col, _pad(g, Sp), form, length=S, n_sel_padded=n_pad)
    assert pad.pic.sel_idx.shape == (N, n_pad)
    assert pad.pic.recovered_k.shape[2] == Sp
    np.testing.assert_array_equal(pad.plan.sel_idx_all,
                                  exact.plan.sel_idx_all)
    assert pad.plan.master == exact.plan.master
    np.testing.assert_allclose(np.asarray(pad.pic.logits),
                               np.asarray(exact.pic.logits),
                               rtol=TOL, atol=TOL)
    for name in ("recovered_k", "recovered_v"):
        np.testing.assert_allclose(
            np.asarray(getattr(pad.pic, name))[:, :, :S],
            np.asarray(getattr(exact.pic, name)), rtol=TOL, atol=TOL,
            err_msg=name)
    # the padding is left as the (zero) base: nothing was recomputed there
    assert not np.asarray(pad.pic.recovered_k)[:, :, S:].any()


def test_one_recovery_program_per_bucket(setup):
    """Prompts of one bucket whose history grows by a block a round (the
    other tokens staying put) run the same program."""
    cfg, params = setup
    clear_programs()
    col = KVCollector(params, cfg, recompute_ratio=RATIO, block_select=BT)
    for nb in (5, 6, 7):
        g = _group(cfg, nb, seed=nb, hist=nb - 3)
        Sp = bucket_len(g["S"], BT)
        fresh = ~(g["smask"] | g["pmask"])
        n_pad = n_sel_for_blocks(fresh, BT, RATIO, length=Sp)
        _recover(col, _pad(g, Sp), "paged", length=g["S"], n_sel_padded=n_pad)
    assert col.programs.take_new_programs() == {"collective_recover": 1}


def _paged_cache(k, v, S, total, bt):
    """A paged decode cache of ``total`` positions over a prefill of S."""
    L, Nn, Sk, KV, hd = k.shape
    nbt = total // bt

    def to_pool(x):
        x = x.reshape(L, Nn, Sk // bt, bt, KV, hd)
        x = jnp.pad(x, ((0, 0), (0, 0), (0, nbt - Sk // bt), (0, 0), (0, 0),
                        (0, 0)))
        return x.reshape(L, Nn * nbt, bt, KV, hd)
    pos = np.zeros((Nn, total), np.int32)
    pos[:, :S] = np.arange(S)
    valid = np.zeros((Nn, total), bool)
    valid[:, :S] = True
    return {"pk": to_pool(k), "pv": to_pool(v),
            "page_idx": jnp.arange(Nn * nbt, dtype=jnp.int32).reshape(Nn, nbt),
            "kv_pos": jnp.asarray(pos), "kv_valid": jnp.asarray(valid),
            "length": jnp.full((Nn,), S, jnp.int32)}


def test_bucketed_paged_decode_matches_exact(setup):
    """A paged decode at the bucketed total against the exact S+G one:
    the same greedy tokens, logits within 1e-5."""
    cfg, params = setup
    G = 2 * BT
    S = 5 * BT
    eng = ServingEngine(params, cfg, gen_len=G, block_select=BT)
    rng = np.random.default_rng(0)
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.resolved_head_dim
    k = jnp.asarray(rng.normal(size=(L, N, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(L, N, S, KV, hd)), jnp.float32)
    first = jnp.asarray(rng.normal(size=(N, cfg.vocab_size)), jnp.float32)
    st = eng._decode_begin(first, {"k": k, "v": v}, N, S, [], True, "g0", 0)
    assert st.cache["page_idx"].shape[1] * BT == bucket_len(S, BT) + G
    exact = _paged_cache(k, v, S, S + G, BT)
    tok = jnp.argmax(first, axis=-1).astype(jnp.int32)
    for _ in range(G - 1):
        lg, exact = decode_step_paged(params, cfg, tok, exact)
        lp, st.cache = decode_step_paged(params, cfg, st.tok, st.cache)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lg),
                                   rtol=TOL, atol=TOL)
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        st.tok = jnp.argmax(lp, axis=-1).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(st.tok), np.asarray(tok))


def test_diff_rows_gather_from_the_bucket():
    """A round family's diff rows are taken from its caches padded to the
    bucket, so prompts of one bucket with the same diff count share one
    gather program, and the stored rows are the mirror's own blocks."""
    from repro.core.diff_store import build_round_family, take_blocks

    L, KV, hd = 2, 2, 8
    built = []
    for S in (5 * BT, 6 * BT + 7, 8 * BT):
        ks = jax.random.normal(jax.random.PRNGKey(S), (N, L, S, KV, hd))
        ks = ks.at[1:].set(ks[0]).at[1:, :, :BT].add(1.0)   # block 0 differs
        before = take_blocks._cache_size()
        master, handles = build_round_family(
            [f"r{i}" for i in range(N)], ks, ks, np.arange(S), master_idx=0)
        built.append(take_blocks._cache_size() - before)
        for i, h in enumerate(handles, start=1):
            np.testing.assert_array_equal(h.diff.block_idx, [0])
            np.testing.assert_array_equal(h.diff.k_vals[:, 0], ks[i][:, :BT])
            assert h.diff.seq_len == S
    assert built[1:] == [0, 0], built
