"""A new architecture joins the benchmark through new files alone: its
adapter and reference (``archs/``) are looked up by the configuration's
``architecture``; the replay runs layers of different kinds; the work
counts follow each layer's shape."""
import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import program, roofline, run
from bench.reference import qwen2, tokendance
from bench.tests.test_bench_rehearsal import _result, _run
from bench.tests.tiny import CONFIG, LIMITS, TRAFFIC, install
from bench.traffic.generator import generate_session

ARCHS = Path(__file__).resolve().parent / "archs"
CONFIGS = Path(__file__).resolve().parents[1] / "configs"
layered = program.load_module(ARCHS / "reference" / "layered.py",
                              "bench_test_layered")


# --------------------------------------------------------------------------
# a Qwen2 layer without QKV bias, served through the CPU rehearsal
# --------------------------------------------------------------------------
def test_architecture_without_bias_runs_from_new_files_only(tmp_path):
    root = install(tmp_path / "checkout")
    b = root / "bench"
    for kind in ("adapters", "reference"):
        assert not (b / kind / "qwen2_nobias.py").exists()
        shutil.copy(ARCHS / kind / "qwen2_nobias.py", b / kind)
    cfg = dict(CONFIG, name="tiny-nobias", architecture="qwen2_nobias",
               torch_dtype="float32")
    (b / "configs" / "tiny-nobias.json").write_text(json.dumps(cfg))
    (b / "limits" / "tiny-nobias.allgather.json").write_text(
        json.dumps(LIMITS))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-nobias", "source": "test",
                             "file": "bench/configs/tiny-nobias.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-nobias.allgather",
                               "config": "tiny-nobias",
                               "traffic": "tiny-allgather", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))

    out = _result(_run(root, "--workload", "tiny-nobias.allgather",
                       "--seed", str(2**31 + 11), "--seconds", "0.1",
                       "--trace", "0"))
    assert out["failed"] == 0 and out["attempted"] == TRAFFIC["agents"]
    assert out["correct"], out["checks"]
    assert out["checks"]["max_gap"]["value"] == 0.0


def test_an_architecture_without_adapter_names_the_missing_file(tmp_path):
    cfg = dict(CONFIG, architecture="no_such_arch")
    with pytest.raises(FileNotFoundError, match="no_such_arch.py"):
        program.program_config(cfg, tmp_path)
    assert program.program_config(dict(CONFIG)).attn_bias
    assert not program.program_config(
        dict(CONFIG, architecture="qwen2_nobias"),
        ARCHS / "adapters").attn_bias


def test_the_lookups_take_their_directories(tmp_path):
    root = install(tmp_path / "checkout")
    (root / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(dict(CONFIG, architecture="qwen2_nobias")))
    old = run.ROOT, run.BENCH
    run.ROOT, run.BENCH = root, root / "bench"
    try:
        cell = run.load_cell("tiny.allgather", ARCHS / "reference",
                             ARCHS / "adapters")
    finally:
        run.ROOT, run.BENCH = old
    assert cell.reference.__name__ == "bench_reference_qwen2_nobias"
    assert cell.adapters == ARCHS / "adapters"


# --------------------------------------------------------------------------
# the replay over layers of different kinds
# --------------------------------------------------------------------------
LAYERED_TRAFFIC = {
    "agents": 2, "rounds_per_session": 3, "memory_round": 2,
    "history": {"base": 8, "jitter": 8}, "length_seed": 0,
    "task_len": 16, "gen_len": 32, "recompute_ratio": 1.0,
    "block_tokens": 32, "topology": {"kind": "all_gather"},
    "sample_requests": 2,
}


def _keys_at(weights, tokens, pos):
    """Every layer's keys [L, T, KV, hd] of ``tokens`` at ``pos``."""
    h, ks = layered.embed(weights, tokens), []
    for li, p in enumerate(layered.blocks(weights)):
        q, k, v = layered.qkv(p, h, pos, None, li)
        h = layered.finish(p, h, layered.attend(q, pos, k, v, pos, None, li),
                           None, li)
        ks.append(k)
    return jnp.stack(ks)


def test_layered_replay_with_every_block_recomputed_is_the_plain_forward():
    cfg = {"vocab_size": layered.V}
    weights = layered.make_weights(3, cfg)
    sess = generate_session(LAYERED_TRAFFIC, layered.V, 5, 0)
    rng = np.random.default_rng(1)
    G, n = LAYERED_TRAFFIC["gen_len"], LAYERED_TRAFFIC["agents"]
    served = {(r, i): rng.integers(0, layered.V - 1, G).astype(np.int32)
              for r in range(3) for i in range(n)}
    prompts, logits, _ = tokendance.replay(
        layered, weights, cfg, LAYERED_TRAFFIC, sess, served, 2, set(served))
    assert len(prompts[(2, 0)]) > 4 * layered.WINDOW   # the window bites
    for key, lg in logits.items():
        seq = np.concatenate([prompts[key], served[key][:-1]])
        want = np.asarray(layered.forward(weights, jnp.asarray(seq)))
        np.testing.assert_allclose(lg, want[len(prompts[key]) - 1:],
                                   rtol=1e-4, atol=1e-4)


def test_layered_cached_keys_move_by_each_layers_own_rope():
    weights = layered.make_weights(4, {})
    T, bt, shift = 96, 32, 37
    tokens = jax.random.randint(jax.random.PRNGKey(0), (T,), 0, layered.V - 1)
    pos = jnp.arange(T)
    cached = jnp.arange(T) < T - bt
    d_items = tuple(sorted(layered.dims({}).items()))
    moved = tokendance._check(
        layered, weights, tokens, _keys_at(weights, tokens, pos + shift),
        pos + shift, cached, bt=bt, d_items=d_items, quant=None)[-1]
    np.testing.assert_allclose(moved, _keys_at(weights, tokens, pos),
                               rtol=1e-4, atol=1e-4)
    # the two thetas differ, so one theta for every layer would not do
    one = jax.vmap(lambda k: qwen2.rope(k, -shift * jnp.ones(T, jnp.int32),
                                        layered.THETA["full"]))(
        _keys_at(weights, tokens, pos + shift))
    assert not np.allclose(one[1], moved[1], atol=1e-3)


# --------------------------------------------------------------------------
# work counts by layer
# --------------------------------------------------------------------------
def test_layered_counts_by_hand():
    d = layered.dims({})
    D, V = 64, 256
    attn = 64 * 64 + 64 * 2 * 2 * 16 + 64 * 64          # wq, wk+wv, wo
    assert attn == 12_288
    dense0 = attn + 3 * 64 * 128                          # dense SwiGLU
    dense_moe = attn + 3 * 64 * 32 + 64 * 4               # shared + router
    expert = 3 * 64 * 32
    assert (dense0, dense_moe, expert) == (36_864, 18_688, 6_144)
    pair = 2 * 4 * (16 + 16)          # score + value products a (q, k) pair

    # one decode step, 2 agents, the new token at position 99: layer 0 and 2
    # see 100 keys, the sliding layer 32; each routed layer computes top-2
    # of 4 experts for 2 tokens (4 expert-token pairs) and reads 2 experts
    flops = (2 * 2 * D * V
             + 2 * 2 * dense0 + 2 * pair * 100
             + 2 * 2 * dense_moe + 2 * 4 * expert + 2 * pair * 32
             + 2 * 2 * dense_moe + 2 * 4 * expert + 2 * pair * 100)
    weights = D * V + dense0 + 2 * (dense_moe + 2 * expert)
    kv_row = 2 * 16 * 2 * 2                               # K+V, bf16
    nbytes = 2 * weights + 2 * (101 + 33 + 101) * kv_row
    assert (flops, nbytes) == (579_584, 290_560)
    assert roofline.decode_step_cost(d, 2, 100) == (flops, nbytes)
    # the program reports 4 and 3 experts read in the routed layers
    assert roofline.decode_step_cost(d, 2, 100, [0, 4, 3]) == \
        (flops, nbytes + 2 * 3 * expert)
    assert flops < roofline.decode_step_cost(
        dict(d, layers=tuple(lay._replace(top_k=4) for lay in d["layers"])),
        2, 100)[0]                                # every expert: more

    # a 96-token prefill of 2 agents: the sliding layer's queries see
    # 1..32 keys, then 32 each
    causal, sliding = 96 * 97 // 2, 32 * 33 // 2 + 64 * 32
    tokens = 2 * 96
    flops = (2 * 2 * D * V
             + 2 * tokens * dense0 + 2 * pair * causal
             + 2 * tokens * dense_moe + 2 * tokens * 2 * expert
             + 2 * pair * sliding
             + 2 * tokens * dense_moe + 2 * tokens * 2 * expert
             + 2 * pair * causal)
    assert roofline.prefill_flops(d, 2, 96) == flops == 44_097_536

    # recovery of the same prompt with 64 positions selected: layers 0-1
    # over all 96, layer 2 over the last block (keys 65..96) and the first
    # 32 positions (keys 1..32)
    sel = (65 + 96) * 32 // 2 + 32 * 33 // 2
    flops = (2 * 2 * D * V
             + 2 * tokens * dense0 + 2 * pair * causal
             + 2 * tokens * dense_moe + 2 * tokens * 2 * expert
             + 2 * pair * sliding
             + 2 * 128 * dense_moe + 2 * 128 * 2 * expert + 2 * pair * sel)
    assert roofline.recovery_flops(d, 2, 96, 64, 32) == flops == 39_337_984

    # a chip that holds 2 of the 4 experts computes half of the top-2
    share = dict(d, layers=tuple(lay._replace(experts=2) if lay.experts
                                 else lay for lay in d["layers"]))
    f_all, b_all = roofline.decode_step_cost(d, 2, 100)
    f_half, b_half = roofline.decode_step_cost(share, 2, 100)
    assert f_all - f_half == 2 * (2 * 2 * expert)
    assert b_half == b_all              # the top-2 floor, 2 experts held


# a frozen copy of the counts as they were before they went by layer
def _old_layer(d):
    D, H, KV, hd, F = d["D"], d["H"], d["KV"], d["hd"], d["F"]
    return D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F


def _old_attn(d, layers, q_keys):
    return 2 * 2 * layers * d["H"] * d["hd"] * q_keys


def _old_prefill(d, N, S):
    causal = S * (S + 1) // 2
    return (2 * N * S * d["L"] * _old_layer(d)
            + N * _old_attn(d, d["L"], causal) + 2 * N * d["D"] * d["V"])


def _old_recovery(d, N, S, n_sel, bt):
    c, rest = 2, d["L"] - 2
    causal = S * (S + 1) // 2
    low = n_sel - bt
    sel_keys = bt * S - bt * (bt - 1) // 2 + low * (low + 1) // 2
    return (2 * N * S * c * _old_layer(d) + N * _old_attn(d, c, causal)
            + 2 * N * n_sel * rest * _old_layer(d)
            + N * _old_attn(d, rest, sel_keys) + 2 * N * d["D"] * d["V"])


def _old_decode_step(d, N, length):
    w = d["L"] * _old_layer(d) + d["D"] * d["V"]
    kv_tok = 2 * d["L"] * d["KV"] * d["hd"] * 2
    return (2 * N * w + N * _old_attn(d, d["L"], length),
            w * 2 + N * length * kv_tok + N * kv_tok)


def _cell():
    cfg = json.loads((CONFIGS / "qwen2.5-7b.json").read_text())
    traffic = json.loads((Path(__file__).resolve().parents[1] / "traffic"
                          / "ga16-allgather.json").read_text())
    return qwen2.dims(cfg), traffic


@pytest.mark.parametrize("S", range(704, 1057, 32))
def test_qwen2_counts_are_todays_over_the_cells_shapes(S):
    d, tr = _cell()
    bt, G = tr["block_tokens"], tr["gen_len"]
    assert roofline.prefill_flops(d, 16, S) == _old_prefill(d, 16, S)
    n_sel = roofline.n_sel_for_blocks(S // bt, 1, tr["recompute_ratio"], bt)
    assert roofline.recovery_flops(d, 16, S, n_sel, bt) == \
        _old_recovery(d, 16, S, n_sel, bt)
    for t in range(G):
        assert roofline.decode_step_cost(d, 16, S + t) == \
            _old_decode_step(d, 16, S + t)


def test_mfu_and_decode_roofline_read_todays_counts():
    from types import SimpleNamespace

    from bench.trace import Reduced
    d, tr = _cell()
    batches = [SimpleNamespace(n=16, prompt_len=S, kind=k, outputs=0)
               for S, k in ((128, "recompute"), (704, "reuse"),
                            (1056, "reuse"))]
    view = SimpleNamespace(
        dims=d, traffic=tr, window=(0.0, 30.0), device_kind="TPU v5 lite",
        window_batches=lambda: batches,
        reduced=Reduced(window=(0, 30e9), busy=[[(0, 10e9)]],
                        spans=[("decode", 0, 30e9)]))
    pk = roofline.peaks("TPU v5 lite")
    G, bt = tr["gen_len"], tr["block_tokens"]
    flops, least = 0, 0.0
    for b in batches:
        if b.kind == "recompute":
            flops += _old_prefill(d, 16, b.prompt_len)
        else:
            n_sel = roofline.n_sel_for_blocks(b.prompt_len // bt, 1, 0.15, bt)
            flops += _old_recovery(d, 16, b.prompt_len, n_sel, bt)
        for t in range(1, G):
            f, by = _old_decode_step(d, 16, b.prompt_len + t)
            flops += f
            least += max(f / pk["bf16_flops"], by / pk["hbm_bytes_per_s"])
    metrics = Path(__file__).resolve().parents[1] / "metrics"
    read = {m: program.load_module(metrics / f"{m}.py", "t_" + m).read
            for m in ("mfu", "decode_step_roofline")}
    assert read["mfu"](view) == flops / (30.0 * pk["bf16_flops"]) * 100.0
    assert read["decode_step_roofline"](view) == least / 10.0 * 100.0
