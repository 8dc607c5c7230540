"""Chip smoke run: the TokenDance serving path once, on one TPU chip.

    python chip_smoke.py [--seed 0]

1. Requires a TPU: with no chip it exits non-zero before any work (no CPU
   fallback) and prints no result line.
2. Runs each Pallas kernel whose TPU layout had to be repaired once, at
   qwen2.5-7b widths (KV 4 x hd 128, 28 query heads, 32-token pages,
   bf16), against its jnp oracle in ``repro.kernels.ref``.
3. Builds qwen2.5-7b at its published widths in bf16, cut to 16 of its 28
   layers (the rest stand for further pipeline stages), with random
   weights drawn from ``--seed``, and serves a 4-round, 8-agent
   generative-agents trace through ``ServingEngine`` + ``TokenDancePolicy``
   with default knobs: round 0 recomputes, round 1 restores the Master-
   Mirror family in full and runs collective recovery, rounds 2-3 restore
   incrementally, and every round decodes over pool pages and stores its
   diffs.
4. Fails if a round served fewer than 8 agents, if outputs are not
   ``[8, 32]`` token ids inside the vocabulary, if first-token logits are
   not finite, or if a default path fell back to its twin (dense decode,
   densified collector history, full restore after round 1), as recorded
   in each round's reuse ledger.

Nothing is caught: any failure exits non-zero. The last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The times printed are one cold run's host-clock phase times, not a
benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np

MODEL = "qwen2.5-7b"
N_LAYERS = 16            # of 28: 9.0 GiB of bf16 weights on a 16 GB chip
N_AGENTS = 8
N_ROUNDS = 4
GEN_LEN = 32             # ServingEngine's default gen_len
BT = 32                  # page / block tokens (the engine's block_select)
#: kernel outputs are bf16: two roundings of 2^-8 each plus the MXU's
#: decomposition of f32 matmuls stay far below this share of the output's
#: largest magnitude, while a wrong head, page, mask or angle is O(1)
KERNEL_TOL = 2e-2


def require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def _max_rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    require(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    require(bool(np.isfinite(got).all()), "non-finite kernel output")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def check_kernels(cfg, seed: int) -> None:
    """Each repaired kernel once at ``cfg``'s widths against its oracle."""
    from repro.kernels import ops, ref

    KV, hd, H = cfg.n_kv_heads, cfg.resolved_head_dim, cfg.n_heads
    L, theta, dt = cfg.n_layers, cfg.rope_theta, jnp.dtype(cfg.dtype)
    nb, ndb, M = 16, 5, N_AGENTS - 1        # 512-token span, 7 mirrors
    S = nb * BT
    rng = np.random.default_rng(seed)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def rand(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dt)

    def i32(x):
        return jnp.asarray(np.asarray(x, np.int32))

    def report(name, err, tol=KERNEL_TOL):
        print(f"kernel {name}: max|kernel-oracle|/max|oracle| = {err:.3e} "
              f"(tol {tol:g})", flush=True)
        require(err <= tol, f"kernel {name} disagrees with its oracle")

    # collective RoPE alignment
    k = rand(S, KV, hd)
    src = i32(rng.integers(0, 4096, S))
    tgt = i32(rng.integers(0, 4096, S))
    report("rope_align", _max_rel_err(
        ops.rope_align(k, src, tgt, theta),
        ref.rope_align_ref(k, src, tgt, theta)))

    # block diff: a max of |differences| is exact
    master = rand(L, S, KV, hd)
    mirror = master.at[L - 1, 5].add(jnp.asarray(0.5, dt)).at[0, S - 1].add(
        jnp.asarray(0.25, dt))
    got = ops.block_diff(master, mirror, BT)
    want = ref.block_diff_ref(master, mirror, BT)
    report("block_diff", _max_rel_err(got, want), tol=0.0)
    require(int((np.asarray(got) > 0).sum()) == 2, "block_diff mask")

    # fused diff restore, one mirror and a whole family
    mk, mv = rand(L, nb, BT, KV, hd), rand(L, nb, BT, KV, hd)
    delta = i32(rng.integers(0, 1024, (nb, BT)))

    def slots(n):
        s = np.full(nb, -1, np.int32)
        s[rng.choice(nb, n, replace=False)] = np.arange(n)
        return s

    dk, dv = rand(L, ndb, BT, KV, hd), rand(L, ndb, BT, KV, hd)
    pk = jnp.zeros((L, nb + 2, BT, KV, hd), dt)
    args = (mk, mv, dk, dv, i32(slots(ndb)),
            i32(rng.permutation(nb + 2)[:nb]), delta, theta, pk, pk)
    got, want = ops.fused_diff_restore(*args), ref.fused_diff_restore_ref(*args)
    report("fused_diff_restore[k]", _max_rel_err(got[0], want[0]))
    report("fused_diff_restore[v]", _max_rel_err(got[1], want[1]), tol=0.0)

    fdk, fdv = rand(M, L, ndb, BT, KV, hd), rand(M, L, ndb, BT, KV, hd)
    pk = jnp.zeros((L, M * nb, BT, KV, hd), dt)
    args = (mk, mv, fdk, fdv, i32(np.stack([slots(ndb) for _ in range(M)])),
            i32(rng.permutation(M * nb).reshape(M, nb)),
            i32(rng.integers(0, 1024, (M, nb, BT))), theta, pk, pk)
    got = ops.fused_family_restore(*args)
    want = ref.fused_family_restore_ref(*args)
    report("fused_family_restore[k]", _max_rel_err(got[0], want[0]))
    report("fused_family_restore[v]", _max_rel_err(got[1], want[1]), tol=0.0)

    # paged prefill / decode attention over a shuffled page table, with a
    # ragged dense tail
    P, T = nb + 4, 20
    pool_k, pool_v = rand(P, BT, KV, hd), rand(P, BT, KV, hd)
    pidx = i32(rng.permutation(P)[:nb])
    tail_k, tail_v = rand(T, KV, hd), rand(T, KV, hd)
    q = rand(H, S + T, hd)
    with jax.default_matmul_precision("highest"):
        want_p = ref.flash_attention_paged_ref(
            q, pool_k, pool_v, pidx, tail_k, tail_v, span_len=S)
        want_d = ref.flash_decode_paged_ref(
            q[:, -1:], pool_k, pool_v, pidx, tail_k, tail_v, span_len=S)
    report("flash_prefill_paged", _max_rel_err(ops.flash_prefill_paged(
        q, pool_k, pool_v, pidx, tail_k, tail_v, span_len=S), want_p))
    report("flash_decode_paged", _max_rel_err(ops.flash_decode_paged(
        q[:, -1:], pool_k, pool_v, pidx, tail_k, tail_v, span_len=S), want_d))


def _entries(stats, key: str) -> list:
    """A reuse-ledger entry as a list (one item per gather batch)."""
    v = stats.reuse.get(key)
    if v is None:
        return []
    return v if isinstance(v, list) else [v]


def serve_and_check(cfg, params, seed: int) -> list:
    """Serve the trace through the default TokenDance path; check every
    round's outputs and that no default path fell back to its twin."""
    from repro.core.rounds import generate_trace
    from repro.serving import ServingEngine, TokenDancePolicy

    trace = generate_trace("generative_agents", N_AGENTS, N_ROUNDS,
                           cfg.vocab_size, seed=seed)
    eng = ServingEngine(params, cfg, TokenDancePolicy(), keep_logits=True)
    compile_s = defaultdict(float)

    def on_event(event, secs, **_):
        if event.startswith("/jax/core/compile/"):
            compile_s[eng.round_idx] += secs

    jax.monitoring.register_event_duration_secs_listener(on_event)
    t0 = time.perf_counter()
    rounds = eng.serve(trace)
    wall = time.perf_counter() - t0
    require(len(rounds) == N_ROUNDS, f"served {len(rounds)} rounds")
    for s in rounds:
        r = s.round_idx
        comp = _entries(s, "compression")
        ratio = comp[0]["compression_ratio"] if comp else float("nan")
        print(f"round {r}: agents={s.n_agents} S={s.prompt_len} "
              f"t_recover={s.t_recover:.4f}s t_restore={s.t_restore:.4f}s "
              f"t_decode={s.t_decode:.4f}s t_store={s.t_store:.4f}s "
              f"compression_ratio={ratio:.3f} "
              f"compile_s={compile_s[r]:.1f}", flush=True)
        require(s.n_agents == N_AGENTS, f"round {r} served {s.n_agents}")
        out = np.asarray(s.outputs)
        require(out.shape == (N_AGENTS, GEN_LEN),
                f"round {r} outputs shaped {out.shape}")
        require(bool(((out >= 0) & (out < cfg.vocab_size)).all()),
                f"round {r} emitted a token outside the vocabulary")
        lg = np.asarray(s.first_logits, np.float32)
        require(lg.shape == (N_AGENTS, cfg.vocab_size),
                f"round {r} first logits shaped {lg.shape}")
        require(bool(np.isfinite(lg).all()),
                f"round {r} first-token logits not finite")
        dec = _entries(s, "decode")
        require(bool(dec) and all(d["paged"] for d in dec),
                f"round {r} decoded dense: {dec}")
        if r >= 1:
            modes = _entries(s, "priv_mode")
            require(bool(modes) and all(m == "paged" for m in modes),
                    f"round {r} collector history mode {modes}")
            rest = _entries(s, "restore")
            require(bool(rest) and all(x["paged"] for x in rest),
                    f"round {r} restore {rest}")
            if r >= 2:
                require(all(x["incremental"] for x in rest),
                        f"round {r} fell back to a full restore")
    print(f"served {N_ROUNDS} rounds x {N_AGENTS} agents in {wall:.1f}s "
          f"(compile {sum(compile_s.values()):.1f}s)", flush=True)
    return rounds


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        sys.exit(1)
    print(f"device: kind={dev.device_kind} count={len(devices)} "
          f"jax={jax.__version__}", flush=True)

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import init_params

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    full = get_config(MODEL)
    cfg = full.replace(n_layers=N_LAYERS)
    check_kernels(cfg, args.seed)

    t0 = time.perf_counter()
    params = jax.block_until_ready(
        init_params(jax.random.PRNGKey(args.seed), cfg))
    gib = sum(x.nbytes for x in jax.tree.leaves(params)) / 2**30
    print(f"model: {MODEL} ({full.source}) {N_LAYERS} of {full.n_layers} "
          f"layers, d_model={cfg.d_model} heads={cfg.n_heads}/"
          f"{cfg.n_kv_heads}x{cfg.resolved_head_dim} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} {cfg.dtype}; weights {gib:.3f} GiB "
          f"(seed {args.seed}, init {time.perf_counter() - t0:.1f}s)",
          flush=True)
    mem = dev.memory_stats()
    print(f"after init: bytes_in_use={mem['bytes_in_use']} "
          f"peak_bytes_in_use={mem['peak_bytes_in_use']}", flush=True)

    serve_and_check(cfg, params, args.seed)
    mem = dev.memory_stats()
    print(f"after serving: bytes_in_use={mem['bytes_in_use']} "
          f"peak_bytes_in_use={mem['peak_bytes_in_use']} "
          f"({mem['peak_bytes_in_use'] / 2**30:.3f} GiB)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
