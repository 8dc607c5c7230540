"""Walkthrough of the paper's storage pipeline on one All-Gather round:
collective recovery -> reuse plan -> Master-Mirror block-sparse diffs ->
fused restore, with exactness checks at every step.

  PYTHONPATH=src python examples/compression_demo.py [--agents 6]
"""
import argparse

import jax.numpy as jnp
import numpy as np

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.common import make_group, model  # noqa: E402
from repro.core.collector import KVCollector
from repro.core.diff_store import build_round_family, compression_stats
from repro.core.restore import dense_restore, fused_restore_paged, dense_restore_paged
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--agents", type=int, default=6)
    args = ap.parse_args()
    enable_compile_cache()

    cfg, params = model("qwen2.5-7b")
    g = make_group(cfg, params, args.agents, priv_len=32, block_len=128,
                   ratio=0.05, seed=1)
    ids = [f"agent{i}" for i in range(args.agents)]
    print(f"round: {args.agents} agents, prompt {g.S} tokens "
          f"({int(np.asarray(g.mask).sum())} shared), n_sel={g.n_sel}")

    coll = KVCollector(params, cfg, block_select=32, recompute_ratio=0.05)
    res = coll.collective_reuse(ids, g.tokens, g.shared_k, g.shared_v,
                                g.src, g.mask, g.n_sel)
    print(f"reuse plan: master={ids[res.plan.master]} "
          f"deviations={res.plan.deviations.round(1)}")

    ks = jnp.swapaxes(res.pic.recovered_k, 0, 1)
    vs = jnp.swapaxes(res.pic.recovered_v, 0, 1)
    master, handles = build_round_family(ids, ks, vs, np.arange(g.S),
                                         res.plan.master)
    st = compression_stats(master, handles)
    print(f"diff store: mirror={st['per_mirror_ratio']:.1f}x "
          f"({st['avg_changed_blocks']:.1f}/{st['total_blocks']} blocks), "
          f"family {st['compression_ratio']:.1f}x")

    # restore exactness: Master + diff must reproduce each Mirror bitwise
    mirrors = [i for i in range(args.agents) if i != res.plan.master]
    h = handles[0]
    rk, rv = dense_restore(h, cfg.rope_theta)
    assert jnp.array_equal(rk, ks[mirrors[0]])
    assert jnp.array_equal(rv, vs[mirrors[0]])
    print("dense restore: exact")

    nb = -(-g.S // 32)
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.resolved_head_dim
    pool_k = jnp.zeros((L, nb, 32, KV, hd))
    slot = jnp.arange(nb, dtype=jnp.int32)
    fk, fv = fused_restore_paged(h, cfg.rope_theta, slot, pool_k,
                                 jnp.zeros_like(pool_k), use_kernel=True)
    dk, dv = dense_restore_paged(h, cfg.rope_theta, slot, pool_k,
                                 jnp.zeros_like(pool_k))
    assert jnp.allclose(fk, dk, atol=1e-5) and jnp.allclose(fv, dv, atol=1e-5)
    print("fused (Pallas, interpret) restore == dense paged restore: ok")


if __name__ == "__main__":
    main()
