"""Train a small dense model on the synthetic pipeline for a few hundred
steps, checkpointing at the end.

  PYTHONPATH=src python examples/train_small.py [--steps 200] [--arch qwen3-4b]
"""
import argparse
import os

import jax

from repro.configs import get_smoke_config, list_archs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.training import AdamWConfig, DataConfig, SyntheticTokens, train
from repro.training.checkpoint import save


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-7b", choices=list_archs())
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--out", default="/tmp/repro_ckpt")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch).replace(dtype="float32")
    n_params = cfg.param_count()
    print(f"training {cfg.name}: {n_params/1e6:.1f}M params, "
          f"{args.steps} steps @ [{args.batch}x{args.seq_len}]")
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                    global_batch=args.batch, seed=0)
    opt = AdamWConfig(lr=args.lr, warmup_steps=max(5, args.steps // 20),
                      total_steps=args.steps)
    params = init_params(jax.random.PRNGKey(0), cfg)
    res = train(cfg, opt, iter(SyntheticTokens(dc)), args.steps,
                params=params, log_every=max(1, args.steps // 10))
    print(f"loss {res.losses[0]:.3f} -> {res.losses[-1]:.3f} "
          f"in {res.wall_s:.0f}s ({res.steps/res.wall_s:.2f} steps/s)")
    save(args.out, res.params, {"arch": cfg.name, "steps": res.steps})
    print(f"checkpoint written to {args.out}.npz")


if __name__ == "__main__":
    main()
