"""Quickstart: build a small model, serve a batch of prompts (prefill +
greedy decode), and show the selectable architecture configs.

  PYTHONPATH=src python examples/quickstart.py [--arch qwen2.5-7b]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config, list_archs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import decode_step, init_params, prefill


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-7b", choices=list_archs())
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=24)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch).replace(dtype="float32")
    print(f"arch={cfg.name} ({cfg.arch_type}) layers={cfg.n_layers} "
          f"d_model={cfg.d_model} vocab={cfg.vocab_size}")
    params = init_params(jax.random.PRNGKey(0), cfg)

    toks = jax.random.randint(jax.random.PRNGKey(1),
                              (args.batch, args.prompt_len), 0, cfg.vocab_size)
    t0 = time.time()
    logits, cache = prefill(params, cfg, toks,
                            max_len=args.prompt_len + args.gen)
    jax.block_until_ready(logits)
    print(f"prefill [{args.batch}x{args.prompt_len}]: {time.time()-t0:.2f}s")

    step = jax.jit(lambda t, c: decode_step(params, cfg, t, c))
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    outs = [tok]
    t0 = time.time()
    for _ in range(args.gen - 1):
        lg, cache = step(tok, cache)
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        outs.append(tok)
    jax.block_until_ready(tok)
    dt = time.time() - t0
    print(f"decode {args.gen} tokens: {dt:.2f}s "
          f"({args.batch*args.gen/dt:.1f} tok/s)")
    gen = jnp.stack(outs, axis=1)
    for b in range(args.batch):
        print(f"  req{b}: {gen[b].tolist()}")


if __name__ == "__main__":
    main()
