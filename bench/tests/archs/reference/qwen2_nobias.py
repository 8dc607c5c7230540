"""Test architecture: the Qwen2 reference without q, k and v biases. The
weights leave the biases out, and the projections add none."""
import jax.numpy as jnp

from bench.reference.qwen2 import (  # noqa: F401
    HI, _mat, _rms, attend, blocks, dims, embed, finish, head, move_keys, rope,
    weight_bytes)
from bench.reference.qwen2 import make_weights as _with_biases


def make_weights(seed: int, cfg: dict) -> dict:
    w = _with_biases(seed, cfg)
    for b in ("bq", "bk", "bv"):
        del w["blocks"]["attn"][b]
    return w


def qkv(p, h, pos, d, li, quant=None):
    a = p["attn"]
    x = _rms(h, p["ln1"], d["eps"])

    def proj(wn, nh):
        y = jnp.einsum("td,de->te", x, _mat(a[wn], quant), precision=HI)
        return y.reshape(-1, nh, d["hd"])

    q = rope(proj("wq", d["H"]), pos, d["theta"])
    k = rope(proj("wk", d["KV"]), pos, d["theta"])
    return q, k, proj("wv", d["KV"])

