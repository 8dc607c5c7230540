"""Collective RoPE alignment — Pallas TPU kernel (paper §4.2).

Re-rotates cached keys from their source positions to the target positions
of the new round prompt. TokenDance calls this ONCE per round group; the
per-request baseline calls it N times. Grid over token tiles; each cell
rotates a [tile_s, KV, hd] slab held in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(k_ref, delta_ref, o_ref, *, theta: float):
    k = k_ref[...]                       # [ts, KV, hd]
    delta = delta_ref[...]               # [ts, 1]
    ts, KV, hd = k.shape
    half = hd // 2
    # Mosaic's iota is integer-only: count in int32, then cast
    exps = jax.lax.broadcasted_iota(jnp.int32, (1, half), 1).astype(
        jnp.float32) / half
    freqs = jnp.exp(-exps * jnp.log(theta))
    ang = delta.astype(jnp.float32) * freqs                 # [ts, half]
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    kf = k.astype(jnp.float32)
    k1, k2 = kf[..., :half], kf[..., half:]
    o_ref[...] = jnp.concatenate(
        [k1 * cos - k2 * sin, k2 * cos + k1 * sin], axis=-1).astype(k.dtype)


def rope_align_kernel(
    k: jax.Array,        # [S, KV, hd], S a multiple of tile_s
    src_pos: jax.Array,  # [S] int32
    tgt_pos: jax.Array,  # [S] int32
    theta: float,
    *,
    tile_s: int = 128,
    interpret: bool = False,
) -> jax.Array:
    S, KV, hd = k.shape
    tile_s = min(tile_s, S)
    assert S % tile_s == 0, "pad S to the token tile"
    # a [S, 1] column: its (tile_s, 1) block keeps the token axis on
    # sublanes, where the [ts, half] angle table needs it
    delta = (tgt_pos - src_pos).astype(jnp.int32).reshape(S, 1)
    grid = (S // tile_s,)
    return pl.pallas_call(
        functools.partial(_kernel, theta=theta),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_s, KV, hd), lambda i: (i, 0, 0)),
            pl.BlockSpec((tile_s, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile_s, KV, hd), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((S, KV, hd), k.dtype),
        interpret=interpret,
    )(k, delta)
