"""Block-sparse diff extraction — Pallas TPU kernel (paper §4.3).

Computes the per-32-token-block max |mirror - master| over all layers and
the K/V planes, the quantity Diff-Aware Storage thresholds to decide which
blocks a Mirror must carry. Grid over (layer, block); the reduction across
layers happens via output revisiting (same output cell for every layer).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(m_ref, x_ref, o_ref):
    l = pl.program_id(1)  # layer is the INNER grid dim so the output cell
    # is revisited on consecutive iterations (legal accumulation pattern)
    d = jnp.abs(x_ref[0, 0].astype(jnp.float32) - m_ref[0, 0].astype(jnp.float32))
    cur = jnp.max(d)

    b = pl.program_id(0)

    @pl.when(l == 0)
    def _init():
        o_ref[b] = cur

    @pl.when(l > 0)
    def _acc():
        o_ref[b] = jnp.maximum(o_ref[b], cur)


def block_diff_kernel(
    master: jax.Array,   # [L, S, KV, hd], S a multiple of bt
    mirror: jax.Array,
    bt: int = 32,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Returns [nb] float32 max-abs diff per token block."""
    L, S, KV, hd = master.shape
    nb = S // bt
    mb = master.reshape(L, nb, bt, KV, hd)
    xb = mirror.reshape(L, nb, bt, KV, hd)
    spec = pl.BlockSpec((1, 1, bt, KV, hd), lambda b, l: (l, b, 0, 0, 0))
    return pl.pallas_call(
        _kernel,
        grid=(nb, L),
        in_specs=[spec, spec],
        # one scalar per block: the whole [nb] vector lives in SMEM for the
        # launch (a (1, 1) VMEM block is below the (8, 128) tile)
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((nb,), jnp.float32),
        interpret=interpret,
    )(mb, xb)
