"""Fused diff restore — Pallas TPU kernels for Algorithm 1 (paper §4.4).

For each (layer, block) grid cell the kernel:
  1. loads the Master's 32-token KV block HBM->VMEM,
  2. selects the Mirror's block-sparse correction if this block carries a
     diff (whole-tile ``where``; skip-or-correct at block granularity is
     free on the VPU, matching Fig. 9's dispatch),
  3. applies the RoPE position recovery to the K plane, and
  4. writes the result through the slot map into the paged KV pool.

The ping-pong double-buffering of the CUDA prototype is played by the
Pallas grid pipeline itself: while cell i is being corrected in VMEM the
next Master block is already streaming in. Scalar-prefetched index maps
(``diff_slot``, ``slot_map``) give the paged-gather/scatter pattern.

Two kernels share the body:

* :func:`fused_diff_restore_kernel` — one Mirror per launch, grid
  ``(L, nb)``. A family of M mirrors pays M launches and re-streams
  every Master block M times.
* :func:`fused_family_restore_kernel` — the whole Master family per
  launch, grid ``(L, nb, M)`` with the mirror index innermost. The
  Master block's index map depends only on ``(l, b)``, so the grid
  pipeline keeps it resident in VMEM across the M mirror iterations:
  each shared block is streamed HBM->VMEM once per (layer, block) and
  corrected for every consumer while hot — "the cost of reusing a
  shared block is paid once regardless of agent count" (§4.2).

Logical block layout: [block_tokens=32, KV, head_dim] with KV*head_dim a
multiple of 128 for the production configs, so one logical block is a
whole number of (8, 128) VMEM tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rope_delta(k: jax.Array, delta: jax.Array, theta: float) -> jax.Array:
    """Rotate keys [bt, KV, hd] by per-token position delta [bt, 1].

    Frequencies use the same ``theta ** (i/half)`` form as the jnp oracle
    (ref.rope_delta_ref) so interpret-mode runs are bit-identical to it.
    """
    bt, KV, hd = k.shape
    half = hd // 2
    # Mosaic's iota is integer-only: count in int32, then cast
    exps = jax.lax.broadcasted_iota(jnp.int32, (1, half), 1).astype(
        jnp.float32) / half
    freqs = 1.0 / (theta ** exps)                        # [1, half]
    ang = delta.astype(jnp.float32) * freqs              # [bt, half]
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    kf = k.astype(jnp.float32)
    k1, k2 = kf[..., :half], kf[..., half:]
    return jnp.concatenate([k1 * cos - k2 * sin, k2 * cos + k1 * sin],
                           axis=-1).astype(k.dtype)


def _kernel(diff_slot_ref, slot_map_ref,      # scalar prefetch
            mk_ref, mv_ref, dk_ref, dv_ref, dp_ref,
            pk_in_ref, pv_in_ref,             # aliased pool (unused reads)
            ok_ref, ov_ref, *, theta: float):
    del slot_map_ref, pk_in_ref, pv_in_ref
    b = pl.program_id(1)
    have = diff_slot_ref[b] >= 0

    k = mk_ref[0, 0]        # [bt, KV, hd]
    v = mv_ref[0, 0]
    kd = dk_ref[0, 0]
    vd = dv_ref[0, 0]
    # skip-or-correct per block: whole-tile select in VMEM
    k = jnp.where(have, kd, k)
    v = jnp.where(have, vd, v)
    # RoPE position recovery (Alg. 1 line 9)
    k = _rope_delta(k, dp_ref[0], theta)
    ok_ref[0, 0] = k
    ov_ref[0, 0] = v


def fused_diff_restore_kernel(
    master_k: jax.Array,   # [L, nb, bt, KV, hd]
    master_v: jax.Array,
    diff_k: jax.Array,     # [L, ndb, bt, KV, hd] (ndb >= 1, padded)
    diff_v: jax.Array,
    diff_slot: jax.Array,  # [nb] int32, row into diff_* or -1
    slot_map: jax.Array,   # [nb] int32, destination page per block
    delta_pos: jax.Array,  # [nb, bt] int32 position delta for RoPE recovery
    theta: float,
    pool_k: jax.Array,     # [L, n_pages, bt, KV, hd] (updated in place)
    pool_v: jax.Array,
    *,
    interpret: bool = False,
):
    L, nb, bt, KV, hd = master_k.shape

    grid = (L, nb)
    spec_master = pl.BlockSpec(
        (1, 1, bt, KV, hd), lambda l, b, ds, sm: (l, b, 0, 0, 0))
    spec_diff = pl.BlockSpec(
        (1, 1, bt, KV, hd),
        lambda l, b, ds, sm: (l, jnp.maximum(ds[b], 0), 0, 0, 0))
    # delta as [nb, bt, 1] columns: a (bt, 1) block is whole in its last
    # dim and keeps the tokens on sublanes, beside the K rows they rotate
    spec_dp = pl.BlockSpec((1, bt, 1), lambda l, b, ds, sm: (b, 0, 0))
    spec_out = pl.BlockSpec(
        (1, 1, bt, KV, hd), lambda l, b, ds, sm: (l, sm[b], 0, 0, 0))

    gridspec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[spec_master, spec_master, spec_diff, spec_diff, spec_dp,
                  spec_out, spec_out],
        out_specs=[spec_out, spec_out],
    )
    fn = pl.pallas_call(
        functools.partial(_kernel, theta=theta),
        grid_spec=gridspec,
        out_shape=[jax.ShapeDtypeStruct(pool_k.shape, pool_k.dtype),
                   jax.ShapeDtypeStruct(pool_v.shape, pool_v.dtype)],
        input_output_aliases={7: 0, 8: 1},  # pools are updated in place
        interpret=interpret,
    )
    return fn(diff_slot, slot_map, master_k, master_v, diff_k, diff_v,
              delta_pos.reshape(nb, bt, 1), pool_k, pool_v)


def _family_kernel(diff_slot_ref, slot_map_ref,   # scalar prefetch [M, nb]
                   mk_ref, mv_ref, dk_ref, dv_ref, dp_ref,
                   pk_in_ref, pv_in_ref,           # aliased pool (unused reads)
                   ok_ref, ov_ref, *, theta: float):
    del slot_map_ref, pk_in_ref, pv_in_ref
    b = pl.program_id(1)
    m = pl.program_id(2)
    have = diff_slot_ref[m, b] >= 0

    k = mk_ref[0, 0]        # [bt, KV, hd] — resident across the m loop
    v = mv_ref[0, 0]
    kd = dk_ref[0, 0, 0]
    vd = dv_ref[0, 0, 0]
    k = jnp.where(have, kd, k)
    v = jnp.where(have, vd, v)
    k = _rope_delta(k, dp_ref[0, 0], theta)
    ok_ref[0, 0] = k
    ov_ref[0, 0] = v


def fused_family_restore_kernel(
    master_k: jax.Array,   # [L, nb, bt, KV, hd] — ONE master, whole family
    master_v: jax.Array,
    diff_k: jax.Array,     # [M, L, ndb, bt, KV, hd] (ndb >= 1, padded)
    diff_v: jax.Array,
    diff_slot: jax.Array,  # [M, nb] int32, row into diff_*[m] or -1
    slot_map: jax.Array,   # [M, nb] int32, destination page per (mirror, block)
    delta_pos: jax.Array,  # [M, nb, bt] int32 position delta for RoPE recovery
    theta: float,
    pool_k: jax.Array,     # [L, n_pages, bt, KV, hd] (updated in place)
    pool_v: jax.Array,
    *,
    interpret: bool = False,
):
    """Restore ALL M mirrors of a Master family in one launch.

    Grid ``(L, nb, M)`` — the mirror index is the innermost (fastest
    revisiting) dimension and the Master specs' index maps ignore it, so
    each Master block crosses HBM->VMEM once per (layer, block) and is
    corrected for all M consumers while resident. Per-mirror slot maps
    must target disjoint pool pages (each mirror owns its pages).
    """
    L, nb, bt, KV, hd = master_k.shape
    M = diff_slot.shape[0]

    grid = (L, nb, M)
    spec_master = pl.BlockSpec(
        (1, 1, bt, KV, hd), lambda l, b, m, ds, sm: (l, b, 0, 0, 0))
    spec_diff = pl.BlockSpec(
        (1, 1, 1, bt, KV, hd),
        lambda l, b, m, ds, sm: (m, l, jnp.maximum(ds[m, b], 0), 0, 0, 0))
    spec_dp = pl.BlockSpec(
        (1, 1, bt, 1), lambda l, b, m, ds, sm: (m, b, 0, 0))
    spec_out = pl.BlockSpec(
        (1, 1, bt, KV, hd), lambda l, b, m, ds, sm: (l, sm[m, b], 0, 0, 0))

    gridspec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[spec_master, spec_master, spec_diff, spec_diff, spec_dp,
                  spec_out, spec_out],
        out_specs=[spec_out, spec_out],
    )
    fn = pl.pallas_call(
        functools.partial(_family_kernel, theta=theta),
        grid_spec=gridspec,
        out_shape=[jax.ShapeDtypeStruct(pool_k.shape, pool_k.dtype),
                   jax.ShapeDtypeStruct(pool_v.shape, pool_v.dtype)],
        input_output_aliases={7: 0, 8: 1},  # pools are updated in place
        interpret=interpret,
    )
    return fn(diff_slot, slot_map, master_k, master_v, diff_k, diff_v,
              delta_pos.reshape(M, nb, bt, 1), pool_k, pool_v)
