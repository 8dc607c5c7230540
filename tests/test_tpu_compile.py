"""Compile for a described TPU v5e chip, without one attached.

The TPU compiler is installed with jax, and a topology can be described
and compiled for with no chip present: Mosaic then refuses what interpret
mode accepts (blocks below the (8, 128) tile, float iotas, ...), and XLA
refuses programs that do not fit the device. Nothing runs here, so these
tests say nothing about results or times — the interpret-mode suites
(tests/test_kernels.py, tests/test_flash_decode.py) pin the values.

Covered at qwen2.5-7b widths (28 query heads over 4 KV heads of 128,
32-token pages, bf16): all eight Pallas kernels, and one 16-layer paged
decode step with the weights as arguments — the program the serving
engine jits.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and a worker that decides at
import time which tests exist would make pytest-xdist's workers disagree.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.block_diff import block_diff_kernel
from repro.kernels.diff_restore import (
    fused_diff_restore_kernel,
    fused_family_restore_kernel,
)
from repro.kernels.flash_decode import (
    Q_ROWS,
    flash_decode_kernel,
    flash_decode_paged_kernel,
)
from repro.kernels.flash_prefill import (
    flash_prefill_kernel,
    flash_prefill_paged_kernel,
)
from repro.kernels.rope_align import rope_align_kernel
from repro.models import decode_step_paged, init_params

CFG = get_config("qwen2.5-7b")
H, KV, HD = CFG.n_heads, CFG.n_kv_heads, CFG.resolved_head_dim
BT = 32                       # page / block tokens
L, NB, M, P, NDB = 16, 16, 7, 64, 4
S = NB * BT                   # a 512-token span
T = 20                        # ragged dense tail
BF16, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, chip):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


# name -> (kernel call, argument shapes/dtypes, expected output shapes)
KERNELS = {
    "rope_align": (
        lambda k, s, t: rope_align_kernel(k, s, t, CFG.rope_theta),
        [((S, KV, HD), BF16), ((S,), I32), ((S,), I32)],
        [(S, KV, HD)]),
    "block_diff": (
        lambda a, b: block_diff_kernel(a, b, BT),
        [((L, S, KV, HD), BF16)] * 2,
        [(NB,)]),
    "flash_prefill": (
        flash_prefill_kernel,
        [((H, S, HD), BF16), ((KV, S, HD), BF16), ((KV, S, HD), BF16)],
        [(H, S, HD)]),
    "flash_decode": (
        lambda q, k, v: flash_decode_kernel(q, k, v, kv_len=S - 3),
        [((H, Q_ROWS, HD), BF16), ((KV, S, HD), BF16), ((KV, S, HD), BF16)],
        [(H, Q_ROWS, HD)]),
    "flash_prefill_paged": (
        lambda q, pk, pv, pi, tk, tv: flash_prefill_paged_kernel(
            q, pk, pv, pi, tk, tv, span_len=S, tail_len=T),
        [((H, S + 128, HD), BF16), ((P, BT, KV, HD), BF16),
         ((P, BT, KV, HD), BF16), ((NB,), I32), ((BT, KV, HD), BF16),
         ((BT, KV, HD), BF16)],
        [(H, S + 128, HD)]),
    "flash_decode_paged": (
        lambda q, pk, pv, pi, tk, tv: flash_decode_paged_kernel(
            q, pk, pv, pi, tk, tv, span_len=S, tail_len=T),
        [((KV, Q_ROWS, HD), BF16), ((P, BT, KV, HD), BF16),
         ((P, BT, KV, HD), BF16), ((NB,), I32), ((BT, KV, HD), BF16),
         ((BT, KV, HD), BF16)],
        [(KV, Q_ROWS, HD)]),
    "fused_diff_restore": (
        lambda mk, mv, dk, dv, ds, sm, dp, pk, pv: fused_diff_restore_kernel(
            mk, mv, dk, dv, ds, sm, dp, CFG.rope_theta, pk, pv),
        [((L, NB, BT, KV, HD), BF16)] * 2 + [((L, NDB, BT, KV, HD), BF16)] * 2
        + [((NB,), I32), ((NB,), I32), ((NB, BT), I32)]
        + [((L, P, BT, KV, HD), BF16)] * 2,
        [(L, P, BT, KV, HD)] * 2),
    "fused_family_restore": (
        lambda mk, mv, dk, dv, ds, sm, dp, pk, pv: fused_family_restore_kernel(
            mk, mv, dk, dv, ds, sm, dp, CFG.rope_theta, pk, pv),
        [((L, NB, BT, KV, HD), BF16)] * 2
        + [((M, L, NDB, BT, KV, HD), BF16)] * 2
        + [((M, NB), I32), ((M, NB), I32), ((M, NB, BT), I32)]
        + [((L, P, BT, KV, HD), BF16)] * 2,
        [(L, P, BT, KV, HD)] * 2),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, arg_shapes, out_shapes = KERNELS[name]
    args = [_spec(s, d, one_chip) for s, d in arg_shapes]
    lowered = jax.jit(fn).lower(*args)
    assert [o.shape for o in jax.tree.leaves(lowered.out_info)] == out_shapes
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()    # Mosaic, not a fallback


def test_decode_step_paged_compiles_full_width(one_chip):
    """16 of qwen2.5-7b's 28 layers at published widths, 8 agents over a
    512-token prompt and 32 generated tokens: the step fits one 16 GB
    chip with its ~9 GiB of weights passed in as arguments."""
    cfg = CFG.replace(n_layers=16)
    N, G = 8, 32
    nbt = (S + G) // BT
    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), shapes)
    pool = (cfg.n_layers, N * nbt, BT, KV, HD)
    cache = {
        "pk": _spec(pool, BF16, one_chip),
        "pv": _spec(pool, BF16, one_chip),
        "page_idx": _spec((N, nbt), I32, one_chip),
        "kv_pos": _spec((N, nbt * BT), I32, one_chip),
        "kv_valid": _spec((N, nbt * BT), jnp.bool_, one_chip),
        "length": _spec((N,), I32, one_chip),
    }
    tok = _spec((N,), I32, one_chip)
    step = jax.jit(lambda p, t, c: decode_step_paged(p, cfg, t, c))
    mem = step.lower(params, tok, cache).compile().memory_analysis()
    weights = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes))
    assert 8.5 * 2**30 < weights < 9.5 * 2**30
    assert mem.argument_size_in_bytes >= weights
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 15 * 2**30, used
