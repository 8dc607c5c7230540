"""Plain float32 reference of the Qwen2 decoder, and the seeded weights
the benchmark serves.

Qwen2 (arXiv:2412.15115; the transformers ``Qwen2ForCausalLM`` code):
token embedding; per layer, RMSNorm, grouped-query causal attention with
q/k/v biases and split-halves RoPE, a residual add, RMSNorm, a SwiGLU MLP
and a residual add; a final RMSNorm and an untied output head. One
departure, in the parameterisation only: norm weights are stored as
offsets from one (``x * rsqrt(mean(x^2) + eps) * (1 + w)``), the layout
the served program reads.

Nothing here imports the program. The weights are made by
:func:`make_weights` in the tree layout the program takes
(``embed``, ``blocks`` with stacked ``[L, ...]`` leaves, ``final_norm``,
``lm_head``), and the reference reads the same arrays.

The module is an architecture as ``bench/reference/tokendance.py`` replays
one (its docstring states the contract): every layer is alike, so
:func:`blocks` hands the replay one stacked tree, and the layer index the
parts are given is not read. The layer comes in parts (:func:`qkv`,
:func:`attend`, :func:`finish`) for one sequence whose queries may be any
subset of its positions, and :func:`move_keys` moves cached keys to other
positions. Every matmul runs at ``Precision.HIGHEST`` in float32 on one
layer's weights at a time, so a caller that scans the layers never holds a
float32 copy of more than one. ``quant`` selects a control: ``"int8"`` rounds
every matrix to int8 with one absmax scale per output column (per row for
the embedding) before use, ``"fp8"`` to float8 e4m3 with the same scales;
everything else is unchanged.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.roofline import Layer

HI = jax.lax.Precision.HIGHEST


def dims(cfg: dict) -> dict:
    """Model sizes from a Hugging Face style Qwen2 config, and each
    layer's shape (``layers``): dense, full causal attention."""
    h, d = cfg["num_attention_heads"], cfg["hidden_size"]
    L, KV, hd, F = (cfg["num_hidden_layers"], cfg["num_key_value_heads"],
                    d // h, cfg["intermediate_size"])
    return dict(L=L, D=d, H=h, KV=KV, hd=hd, F=F, V=cfg["vocab_size"],
                theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
                layers=(Layer(h, KV, hd, hd, F=F),) * L)


def weight_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (64 bits are kept)."""
    words = np.random.SeedSequence(int(seed) % (1 << 63)).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


@partial(jax.jit, static_argnames=("L", "D", "H", "KV", "hd", "F", "V"))
def _make(key, *, L, D, H, KV, hd, F, V):
    keys = iter(jax.random.split(key, 16))
    out_scale = 0.02 / math.sqrt(2 * L)

    def w(shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(jnp.bfloat16)

    return {
        "embed": w((V, D)),
        "blocks": {
            "ln1": w((L, D)),
            "attn": {
                "wq": w((L, D, H * hd)), "wk": w((L, D, KV * hd)),
                "wv": w((L, D, KV * hd)),
                "wo": w((L, H * hd, D), out_scale),
                "bq": w((L, H * hd)), "bk": w((L, KV * hd)),
                "bv": w((L, KV * hd)),
            },
            "ln2": w((L, D)),
            "mlp": {"w_gate": w((L, D, F)), "w_up": w((L, D, F)),
                    "w_down": w((L, F, D), out_scale)},
        },
        "final_norm": w((D,)),
        "lm_head": w((D, V)),
    }


def make_weights(seed: int, cfg: dict) -> dict:
    """bf16 weights in the program's tree layout, drawn on the device in
    one jitted call: N(0, 0.02) everywhere, output projections scaled by
    1/sqrt(2L), biases and norm offsets drawn too so that every term of
    the layer equations is exercised."""
    d = dims(cfg)
    return _make(weight_key(seed), L=d["L"], D=d["D"], H=d["H"], KV=d["KV"],
                 hd=d["hd"], F=d["F"], V=d["V"])


def weight_bytes(weights: dict) -> int:
    return int(sum(x.nbytes for x in jax.tree.leaves(weights)))


def blocks(weights: dict) -> dict:
    """The layers' weights: one tree with stacked ``[L, ...]`` leaves."""
    return weights["blocks"]


# --------------------------------------------------------------------------
# reference forward
# --------------------------------------------------------------------------
def _q8(w, axis):
    """int8 absmax round trip along ``axis`` (the reduced axis keeps one
    scale per remaining index)."""
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def _f8(w, axis):
    """float8 e4m3 round trip with one absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


QUANT = {None: lambda w, axis: w, "int8": _q8, "fp8": _f8}


def _mat(w, quant, axis=-2):
    return QUANT[quant](w.astype(jnp.float32), axis)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def rope(x, pos, theta):
    """Split-halves RoPE of ``x`` [T, heads, hd] by the angles of ``pos``
    [T] (any whole numbers, so also a shift from one position to
    another)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs          # [T, half]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# --------------------------------------------------------------------------
# the layer, in parts, for one sequence: queries may be any subset of
# positions, keys and values any cache laid out by position
# --------------------------------------------------------------------------
def embed(weights, tokens, quant=None):
    """float32 embeddings [T, D] of ``tokens`` [T]."""
    return _mat(jnp.take(weights["embed"], tokens, axis=0), quant, axis=-1)


def move_keys(k, delta, d, li):
    """Cached keys [T, KV, hd] of layer ``li`` moved by ``delta`` [T]
    positions: RoPE composes, so one rotation by the difference."""
    return rope(k, delta, d["theta"])


def qkv(p, h, pos, d, li, quant=None):
    """RoPE'd queries [T, H, hd], keys and values [T, KV, hd] of the
    residual stream ``h`` [T, D] at positions ``pos`` [T]."""
    a = p["attn"]
    x = _rms(h, p["ln1"], d["eps"])

    def proj(wn, bn, nh):
        y = jnp.einsum("td,de->te", x, _mat(a[wn], quant), precision=HI)
        return (y + a[bn].astype(jnp.float32)).reshape(-1, nh, d["hd"])

    q = rope(proj("wq", "bq", d["H"]), pos, d["theta"])
    k = rope(proj("wk", "bk", d["KV"]), pos, d["theta"])
    return q, k, proj("wv", "bv", d["KV"])


def attend(q, q_pos, k, v, kv_pos, d, li):
    """Causal grouped-query attention: query ``i`` sees every key whose
    position is at most ``q_pos[i]``. Returns [T, H * hd]."""
    T, KV, hd = q.shape[0], d["KV"], d["hd"]
    qg = q.reshape(T, KV, d["H"] // KV, hd)
    s = jnp.einsum("qkgh,skh->kgqs", qg, k, precision=HI) / math.sqrt(hd)
    s = jnp.where(kv_pos[None, None, None, :] <= q_pos[None, None, :, None],
                  s, -jnp.inf)
    o = jnp.einsum("kgqs,skh->qkgh", jax.nn.softmax(s, axis=-1), v,
                   precision=HI)
    return o.reshape(T, d["H"] * hd)


def finish(p, h, o, d, li, quant=None):
    """The output projection's residual add, then the MLP's."""
    h = h + jnp.einsum("te,ed->td", o, _mat(p["attn"]["wo"], quant),
                       precision=HI)
    m = p["mlp"]
    x = _rms(h, p["ln2"], d["eps"])
    g = jnp.einsum("td,df->tf", x, _mat(m["w_gate"], quant), precision=HI)
    u = jnp.einsum("td,df->tf", x, _mat(m["w_up"], quant), precision=HI)
    return h + jnp.einsum("tf,fd->td", jax.nn.silu(g) * u,
                          _mat(m["w_down"], quant), precision=HI)


def head(weights, h, d, quant=None):
    """float32 logits [T, V] of the final residual stream ``h`` [T, D]."""
    h = _rms(h, weights["final_norm"], d["eps"])
    w = weights["lm_head"]
    V = w.shape[1]
    step = -(-V // 4)
    return jnp.concatenate(
        [jnp.einsum("td,dv->tv", h, _mat(w[:, i:i + step], quant),
                    precision=HI) for i in range(0, V, step)], axis=-1)
