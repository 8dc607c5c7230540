"""Test architecture whose layers differ in kind, as the replay's list of
layers allows: a leading dense layer with full attention, then a layer
with sliding-window attention and one with full attention, both with
routed experts (softmax router, top-k renormalised, dropless) beside a
shared expert. Full and sliding layers rotate by RoPE of their own
theta. Float32 throughout, every matmul at ``Precision.HIGHEST``; nothing
here imports the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference.qwen2 import rope, weight_bytes  # noqa: F401
from bench.roofline import Layer

HI = jax.lax.Precision.HIGHEST
D, H, KV, HD, F, V = 64, 4, 2, 16, 128, 256
EXPERTS, TOP_K, EXPERT_F, SHARED_F, WINDOW = 4, 2, 32, 32, 32
THETA = {"full": 1e6, "sliding": 1e4}
LAYERS = (
    Layer(H, KV, HD, HD, F=F),
    Layer(H, KV, HD, HD, attention="sliding", window=WINDOW,
          experts=EXPERTS, top_k=TOP_K, expert_F=EXPERT_F, shared_F=SHARED_F,
          router=EXPERTS),
    Layer(H, KV, HD, HD, experts=EXPERTS, top_k=TOP_K, expert_F=EXPERT_F,
          shared_F=SHARED_F, router=EXPERTS),
)


def dims(cfg: dict) -> dict:
    return dict(L=len(LAYERS), D=D, H=H, KV=KV, hd=HD, V=V, eps=1e-6,
                layers=LAYERS)


def make_weights(seed: int, cfg: dict) -> dict:
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))

    def w(*shape, scale=0.1):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def swiglu(width, *lead):
        return {"w_gate": w(*lead, D, width), "w_up": w(*lead, D, width),
                "w_down": w(*lead, width, D)}

    out = []
    for lay in LAYERS:
        p = {"ln1": w(D), "ln2": w(D),
             "wq": w(D, H * HD), "wk": w(D, KV * HD), "wv": w(D, KV * HD),
             "wo": w(H * HD, D)}
        if lay.experts:
            p.update(router=w(D, EXPERTS, scale=1.0),
                     experts=swiglu(EXPERT_F, EXPERTS),
                     shared=swiglu(SHARED_F))
        else:
            p["mlp"] = swiglu(F)
        out.append(p)
    return {"embed": w(V, D, scale=1.0), "blocks": out, "final_norm": w(D),
            "lm_head": w(D, V)}


def blocks(weights):
    return weights["blocks"]


def _rms(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
        * (1.0 + w)


def _swiglu(m, x):
    g = jnp.einsum("td,df->tf", x, m["w_gate"], precision=HI)
    u = jnp.einsum("td,df->tf", x, m["w_up"], precision=HI)
    return jnp.einsum("tf,fd->td", jax.nn.silu(g) * u, m["w_down"],
                      precision=HI)


def embed(weights, tokens, quant=None):
    return jnp.take(weights["embed"], tokens, axis=0)


def move_keys(k, delta, d, li):
    return rope(k, delta, THETA[LAYERS[li].attention])


def qkv(p, h, pos, d, li, quant=None):
    x = _rms(h, p["ln1"])
    theta = THETA[LAYERS[li].attention]

    def proj(wn, nh):
        return jnp.einsum("td,de->te", x, p[wn], precision=HI).reshape(
            -1, nh, HD)

    return (rope(proj("wq", H), pos, theta), rope(proj("wk", KV), pos, theta),
            proj("wv", KV))


def attend(q, q_pos, k, v, kv_pos, d, li):
    T = q.shape[0]
    qg = q.reshape(T, KV, H // KV, HD)
    s = jnp.einsum("qkgh,skh->kgqs", qg, k, precision=HI) / math.sqrt(HD)
    seen = kv_pos[None, :] <= q_pos[:, None]
    if LAYERS[li].attention == "sliding":
        seen &= kv_pos[None, :] > q_pos[:, None] - WINDOW
    s = jnp.where(seen[None, None], s, -jnp.inf)
    o = jnp.einsum("kgqs,skh->qkgh", jax.nn.softmax(s, -1), v, precision=HI)
    return o.reshape(T, H * HD)


def finish(p, h, o, d, li, quant=None):
    h = h + jnp.einsum("te,ed->td", o, p["wo"], precision=HI)
    x = _rms(h, p["ln2"])
    if not LAYERS[li].experts:
        return h + _swiglu(p["mlp"], x)
    probs = jax.nn.softmax(
        jnp.einsum("td,de->te", x, p["router"], precision=HI), -1)
    top_w, top_i = jax.lax.top_k(probs, TOP_K)
    top_w = top_w / top_w.sum(-1, keepdims=True)
    every = jnp.stack([_swiglu(jax.tree.map(lambda a, e=e: a[e],
                                            p["experts"]), x)
                       for e in range(EXPERTS)], 1)            # [T, E, D]
    routed = jnp.einsum("tk,tkd->td", top_w,
                        jnp.take_along_axis(every, top_i[..., None], 1),
                        precision=HI)
    return h + routed + _swiglu(p["shared"], x)


def head(weights, h, d, quant=None):
    return jnp.einsum("td,dv->tv", _rms(h, weights["final_norm"]),
                      weights["lm_head"], precision=HI)


def forward(weights, tokens):
    """Logits [T, V] of the plain causal forward over ``tokens``."""
    pos = jnp.arange(tokens.shape[0])
    h = embed(weights, tokens)
    for li, p in enumerate(blocks(weights)):
        q, k, v = qkv(p, h, pos, None, li)
        h = finish(p, h, attend(q, pos, k, v, pos, None, li), None, li)
    return head(weights, h, None)
