"""Composable decoder model covering all assigned architecture families.

One block function handles dense / MoE / SSM / hybrid layers; layers are
stacked along axis 0 and driven by ``lax.scan`` (keeps HLO small for the
512-device dry-run) with optional remat for training.

Public API:
  init_params(rng, cfg)
  forward(params, cfg, tokens, ...)            -> logits, aux
  prefill(params, cfg, tokens, max_len, ...)   -> logits, cache
  decode_step(params, cfg, token, cache, ...)  -> logits, cache
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    _noshard,
    attention_block,
    moe_block,
    rmsnorm,
    rope_cos_sin,
    swiglu_mlp,
)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------
def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


@partial(jax.jit, static_argnums=1)
def init_params(rng: jax.Array, cfg: ModelConfig) -> dict:
    """Initialize a parameter pytree with stacked layer leaves ([L, ...]).

    One jitted program: each leaf is drawn and cast inside it, so a weight
    is created on the device in ``cfg.dtype`` and never exists there as
    its float32 draw. Threefry draws the same values under jit as eagerly.
    """
    dt = _dtype(cfg)
    L, D = cfg.n_layers, cfg.d_model
    hd, H, KV = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    keys = iter(jax.random.split(rng, 64))

    def norm(shape):
        return jnp.zeros(shape, dt)

    def w(shape, scale=0.02):
        return (jax.random.normal(next(keys), shape, jnp.float32) * scale).astype(dt)

    blocks: dict = {"ln1": norm((L, D))}
    if cfg.has_attention:
        attn = {
            "wq": w((L, D, H * hd)),
            "wk": w((L, D, KV * hd)),
            "wv": w((L, D, KV * hd)),
            "wo": w((L, H * hd, D), scale=0.02 / math.sqrt(2 * L)),
        }
        if cfg.attn_bias:
            attn["bq"] = jnp.zeros((L, H * hd), dt)
            attn["bk"] = jnp.zeros((L, KV * hd), dt)
            attn["bv"] = jnp.zeros((L, KV * hd), dt)
        if cfg.qk_norm:
            attn["q_norm"] = norm((L, hd))
            attn["k_norm"] = norm((L, hd))
        blocks["attn"] = attn
    if cfg.has_ssm:
        di, n, nh = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
        d_in_proj = 2 * di + 2 * n + nh
        conv_dim = di + 2 * n
        dt_init = jnp.exp(
            jax.random.uniform(next(keys), (L, nh), jnp.float32)
            * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        blocks["ssm"] = {
            "in_proj": w((L, D, d_in_proj)),
            "conv_w": w((L, ssm_mod.D_CONV, conv_dim), scale=0.2),
            "dt_bias": (dt_init + jnp.log(-jnp.expm1(-dt_init))).astype(jnp.float32),
            "A_log": jnp.log(
                1.0 + 15.0 * jax.random.uniform(next(keys), (L, nh), jnp.float32)),
            "D_skip": jnp.ones((L, nh), dt),
            "out_norm": norm((L, di)),
            "out_proj": w((L, di, D), scale=0.02 / math.sqrt(2 * L)),
        }
    if cfg.hybrid:
        blocks["attn_out_norm"] = norm((L, D))
        blocks["ssm_out_norm"] = norm((L, D))
    if cfg.is_moe:
        F, E = cfg.d_ff, cfg.n_experts
        moe = {
            "router": w((L, D, E)),
            "w_gate": w((L, E, D, F)),
            "w_up": w((L, E, D, F)),
            "w_down": w((L, E, F, D), scale=0.02 / math.sqrt(2 * L)),
        }
        if cfg.dense_residual:
            moe["dense"] = {
                "w_gate": w((L, D, F)),
                "w_up": w((L, D, F)),
                "w_down": w((L, F, D), scale=0.02 / math.sqrt(2 * L)),
            }
        blocks["moe"] = moe
        blocks["ln2"] = norm((L, D))
    elif cfg.d_ff and cfg.arch_type != "ssm":
        F = cfg.d_ff
        blocks["mlp"] = {
            "w_gate": w((L, D, F)),
            "w_up": w((L, D, F)),
            "w_down": w((L, F, D), scale=0.02 / math.sqrt(2 * L)),
        }
        blocks["ln2"] = norm((L, D))

    params = {
        "embed": w((cfg.vocab_size, D)),
        "blocks": blocks,
        "final_norm": norm((D,)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = w((D, cfg.vocab_size))
    return params


# --------------------------------------------------------------------------
# block forward (one layer)
# --------------------------------------------------------------------------
def _block_full(h, p, cfg: ModelConfig, *, window, positions, cos, sin,
                shard, init_ssm=None, init_conv=None):
    """Full-sequence block (train / prefill). Returns (h, per-layer outs)."""
    outs = {}
    aux = jnp.float32(0.0)
    x = rmsnorm(h, p["ln1"], cfg.rmsnorm_eps)

    mixer_out = 0.0
    if cfg.has_attention:
        a_out, (k, v) = attention_block(
            x, p["attn"], cfg=cfg, positions=positions, window=window,
            cos=cos, sin=sin, shard=shard)
        outs["k"], outs["v"] = k, v
        if cfg.hybrid:
            a_out = rmsnorm(a_out, p["attn_out_norm"], cfg.rmsnorm_eps)
        mixer_out = a_out
    if cfg.has_ssm:
        s_out, (state, conv) = ssm_mod.mamba2_forward(
            x, p["ssm"], cfg=cfg, init_state=init_ssm, conv_state=init_conv)
        outs["ssm"], outs["conv"] = state, conv
        if cfg.hybrid:
            s_out = rmsnorm(s_out, p["ssm_out_norm"], cfg.rmsnorm_eps)
            mixer_out = 0.5 * (mixer_out + s_out)
        else:
            mixer_out = s_out
    h = h + mixer_out

    if cfg.is_moe:
        x2 = rmsnorm(h, p["ln2"], cfg.rmsnorm_eps)
        m_out, aux = moe_block(x2, p["moe"], cfg=cfg, shard=shard)
        h = h + m_out
    elif "mlp" in p:
        x2 = rmsnorm(h, p["ln2"], cfg.rmsnorm_eps)
        h = h + swiglu_mlp(x2, p["mlp"], shard)
    return shard(h, "act_resid"), outs, aux


def _block_decode(h, p, cfg: ModelConfig, *, window, positions, cos, sin,
                  shard, layer_cache):
    """One-token block against a cache. Returns (h, updated layer cache)."""
    new_cache = {}
    x = rmsnorm(h, p["ln1"], cfg.rmsnorm_eps)
    mixer_out = 0.0
    if cfg.has_attention:
        # project the new token, write into cache, attend over everything
        a_out, (k_new, v_new) = _decode_attention(
            x, p["attn"], cfg, window, positions, cos, sin, shard, layer_cache)
        new_cache["k"], new_cache["v"] = k_new, v_new
        if cfg.hybrid:
            a_out = rmsnorm(a_out, p["attn_out_norm"], cfg.rmsnorm_eps)
        mixer_out = a_out
    if cfg.has_ssm:
        s_out, (state, conv) = ssm_mod.mamba2_decode(
            x, p["ssm"], cfg=cfg, state=layer_cache["ssm"],
            conv_state=layer_cache["conv"])
        new_cache["ssm"], new_cache["conv"] = state, conv
        if cfg.hybrid:
            s_out = rmsnorm(s_out, p["ssm_out_norm"], cfg.rmsnorm_eps)
            mixer_out = 0.5 * (mixer_out + s_out)
        else:
            mixer_out = s_out
    h = h + mixer_out
    if cfg.is_moe:
        x2 = rmsnorm(h, p["ln2"], cfg.rmsnorm_eps)
        m_out, _ = moe_block(x2, p["moe"], cfg=cfg, shard=shard)
        h = h + m_out
    elif "mlp" in p:
        x2 = rmsnorm(h, p["ln2"], cfg.rmsnorm_eps)
        h = h + swiglu_mlp(x2, p["mlp"], shard)
    return shard(h, "act_resid"), new_cache


def _block_decode_paged(h, p, cfg: ModelConfig, *, window, positions, cos,
                        sin, shard, layer_cache):
    """One-token block whose attention KV cache is a page pool
    (attention-only — see :func:`decode_step_paged`). Mirrors
    :func:`_block_decode` minus the SSM branch."""
    x = rmsnorm(h, p["ln1"], cfg.rmsnorm_eps)
    a_out, (pk, pv) = _decode_attention_paged(
        x, p["attn"], cfg, window, positions, cos, sin, shard, layer_cache)
    h = h + a_out
    if cfg.is_moe:
        x2 = rmsnorm(h, p["ln2"], cfg.rmsnorm_eps)
        m_out, _ = moe_block(x2, p["moe"], cfg=cfg, shard=shard)
        h = h + m_out
    elif "mlp" in p:
        x2 = rmsnorm(h, p["ln2"], cfg.rmsnorm_eps)
        h = h + swiglu_mlp(x2, p["mlp"], shard)
    return shard(h, "act_resid"), {"pk": pk, "pv": pv}


def _decode_attention_paged(x, p, cfg, window, positions, cos, sin, shard, lc):
    """Paged twin of :func:`_decode_attention`: the new token's K/V is
    scatter-written into its round pool page (``page_idx[b, length//bt]``
    at slot ``length % bt``) instead of a dense cache row, and the
    attention stream is gathered back through the page table at the
    point of use. The gather reconstructs exactly the dense ``k_all``
    the dense path builds — pages are the dense cache's blocks — so the
    two paths are bit-identical (pinned in tests). This is the XLA form
    of ``kernels.flash_decode``'s paged kernel: same data, fetched
    through the page table (the Pallas kernel is the TPU form, validated
    against the same oracle in interpret mode)."""
    from repro.models.layers import apply_rope, dispatch_attention

    B, S1, D = x.shape  # S1 == 1
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim

    def proj(wname, bname, nh):
        y = jnp.einsum("bsd,dhk->bshk", x, p[wname].reshape(D, nh, hd))
        if bname in p:
            y = y + p[bname].reshape(nh, hd)
        return y

    q = proj("wq", "bq", H)
    k = proj("wk", "bk", KV)
    v = proj("wv", "bv", KV)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.rmsnorm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.rmsnorm_eps)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    length = lc["length"]                         # [B]
    page_idx = lc["page_idx"]                     # [B, nbt] int32
    bt = lc["pk"].shape[1]
    B_, nbt = page_idx.shape
    rows = jnp.arange(B_)
    pages = page_idx[rows, length // bt]          # each seq's open gen page
    slots = length % bt
    pk = lc["pk"].at[pages, slots].set(k[:, 0])   # [P, bt, KV, hd]
    pv = lc["pv"].at[pages, slots].set(v[:, 0])
    k_all = pk[page_idx].reshape(B_, nbt * bt, KV, hd)
    v_all = pv[page_idx].reshape(B_, nbt * bt, KV, hd)
    out = dispatch_attention(
        cfg, q, k_all, v_all, q_pos=positions, kv_pos=lc["kv_pos"],
        window=window, softcap=cfg.attn_logit_softcap,
        kv_valid=lc["kv_valid"])
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].reshape(H, hd, D))
    return shard(out, "act_resid"), (pk, pv)


def _decode_attention(x, p, cfg, window, positions, cos, sin, shard, lc):
    """Write the new token's K/V into the cache and attend over it."""
    from repro.models.layers import apply_rope, dispatch_attention

    B, S1, D = x.shape  # S1 == 1
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim

    def proj(wname, bname, nh):
        y = jnp.einsum("bsd,dhk->bshk", x, p[wname].reshape(D, nh, hd))
        if bname in p:
            y = y + p[bname].reshape(nh, hd)
        return y

    q = proj("wq", "bq", H)
    k = proj("wk", "bk", KV)
    v = proj("wv", "bv", KV)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.rmsnorm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.rmsnorm_eps)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    length = lc["length"]  # [B]

    def write(cache_b, new_b, idx):
        return jax.lax.dynamic_update_slice(cache_b, new_b, (idx, 0, 0))

    k_all = jax.vmap(write)(lc["k"], k, length)   # [B, Smax, KV, hd]
    v_all = jax.vmap(write)(lc["v"], v, length)
    out = dispatch_attention(
        cfg, q, k_all, v_all, q_pos=positions, kv_pos=lc["kv_pos"],
        window=window, softcap=cfg.attn_logit_softcap,
        kv_valid=lc["kv_valid"])
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].reshape(H, hd, D))
    return shard(out, "act_resid"), (k_all, v_all)


# --------------------------------------------------------------------------
# model-level entry points
# --------------------------------------------------------------------------
def _embed(params, cfg, tokens, frontend_embeds):
    h = jnp.take(params["embed"], tokens, axis=0).astype(_dtype(cfg))
    if frontend_embeds is not None:
        sf = frontend_embeds.shape[1]
        h = jnp.concatenate(
            [frontend_embeds.astype(h.dtype), h[:, sf:]], axis=1)
    return h


def _logits(params, cfg, h, shard):
    h = rmsnorm(h, params["final_norm"], cfg.rmsnorm_eps)
    table = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", h, table.astype(h.dtype))
    return shard(logits.astype(jnp.float32), "logits")


def _windows(cfg: ModelConfig, seq_len: int, long_context: bool) -> jax.Array:
    if long_context and cfg.long_context_window:
        ws = [min(cfg.long_context_window, seq_len)] * cfg.n_layers
    else:
        ws = list(cfg.layer_window_sizes(seq_len)) or [seq_len] * cfg.n_layers
    return jnp.asarray(ws, jnp.int32)


def forward(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,                     # [B, S] int32
    *,
    frontend_embeds: Optional[jax.Array] = None,
    shard=_noshard,
    remat: bool = False,
    long_context: bool = False,
    unroll: bool = False,
    return_hidden: bool = False,
):
    """Training/scoring forward pass. Returns (logits [B,S,V], aux_loss).
    ``return_hidden=True`` returns the final-norm'd hidden states instead
    of logits (used by the chunked-xent loss path).

    ``unroll=True`` unrolls the layer scan — used by the dry-run so
    ``cost_analysis`` counts every layer (while-loop bodies are costed
    once), and by perf variants trading compile time for schedule freedom.
    """
    B, S = tokens.shape
    h = _embed(params, cfg, tokens, frontend_embeds)
    h = shard(h, "act_resid")
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    cos, sin = (None, None)
    if cfg.has_attention:
        cos, sin = rope_cos_sin(positions, cfg.resolved_head_dim, cfg.rope_theta)
    windows = _windows(cfg, S, long_context)

    def body(h, xs):
        p, window = xs
        h, _, aux = _block_full(
            h, p, cfg, window=window, positions=positions, cos=cos, sin=sin,
            shard=shard)
        return h, aux

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    h, auxes = jax.lax.scan(body, h, (params["blocks"], windows),
                            unroll=cfg.n_layers if unroll else 1)
    if return_hidden:
        # pre-final-norm hidden; _logits (in the chunked loss) applies it
        return h, jnp.sum(auxes)
    return _logits(params, cfg, h, shard), jnp.sum(auxes)


def prefill(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,                     # [B, S]
    *,
    max_len: Optional[int] = None,
    frontend_embeds: Optional[jax.Array] = None,
    shard=_noshard,
    long_context: bool = False,
    logits_last_only: bool = False,
    unroll: bool = False,
):
    """Run the prompt and build a decode cache. Returns (logits, cache).

    ``logits_last_only`` avoids materializing the full [B, S, V] logits
    (serving only needs the last position to start decoding).
    """
    B, S = tokens.shape
    max_len = max_len or S
    h = _embed(params, cfg, tokens, frontend_embeds)
    h = shard(h, "act_resid")
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    cos, sin = (None, None)
    if cfg.has_attention:
        cos, sin = rope_cos_sin(positions, cfg.resolved_head_dim, cfg.rope_theta)
    windows = _windows(cfg, max_len, long_context)

    def body(h, xs):
        p, window = xs
        h, outs, _ = _block_full(
            h, p, cfg, window=window, positions=positions, cos=cos, sin=sin,
            shard=shard)
        return h, outs

    h, outs = jax.lax.scan(body, h, (params["blocks"], windows),
                           unroll=cfg.n_layers if unroll else 1)
    logits = _logits(params, cfg, h[:, -1:] if logits_last_only else h, shard)

    cache: dict = {"length": jnp.full((B,), S, jnp.int32)}
    if cfg.has_attention:
        pad = max_len - S
        k = jnp.pad(outs["k"], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(outs["v"], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        cache["k"] = shard(k, "cache_kv")
        cache["v"] = shard(v, "cache_kv")
        cache["kv_pos"] = jnp.pad(positions, ((0, 0), (0, pad)))
        cache["kv_valid"] = jnp.pad(
            jnp.ones((B, S), bool), ((0, 0), (0, pad)))
    if cfg.has_ssm:
        cache["ssm"] = outs["ssm"]
        cache["conv"] = outs["conv"]
    return logits, cache


def make_empty_cache(cfg: ModelConfig, batch: int, max_len: int,
                     dtype=None) -> dict:
    """An all-empty decode cache (for dry-run decode shapes and the engine)."""
    dt = dtype or _dtype(cfg)
    L = cfg.n_layers
    cache: dict = {"length": jnp.zeros((batch,), jnp.int32)}
    if cfg.has_attention:
        KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        cache["k"] = jnp.zeros((L, batch, max_len, KV, hd), dt)
        cache["v"] = jnp.zeros((L, batch, max_len, KV, hd), dt)
        cache["kv_pos"] = jnp.zeros((batch, max_len), jnp.int32)
        cache["kv_valid"] = jnp.zeros((batch, max_len), bool)
    if cfg.has_ssm:
        cache["ssm"] = jnp.zeros(
            (L, batch, cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state),
            jnp.float32)
        cache["conv"] = jnp.zeros(
            (L, batch, ssm_mod.D_CONV - 1, cfg.d_inner + 2 * cfg.ssm_state), dt)
    return cache


def extend(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,         # [B, T] known new tokens (chunked prefill)
    cache: dict,
    *,
    shard=_noshard,
    long_context: bool = False,
):
    """Extend an existing cache by T known tokens in one pass (used for
    prefix-cache suffix compute and teacher-forced insertion).

    Requires attention (SSM archs extend via repeated decode or a fresh
    prefill). All sequences in the batch must share ``cache['length']``.
    Returns (logits [B, T, V], new cache).
    """
    assert cfg.has_attention and not cfg.has_ssm, \
        "extend() supports attention caches; use prefill/decode for SSM"
    B, T = tokens.shape
    h = _embed(params, cfg, tokens, None)
    length = cache["length"]
    positions = length[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    cos, sin = rope_cos_sin(positions, cfg.resolved_head_dim, cfg.rope_theta)
    max_len = cache["k"].shape[2]
    windows = _windows(cfg, max_len, long_context)

    def write_rows(cache_b, new_b, idx):
        return jax.lax.dynamic_update_slice(cache_b, new_b, (idx, 0, 0))

    kv_pos = jax.vmap(lambda p_, i, v_: jax.lax.dynamic_update_slice(p_, v_, (i,)))(
        cache["kv_pos"], length, positions)
    kv_valid = jax.vmap(lambda v_, i: jax.lax.dynamic_update_slice(
        v_, jnp.ones((T,), bool), (i,)))(cache["kv_valid"], length)

    def body(h, xs):
        p, window, lc = xs
        from repro.models.layers import apply_rope, gqa_attention

        x = rmsnorm(h, p["ln1"], cfg.rmsnorm_eps)
        # project new tokens, write into the layer cache, attend over it
        B_, T_, D = x.shape
        H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
        ap = p["attn"]
        q = jnp.einsum("btd,dhk->bthk", x, ap["wq"].reshape(D, H, hd))
        k = jnp.einsum("btd,dhk->bthk", x, ap["wk"].reshape(D, KV, hd))
        v = jnp.einsum("btd,dhk->bthk", x, ap["wv"].reshape(D, KV, hd))
        if "bq" in ap:
            q = q + ap["bq"].reshape(H, hd)
            k = k + ap["bk"].reshape(KV, hd)
            v = v + ap["bv"].reshape(KV, hd)
        if cfg.qk_norm:
            q = rmsnorm(q, ap["q_norm"], cfg.rmsnorm_eps)
            k = rmsnorm(k, ap["k_norm"], cfg.rmsnorm_eps)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        k_all = jax.vmap(write_rows)(lc["k"], k, length)
        v_all = jax.vmap(write_rows)(lc["v"], v, length)
        out = gqa_attention(q, k_all, v_all, q_pos=positions, kv_pos=kv_pos,
                            window=window, softcap=cfg.attn_logit_softcap,
                            kv_valid=kv_valid)
        out = jnp.einsum("bthk,hkd->btd", out, ap["wo"].reshape(H, hd, D))
        h = h + shard(out, "act_resid")
        x2 = rmsnorm(h, p["ln2"], cfg.rmsnorm_eps)
        if cfg.is_moe:
            m, _ = moe_block(x2, p["moe"], cfg=cfg, shard=shard)
            h = h + m
        else:
            h = h + swiglu_mlp(x2, p["mlp"], shard)
        return shard(h, "act_resid"), {"k": k_all, "v": v_all}

    layer_caches = {k_: cache[k_] for k_ in ("k", "v")}
    h, new_caches = jax.lax.scan(body, h, (params["blocks"], windows, layer_caches))
    logits = _logits(params, cfg, h, shard)

    new_cache = dict(cache)
    new_cache.update(new_caches)
    new_cache["kv_pos"], new_cache["kv_valid"] = kv_pos, kv_valid
    new_cache["length"] = length + T
    return logits, new_cache


def decode_step(
    params: dict,
    cfg: ModelConfig,
    token: jax.Array,          # [B] int32
    cache: dict,
    *,
    shard=_noshard,
    long_context: bool = False,
    unroll: bool = False,
):
    """Generate logits for one new token per sequence; update the cache."""
    B = token.shape[0]
    h = jnp.take(params["embed"], token[:, None], axis=0).astype(_dtype(cfg))
    h = h.reshape(B, 1, -1)
    length = cache["length"]
    positions = length[:, None]  # [B, 1]
    cos, sin = (None, None)
    if cfg.has_attention:
        cos, sin = rope_cos_sin(positions, cfg.resolved_head_dim, cfg.rope_theta)
        max_len = cache["k"].shape[2]
        kv_pos = jax.vmap(
            lambda p_, i, l: jax.lax.dynamic_update_slice(p_, l[None], (i,))
        )(cache["kv_pos"], length, length)
        kv_valid = jax.vmap(
            lambda v_, i: jax.lax.dynamic_update_slice(v_, jnp.ones((1,), bool), (i,))
        )(cache["kv_valid"], length)
    else:
        max_len = 0
        kv_pos = kv_valid = None
    windows = _windows(cfg, max_len or 1, long_context)

    def body(h, xs):
        p, window, lc = xs
        lc = dict(lc)
        lc["length"] = length
        if cfg.has_attention:
            lc["kv_pos"], lc["kv_valid"] = kv_pos, kv_valid
        h, new_lc = _block_decode(
            h, p, cfg, window=window, positions=positions, cos=cos, sin=sin,
            shard=shard, layer_cache=lc)
        return h, new_lc

    layer_caches = {k_: cache[k_] for k_ in ("k", "v", "ssm", "conv")
                    if k_ in cache}
    h, new_caches = jax.lax.scan(body, h,
                                 (params["blocks"], windows, layer_caches),
                                 unroll=cfg.n_layers if unroll else 1)
    logits = _logits(params, cfg, h, shard)[:, 0]

    new_cache = dict(cache)
    new_cache.update(new_caches)
    if cfg.has_attention:
        new_cache["kv_pos"], new_cache["kv_valid"] = kv_pos, kv_valid
    new_cache["length"] = length + 1
    return logits, new_cache


def decode_step_paged(
    params: dict,
    cfg: ModelConfig,
    token: jax.Array,          # [B] int32
    cache: dict,
    *,
    shard=_noshard,
    long_context: bool = False,
    unroll: bool = False,
):
    """:func:`decode_step` whose attention KV lives in round pool pages.

    ``cache`` carries per-layer page pools ``pk``/``pv``
    [L, P, bt, KV, hd] and a shared page table ``page_idx`` [B, nbt]
    instead of dense ``k``/``v``: the new token's K/V is scatter-written
    into page ``page_idx[b, length // bt]`` at slot ``length % bt`` (the
    page fills across steps and seals when generation crosses the next
    block boundary), and attention gathers the table's pages back into
    the dense-equivalent stream at the point of use. Outputs and updated
    state are bit-identical to :func:`decode_step` on the corresponding
    dense cache. Attention-only architectures — the serving engine
    routes SSM/hybrid state through the dense loop.
    """
    assert cfg.has_attention and not cfg.has_ssm, \
        "paged decode carries attention KV only; use decode_step for SSM"
    B = token.shape[0]
    h = jnp.take(params["embed"], token[:, None], axis=0).astype(_dtype(cfg))
    h = h.reshape(B, 1, -1)
    length = cache["length"]
    positions = length[:, None]  # [B, 1]
    cos, sin = rope_cos_sin(positions, cfg.resolved_head_dim, cfg.rope_theta)
    page_idx = cache["page_idx"]
    nbt, bt = page_idx.shape[1], cache["pk"].shape[2]
    max_len = nbt * bt
    kv_pos = jax.vmap(
        lambda p_, i, l: jax.lax.dynamic_update_slice(p_, l[None], (i,))
    )(cache["kv_pos"], length, length)
    kv_valid = jax.vmap(
        lambda v_, i: jax.lax.dynamic_update_slice(v_, jnp.ones((1,), bool), (i,))
    )(cache["kv_valid"], length)
    windows = _windows(cfg, max_len, long_context)

    def body(h, xs):
        p, window, lc = xs
        lc = dict(lc)
        lc["length"] = length
        lc["kv_pos"], lc["kv_valid"] = kv_pos, kv_valid
        lc["page_idx"] = page_idx
        h, new_lc = _block_decode_paged(
            h, p, cfg, window=window, positions=positions, cos=cos, sin=sin,
            shard=shard, layer_cache=lc)
        return h, new_lc

    layer_caches = {"pk": cache["pk"], "pv": cache["pv"]}
    h, new_caches = jax.lax.scan(body, h,
                                 (params["blocks"], windows, layer_caches),
                                 unroll=cfg.n_layers if unroll else 1)
    logits = _logits(params, cfg, h, shard)[:, 0]

    new_cache = dict(cache)
    new_cache.update(new_caches)
    new_cache["kv_pos"], new_cache["kv_valid"] = kv_pos, kv_valid
    new_cache["length"] = length + 1
    return logits, new_cache
