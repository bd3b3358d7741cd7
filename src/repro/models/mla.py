"""DeepSeek-V2 decoder: multi-head latent attention (MLA) with YaRN rope, a
leading dense layer, then MoE layers of shared and routed experts.

Written as ``modeling_deepseek.py`` of the DeepSeek-V2 source repository
defines the model, for ``q_lora_rank`` null (queries projected directly):

* attention.  ``q = x Wq`` splits into ``q_nope`` (``qk_nope_head_dim``)
  and ``q_pe`` (``qk_rope_head_dim``) per head; ``x Wkv_a`` gives the
  latent ``c`` (``kv_lora_rank``, RMS-normed) and one rope key ``k_pe``
  shared by the heads; ``c`` expands to ``k_nope`` (``W_uk``) and ``v``
  (``W_uv``).  Scores are ``q_nope.k_nope + q_pe.k_pe`` times
  ``(qk_nope + qk_rope)^-0.5 * m^2``, ``m = 0.1 * mscale_all_dim *
  ln(factor) + 1``, softmax in float32, then ``Wo``;
* rope.  YaRN frequencies (``yarn_find_correction_range`` and the linear
  ramp between the interpolated and the original frequencies), cos/sin
  times ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``, and
  the source's pair order: the interleaved pairs are first gathered into
  even and odd halves and then rotated half against half;
* experts.  ``moe.held_moe_block``: softmax gates in float32 over all
  routed experts, top-k, renormalised only where ``norm_topk_prob``, times
  ``routed_scaling_factor``, plus the shared experts as one MLP;
* RMSNorm as the source's: normalise in float32, cast, times the weight.

The cache holds, per layer and position, the normed latent ``c`` and the
roped ``k_pe``: ``kv_lora_rank + qk_rope_head_dim`` values in place of the
expanded keys and values of every head.  Prefill expands the latent into
keys and values; decode reads only the latent cache, with ``W_uk`` folded
into the query and ``W_uv`` into the output (the "absorbed" form), and
writes each layer's new entry into the cache in place.

Departures from the source: the weights are random from a seed; the
vocabulary is padded to ``cfg.vocab_padded`` with the padded logits
masked, as the other decoders of this package do; only the routed experts
``cfg.held`` lists are held and computed (``moe.held_moe_block``); the
cache also carries ``load``, the tokens each held expert took in each MoE
layer, summed over decode steps.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spans
from repro.models import layers, moe as moe_lib, transformer


# ---------------------------------------------------------------------------
# YaRN rope
# ---------------------------------------------------------------------------


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _correction_dim(rotations: float, dim: int, base: float,
                    max_pos: int) -> float:
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))
            / (2 * math.log(base)))


def inv_freq(cfg) -> np.ndarray:
    """(qk_rope_head_dim // 2,) float32 rope frequencies, YaRN-blended
    where ``cfg.yarn_factor`` is set."""
    r, base = cfg.qk_rope_head_dim, cfg.rope_theta
    expo = np.arange(0, r, 2, dtype=np.float32) / r
    extra = (1.0 / base ** expo).astype(np.float32)
    if not cfg.yarn_factor:
        return extra
    inter = (1.0 / (cfg.yarn_factor * base ** expo)).astype(np.float32)
    orig = cfg.yarn_original_max_position
    low = max(math.floor(_correction_dim(cfg.yarn_beta_fast, r, base, orig)), 0)
    high = min(math.ceil(_correction_dim(cfg.yarn_beta_slow, r, base, orig)),
               r - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(r // 2, dtype=np.float32) - low) / (high - low),
                   0, 1)
    keep = 1.0 - ramp        # share of the original (extrapolated) frequency
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def rope_mscale(cfg) -> float:
    """Factor on cos and sin."""
    if not cfg.yarn_factor:
        return 1.0
    return (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
            / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))


def softmax_scale(cfg) -> float:
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        m = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
        s = s * m * m
    return s


def rope(x: jax.Array, positions: jax.Array, cfg) -> jax.Array:
    """x (..., S, [heads,] r) at ``positions`` (..., S): the source's pair
    order (interleaved pairs gathered into halves, rotated half against
    half); the result stays in that order, for queries and keys alike."""
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq(cfg))
    if x.ndim == positions.ndim + 2:          # a heads axis
        ang = ang[..., None, :]
    m = rope_mscale(cfg)
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return xf.astype(x.dtype) * w.astype(x.dtype)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_attn(key, cfg, dtype) -> dict:
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rp, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 5)
    nrm = layers.scaled_normal
    return {
        "wq": nrm(ks[0], (d, h, nope + rp), dtype, d),
        "wkv_a": nrm(ks[1], (d, r + rp), dtype, d),
        "kv_norm": jnp.ones((r,), dtype),
        "w_uk": nrm(ks[2], (r, h, nope), dtype, r),
        "w_uv": nrm(ks[3], (r, h, vd), dtype, r),
        "wo": nrm(ks[4], (h, vd, d), dtype, h * vd,
                  1.0 / (2 * cfg.n_layers) ** 0.5),
    }


def init_block(key, cfg, dtype, use_moe: bool) -> dict:
    k1, k2 = jax.random.split(key)
    p = {"attn_norm": jnp.ones((cfg.d_model,), dtype),
         "mlp_norm": jnp.ones((cfg.d_model,), dtype),
         "attn": init_attn(k1, cfg, dtype)}
    if use_moe:
        p["moe"] = moe_lib.init_held_moe(k2, cfg, dtype)
    else:
        p["mlp"] = transformer.init_mlp(k2, cfg, dtype)
    return p


def init_params(key, cfg) -> dict:
    dtype = jnp.dtype(cfg.param_dtype)
    d, vp = cfg.d_model, cfg.vocab_padded
    k_emb, k_dense, k_moe, k_head = jax.random.split(key, 4)
    n_moe = cfg.n_layers - cfg.n_dense_layers
    stack = lambda k, n, use_moe: jax.vmap(
        lambda kk: init_block(kk, cfg, dtype, use_moe))(jax.random.split(k, n))
    params = {"embed": layers.embed_init(k_emb, (vp, d), dtype),
              "final_norm": jnp.ones((d,), dtype),
              "lm_head": layers.scaled_normal(k_head, (d, vp), dtype, d),
              "moe_layers": stack(k_moe, n_moe, True)}
    if cfg.n_dense_layers:
        params["dense_layers"] = stack(k_dense, cfg.n_dense_layers, False)
    return params


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def attn_inputs(p, cfg, x, positions):
    """x (B, S, d) normed -> q_nope (B,S,H,n), q_pe (B,S,H,r) roped, the
    normed latent c (B,S,R) and the roped shared key k_pe (B,S,r)."""
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    q_nope, q_pe = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    kv = jnp.einsum("bsd,de->bse", x, p["wkv_a"])
    c, k_pe = jnp.split(kv, [cfg.kv_lora_rank], axis=-1)
    c = rms_norm(c, p["kv_norm"], cfg.norm_eps)
    return (q_nope, rope(q_pe, positions, cfg), c,
            rope(k_pe, positions, cfg))


def attention_expanded(p, cfg, x, positions):
    """Causal self-attention over the sequence with the latent expanded to
    per-head keys and values.  Returns (out (B,S,d), c, k_pe)."""
    q_nope, q_pe, c, k_pe = attn_inputs(p, cfg, x, positions)
    k_nope = jnp.einsum("btc,chn->bthn", c, p["w_uk"])
    v = jnp.einsum("btc,chv->bthv", c, p["w_uv"])
    s = (jnp.einsum("bshn,bthn->bhst", q_nope, k_nope,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bshr,btr->bhst", q_pe, k_pe,
                      preferred_element_type=jnp.float32))
    S = x.shape[1]
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s * softmax_scale(cfg), -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhst,bthv->bshv", pr, v)
    return jnp.einsum("bshv,hvd->bsd", o, p["wo"]), c, k_pe


def attention_absorbed(p, cfg, q_nope, q_pe, c_cache, pe_cache, pos):
    """One query per sequence against the latent cache.  q_nope (B,H,n),
    q_pe (B,H,r); caches (B,S,R) and (B,S,r) holding positions 0..pos;
    ``W_uk`` is folded into the query and ``W_uv`` into the output."""
    q_lat = jnp.einsum("bhn,chn->bhc", q_nope, p["w_uk"])
    s = (jnp.einsum("bhc,bsc->bhs", q_lat, c_cache,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhr,bsr->bhs", q_pe, pe_cache,
                      preferred_element_type=jnp.float32))
    valid = jnp.arange(c_cache.shape[1])[None, :] <= pos[:, None]
    s = jnp.where(valid[:, None], s * softmax_scale(cfg), -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1).astype(c_cache.dtype)
    o_lat = jnp.einsum("bhs,bsc->bhc", pr, c_cache)
    o = jnp.einsum("bhc,chv->bhv", o_lat, p["w_uv"])
    return jnp.einsum("bhv,hvd->bd", o, p["wo"])


def ffn(lp, cfg, y, use_moe: bool):
    """y (..., d) -> (out, tokens per held expert or None)."""
    if not use_moe:
        m = lp["mlp"]
        return layers.swiglu(y, m["w_gate"], m["w_up"], m["w_down"]), None
    lead = y.shape[:-1]
    out, sizes = moe_lib.held_moe_block(lp["moe"], y.reshape(-1, y.shape[-1]),
                                        cfg)
    return out.reshape(*lead, -1), sizes


def unembed(params, cfg, x):
    """x (..., d) -> float32 logits (..., Vp): the source's final norm,
    then the decoders' shared head."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = transformer.unembed(params, cfg, x.reshape(-1, 1, x.shape[-1]),
                                 out_dtype=jnp.float32)
    return logits.reshape(*x.shape[:-1], -1)


# ---------------------------------------------------------------------------
# Full sequence, prefill, cache, decode
# ---------------------------------------------------------------------------


def _run_sequence(params, cfg, tokens):
    """tokens (B, S) -> (final hidden (B,S,d), c (L,B,S,R), k_pe (L,B,S,r))."""
    B, S = tokens.shape
    x = params["embed"][tokens].astype(jnp.dtype(cfg.compute_dtype))
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    cs, pes = [], []
    for stacked, use_moe, _ in transformer._layer_stacks(params, cfg):
        def body(h, lp):
            with jax.named_scope(spans.MLA):
                a, c, k_pe = attention_expanded(
                    lp["attn"], cfg, rms_norm(h, lp["attn_norm"], cfg.norm_eps),
                    positions)
            h = h + a
            out, _ = ffn(lp, cfg, rms_norm(h, lp["mlp_norm"], cfg.norm_eps),
                         use_moe)
            return h + out, (c, k_pe)

        x, (c, k_pe) = jax.lax.scan(body, x, stacked)
        cs.append(c)
        pes.append(k_pe)
    return x, jnp.concatenate(cs, axis=0), jnp.concatenate(pes, axis=0)


def forward(params: dict, cfg, tokens: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """tokens (B, S) -> (logits (B, S, Vp) float32, aux loss 0)."""
    x, _, _ = _run_sequence(params, cfg, tokens)
    return unembed(params, cfg, x), jnp.zeros((), jnp.float32)


def init_cache(cfg, batch: int, max_len: int) -> dict:
    """Per layer, slot and position the normed latent ``c_kv`` and the
    roped ``k_pe``; ``pos`` (B,) is each slot's next position; ``load``
    (MoE layers, held experts) counts the tokens each held expert took in
    the decode steps so far."""
    L, dtype = cfg.n_layers, jnp.dtype(cfg.compute_dtype)
    return {
        "c_kv": jnp.zeros((L, batch, max_len, cfg.kv_lora_rank), dtype),
        "k_pe": jnp.zeros((L, batch, max_len, cfg.qk_rope_head_dim), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
        "load": jnp.zeros((cfg.n_layers - cfg.n_dense_layers,
                           len(cfg.held)), jnp.int32),
    }


def prefill(params: dict, cfg, tokens: jax.Array, max_len: int,
            lengths=None) -> Tuple[jax.Array, dict]:
    """Right-padded prompts (B, S) -> (float32 logits at each prompt's last
    position (B, Vp), the prompts' cache entries: ``c_kv``/``k_pe`` over the
    S positions and ``pos``).  Entries past a prompt's length are never
    read: decode attends to positions up to ``pos`` and writes ``pos``
    first."""
    B, S = tokens.shape
    if S > max_len:
        raise ValueError(f"prompt of {S} positions over max_len {max_len}")
    lengths = (jnp.full((B,), S, jnp.int32) if lengths is None
               else lengths.astype(jnp.int32))
    x, c, k_pe = _run_sequence(params, cfg, tokens)
    last = jnp.take_along_axis(x, jnp.maximum(lengths - 1, 0)[:, None, None],
                               axis=1)[:, 0]
    return unembed(params, cfg, last), {"c_kv": c, "k_pe": k_pe,
                                        "pos": lengths}


def decode_step(params: dict, cfg, cache: dict, token: jax.Array):
    """token (B,) at each slot's ``pos`` -> (float32 logits (B, Vp), the
    cache with every layer's entry for the token written in place, ``pos``
    advanced and ``load`` counted)."""
    with jax.named_scope(spans.DECODE):
        B = token.shape[0]
        pos = cache["pos"]
        S = cache["c_kv"].shape[2]
        slot = jnp.minimum(pos, S - 1)
        bidx = jnp.arange(B)
        x = params["embed"][token].astype(jnp.dtype(cfg.compute_dtype))
        c_all, pe_all, load = cache["c_kv"], cache["k_pe"], cache["load"]
        first = 0
        for stacked, use_moe, n in transformer._layer_stacks(params, cfg):
            def body(carry, xs):
                h, c_all, pe_all = carry
                lp, layer = xs
                with jax.named_scope(spans.MLA):
                    q_nope, q_pe, c, k_pe = attn_inputs(
                        lp["attn"], cfg,
                        rms_norm(h, lp["attn_norm"], cfg.norm_eps)[:, None],
                        pos[:, None])
                    # the token attends to itself: write before reading
                    c_all = c_all.at[layer, bidx, slot].set(c[:, 0])
                    pe_all = pe_all.at[layer, bidx, slot].set(k_pe[:, 0])
                    h = h + attention_absorbed(
                        lp["attn"], cfg, q_nope[:, 0], q_pe[:, 0],
                        c_all[layer], pe_all[layer], pos)
                out, sizes = ffn(lp, cfg,
                                 rms_norm(h, lp["mlp_norm"], cfg.norm_eps),
                                 use_moe)
                return (h + out, c_all, pe_all), sizes

            layer_ids = jnp.arange(first, first + n, dtype=jnp.int32)
            (x, c_all, pe_all), sizes = jax.lax.scan(
                body, (x, c_all, pe_all), (stacked, layer_ids))
            if use_moe:
                load = load + sizes
            first += n
        logits = unembed(params, cfg, x)
        return logits, {"c_kv": c_all, "k_pe": pe_all, "pos": pos + 1,
                        "load": load}
