"""Decoder-only transformer LM covering the dense / MoE / SWA families
(starcoder2, granite, h2o-danube, qwen1.5, dbrx, kimi-k2, chameleon).

Pure functional JAX: ``init_params`` -> pytree; ``forward`` (teacher forcing),
``prefill`` and ``decode_step`` share block code.  Layers are stacked on a
leading axis and iterated with ``lax.scan`` (+ optional per-layer remat) so
compile time stays flat in depth.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.api import constrain
from repro.models import layers, moe as moe_lib


def _dtype(cfg):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_attn(key, cfg, dtype) -> dict:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    p = {
        "wq": layers.dense_init(ks[0], (d, h, dh), dtype),
        "wk": layers.dense_init(ks[1], (d, kv, dh), dtype),
        "wv": layers.dense_init(ks[2], (d, kv, dh), dtype),
        "wo": layers.dense_init(ks[3], (h, dh, d), dtype, scale=1.0 / (2 * cfg.n_layers) ** 0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, dh), dtype)
        p["bk"] = jnp.zeros((kv, dh), dtype)
        p["bv"] = jnp.zeros((kv, dh), dtype)
    return p


def init_mlp(key, cfg, dtype) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": layers.dense_init(ks[0], (d, f), dtype),
        "w_up": layers.dense_init(ks[1], (d, f), dtype),
        "w_down": layers.dense_init(ks[2], (f, d), dtype,
                                    scale=1.0 / (2 * cfg.n_layers) ** 0.5),
    }


def init_block(key, cfg, dtype, use_moe: bool) -> dict:
    k1, k2 = jax.random.split(key)
    p = {
        "attn_norm": jnp.zeros((cfg.d_model,), dtype),
        "mlp_norm": jnp.zeros((cfg.d_model,), dtype),
        "attn": init_attn(k1, cfg, dtype),
    }
    if use_moe:
        p["moe"] = moe_lib.init_moe(k2, cfg, dtype)
    else:
        p["mlp"] = init_mlp(k2, cfg, dtype)
    return p


def _stack_layers(key, n, init_one):
    keys = jax.random.split(key, max(n, 1))[:n]
    if n == 0:
        return None
    return jax.vmap(init_one)(keys)


def init_params(key, cfg) -> dict:
    dtype = _dtype(cfg)
    k_emb, k_layers, k_dense, k_head = jax.random.split(key, 4)
    params = {
        "embed": layers.embed_init(k_emb, (cfg.vocab_padded, cfg.d_model), dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.dense_init(
            k_head, (cfg.d_model, cfg.vocab_padded), dtype)
    if cfg.family == "moe":
        n_moe = cfg.n_layers - cfg.n_dense_layers
        params["moe_layers"] = _stack_layers(
            k_layers, n_moe, lambda k: init_block(k, cfg, dtype, True))
        if cfg.n_dense_layers:
            params["dense_layers"] = _stack_layers(
                k_dense, cfg.n_dense_layers, lambda k: init_block(k, cfg, dtype, False))
    else:
        params["layers"] = _stack_layers(
            k_layers, cfg.n_layers, lambda k: init_block(k, cfg, dtype, False))
    return params


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _qkv(p, cfg, x, positions):
    """x: (B, S, d) -> q (B,S,H,dh), k/v (B,S,KV,dh) with RoPE applied."""
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k = jnp.einsum("bsd,dke->bske", x, p["wk"])
    v = jnp.einsum("bsd,dke->bske", x, p["wv"])
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = layers.apply_rope(
        q.transpose(0, 2, 1, 3), positions[:, None, :], cfg.rope_theta
    ).transpose(0, 2, 1, 3)
    k = layers.apply_rope(
        k.transpose(0, 2, 1, 3), positions[:, None, :], cfg.rope_theta
    ).transpose(0, 2, 1, 3)
    return q, k, v


def attention_full(p, cfg, x, positions):
    """Full-sequence attention (train / prefill). Returns (out, k, v).

    cfg.attn_impl selects the XLA blockwise path (CPU / dry-run) or the
    Pallas TPU flash-attention kernel (interpret-mode on CPU)."""
    q, k, v = _qkv(p, cfg, x, positions)
    window = cfg.window if cfg.attention == "swa" else 0
    if cfg.attn_impl == "pallas":
        from repro.kernels.flash_attn.ops import flash_attn
        o = flash_attn(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                       v.transpose(0, 2, 1, 3), causal=True, window=window,
                       block_q=min(cfg.attn_block_q, 128),
                       block_kv=min(cfg.attn_block_kv, 128)
                       ).transpose(0, 2, 1, 3)
    else:
        o = layers.blockwise_attention(
            q, k, v, causal=True, window=window,
            block_q=cfg.attn_block_q, block_kv=cfg.attn_block_kv)
    out = jnp.einsum("bshe,hed->bsd", o, p["wo"])
    return out, k, v


def attention_decode(p, cfg, x, pos, k_cache, v_cache, kv_pos):
    """x: (B, 1, d); caches (B, S, KV, dh). Returns (out, k_new, v_new)."""
    B = x.shape[0]
    positions = jnp.broadcast_to(jnp.asarray(pos).reshape(-1, 1), (B, 1))
    q, k, v = _qkv(p, cfg, x, positions)
    o = layers.decode_attention(q[:, 0], k_cache, v_cache, kv_pos,
                                positions[:, 0])
    out = jnp.einsum("bhe,hed->bd", o, p["wo"])[:, None]
    return out, k[:, 0], v[:, 0]


def mlp_block(p, cfg, x):
    return layers.swiglu(x, p["w_gate"], p["w_up"], p["w_down"])


def block_forward(p, cfg, x, positions, use_moe: bool):
    if cfg.seq_parallel:
        x = constrain(x, "batch", "seq", None)
    h, k, v = attention_full(p["attn"], cfg,
                             layers.rms_norm(x, p["attn_norm"], cfg.norm_eps),
                             positions)
    x = x + h
    if cfg.seq_parallel:
        x = constrain(x, "batch", "seq", None)
    y = layers.rms_norm(x, p["mlp_norm"], cfg.norm_eps)
    if use_moe:
        B, S, d = y.shape
        out, aux = moe_lib.moe_block(p["moe"], y.reshape(B * S, d), cfg)
        out = out.reshape(B, S, d)
    else:
        out, aux = mlp_block(p["mlp"], cfg, y), jnp.zeros((), jnp.float32)
    return x + out, aux


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------


def _scan_blocks(stacked, cfg, x, positions, use_moe):
    def body(carry, lp):
        h, aux = carry
        h, a = block_forward(lp, cfg, h, positions, use_moe)
        return (h, aux + a), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    if cfg.scan_layers:
        (x, aux), _ = jax.lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)), stacked)
    else:
        aux = jnp.zeros((), jnp.float32)
        n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
        for i in range(n):
            lp = jax.tree.map(lambda a: a[i], stacked)
            (x, aux), _ = body_fn((x, aux), lp)
    return x, aux


def forward(params: dict, cfg, tokens: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """tokens: (B, S) int32 -> (logits (B, S, Vp), aux_loss)."""
    B, S = tokens.shape
    x = params["embed"][tokens].astype(jnp.dtype(cfg.compute_dtype))
    x = constrain(x, "batch", None, None)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "moe":
        if cfg.n_dense_layers:
            x, a = _scan_blocks(params["dense_layers"], cfg, x, positions, False)
            aux += a
        x, a = _scan_blocks(params["moe_layers"], cfg, x, positions, True)
        aux += a
    else:
        x, a = _scan_blocks(params["layers"], cfg, x, positions, False)
        aux += a
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, cfg, x)
    return logits, aux


def unembed(params, cfg, x, out_dtype=None):
    """x (B, S, d) -> logits (B, S, Vp), the padded vocabulary masked;
    ``out_dtype`` float32 accumulates the head's product in float32 and
    returns it so."""
    x = constrain(x, "batch", None, None)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"],
                            preferred_element_type=out_dtype)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                            preferred_element_type=out_dtype)
    logits = constrain(logits, "batch", None, "vocab")
    if cfg.vocab_padded != cfg.vocab:
        pad_mask = jnp.arange(cfg.vocab_padded) >= cfg.vocab
        logits = jnp.where(pad_mask, -1e9, logits.astype(jnp.float32)).astype(logits.dtype)
        logits = constrain(logits, "batch", None, "vocab")
    return logits


# --------------------------- KV cache ---------------------------------------


def cache_len(cfg, max_len: int) -> int:
    if cfg.attention == "swa":
        return min(cfg.window, max_len)
    return max_len


def init_cache(cfg, batch: int, max_len: int) -> dict:
    """Slot-based cache: ``pos`` is PER SEQUENCE (B,) so a continuous-
    batching engine can stagger requests across slots."""
    S = cache_len(cfg, max_len)
    kv, dh = cfg.n_kv_heads, cfg.d_head
    dtype = jnp.dtype(cfg.compute_dtype)
    return {
        "k": jnp.zeros((cfg.n_layers, batch, S, kv, dh), dtype),
        "v": jnp.zeros((cfg.n_layers, batch, S, kv, dh), dtype),
        "kv_pos": jnp.full((batch, S), -1, jnp.int32),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def _layer_stacks(params, cfg):
    """Yield (stacked_params, use_moe, n_layers) in execution order."""
    if cfg.family == "moe":
        out = []
        if cfg.n_dense_layers:
            out.append((params["dense_layers"], False, cfg.n_dense_layers))
        out.append((params["moe_layers"], True, cfg.n_layers - cfg.n_dense_layers))
        return out
    return [(params["layers"], False, cfg.n_layers)]


def decode_step(params: dict, cfg, cache: dict, token: jax.Array) -> Tuple[jax.Array, dict]:
    """token: (B,) int32. One autoregressive step; updates the cache.
    Per-sequence positions: cache["pos"] is (B,) so slots may be staggered
    (continuous batching)."""
    B = token.shape[0]
    pos = jnp.broadcast_to(cache["pos"], (B,))
    S = cache["k"].shape[2]
    if cfg.attention == "swa":
        slot = pos % S  # ring buffer over the window
    else:
        slot = jnp.minimum(pos, S - 1)
    bidx = jnp.arange(B)
    x = params["embed"][token][:, None].astype(jnp.dtype(cfg.compute_dtype))
    kv_pos = cache["kv_pos"].at[bidx, slot].set(pos)

    new_k, new_v = [], []
    offset = 0
    for stacked, use_moe, n in _layer_stacks(params, cfg):
        ck = jax.lax.dynamic_slice_in_dim(cache["k"], offset, n, axis=0)
        cv = jax.lax.dynamic_slice_in_dim(cache["v"], offset, n, axis=0)

        # the current token's K/V must be inserted into the cache *before*
        # attending (self-attention includes the current token)
        def body2(h, xs):
            lp, k_l, v_l = xs
            hn = layers.rms_norm(h, lp["attn_norm"], cfg.norm_eps)
            positions = pos[:, None]
            q, k, v = _qkv(lp["attn"], cfg, hn, positions)
            k_l = k_l.at[bidx, slot].set(k[:, 0])
            v_l = v_l.at[bidx, slot].set(v[:, 0])
            o = layers.decode_attention(q[:, 0], k_l, v_l, kv_pos, pos)
            attn_out = jnp.einsum("bhe,hed->bd", o, lp["attn"]["wo"])[:, None]
            h = h + attn_out
            y = layers.rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
            if use_moe:
                out, _ = moe_lib.moe_block(lp["moe"], y.reshape(B, -1), cfg)
                out = out.reshape(B, 1, -1)
            else:
                out = mlp_block(lp["mlp"], cfg, y)
            return h + out, (k_l, v_l)

        x, (ks, vs) = jax.lax.scan(body2, x, (stacked, ck, cv))
        new_k.append(ks)
        new_v.append(vs)
        offset += n

    cache = dict(cache)
    cache["k"] = jnp.concatenate(new_k, axis=0) if len(new_k) > 1 else new_k[0]
    cache["v"] = jnp.concatenate(new_v, axis=0) if len(new_v) > 1 else new_v[0]
    cache["kv_pos"] = kv_pos
    cache["pos"] = pos + 1

    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, cfg, x)[:, 0]
    return logits, cache


def prefill(params: dict, cfg, tokens: jax.Array, max_len: int,
            lengths: Optional[jax.Array] = None) -> Tuple[jax.Array, dict]:
    """Process the prompt; returns (next-token logits, primed cache).

    ``lengths`` (B,) enables right-padded variable-length prompts (serving
    engine path): logits are taken at position lengths-1 and padded cache
    entries are masked out.  With lengths=None the whole row is the prompt
    (training/dry-run path — only last-position logits are computed).
    """
    B, S = tokens.shape
    x = params["embed"][tokens].astype(jnp.dtype(cfg.compute_dtype))
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    all_k, all_v = [], []
    for stacked, use_moe, n in _layer_stacks(params, cfg):
        def body(h, lp):
            if cfg.seq_parallel:
                h = constrain(h, "batch", "seq", None)
            hn = layers.rms_norm(h, lp["attn_norm"], cfg.norm_eps)
            attn_out, k, v = attention_full(lp["attn"], cfg, hn, positions)
            h = h + attn_out
            if cfg.seq_parallel:
                h = constrain(h, "batch", "seq", None)
            y = layers.rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
            if use_moe:
                out, _ = moe_lib.moe_block(lp["moe"], y.reshape(B * S, -1), cfg)
                out = out.reshape(B, S, -1)
            else:
                out = mlp_block(lp["mlp"], cfg, y)
            return h + out, (k, v)

        body_fn = jax.checkpoint(body) if cfg.remat else body
        x, (ks, vs) = jax.lax.scan(body_fn, x, stacked)
        all_k.append(ks)
        all_v.append(vs)

    k = jnp.concatenate(all_k, axis=0) if len(all_k) > 1 else all_k[0]
    v = jnp.concatenate(all_v, axis=0) if len(all_v) > 1 else all_v[0]

    C = cache_len(cfg, max_len)
    if cfg.attention == "swa" and S > C:
        # keep the last `window` tokens, aligned to ring slots
        start = S - C
        k = jax.lax.dynamic_slice_in_dim(k, start, C, axis=2)
        v = jax.lax.dynamic_slice_in_dim(v, start, C, axis=2)
        kept_pos = jnp.arange(start, S, dtype=jnp.int32)
        # place position p at slot p % C
        slots = kept_pos % C
        k = k[:, :, jnp.argsort(slots)]
        v = v[:, :, jnp.argsort(slots)]
        kv_pos = jnp.zeros((B, C), jnp.int32).at[:, slots].set(kept_pos[None])
    else:
        pad = C - S
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.concatenate(
            [jnp.broadcast_to(positions, (B, S)),
             jnp.full((B, pad), -1, jnp.int32)], axis=1)

    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if lengths is None:
        cache = {"k": k, "v": v, "kv_pos": kv_pos,
                 "pos": jnp.full((B,), S, jnp.int32)}
        logits = unembed(params, cfg, x[:, -1:])[:, 0]
        return logits, cache
    # variable-length: mask padded cache slots, per-sequence positions
    valid = kv_pos < lengths[:, None]
    kv_pos = jnp.where(valid & (kv_pos >= 0), kv_pos, -1)
    cache = {"k": k, "v": v, "kv_pos": kv_pos,
             "pos": lengths.astype(jnp.int32)}
    x_last = jnp.take_along_axis(
        x, jnp.maximum(lengths - 1, 0)[:, None, None].astype(jnp.int32), axis=1)
    logits = unembed(params, cfg, x_last)[:, 0]
    return logits, cache
