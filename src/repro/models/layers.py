"""Shared model layers: norms, RoPE, blockwise (flash-style) attention.

The attention here is the pure-jnp "xla" implementation used for training,
CPU tests and the multi-pod dry-run.  It streams KV blocks with an online
softmax and — crucially for the roofline — enumerates only the (q-block,
kv-block) pairs that are actually needed under causal/sliding-window masks,
so compiled HLO FLOPs stay close to MODEL_FLOPS (no 2x wasted masked work).
The Pallas TPU kernels in ``repro.kernels`` implement the same math.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.api import constrain

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))
    return out.astype(dtype)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32) + b.astype(jnp.float32)
    return out.astype(dtype)


def group_norm_heads(x: jax.Array, w: jax.Array, b: jax.Array, n_heads: int,
                     eps: float = 1e-5) -> jax.Array:
    """GroupNorm with one group per head over the last dim (rwkv output norm)."""
    dtype = x.dtype
    *lead, d = x.shape
    x = x.astype(jnp.float32).reshape(*lead, n_heads, d // n_heads)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    x = x.reshape(*lead, d)
    return (x * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(d_head: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, d_head); positions: (..., S) int32."""
    dtype = x.dtype
    d_head = x.shape[-1]
    freqs = rope_frequencies(d_head, theta)  # (d_head//2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, d/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)


# ---------------------------------------------------------------------------
# Blockwise attention (exact-work flash-style streaming, pure jnp)
# ---------------------------------------------------------------------------


def _block_pairs(n_q: int, n_kv: int, block_q: int, block_kv: int,
                 causal: bool, window: int, kv_offset: int) -> np.ndarray:
    """Static (q-block, kv-block) pairs that contain any unmasked entry.

    kv_offset: absolute position of kv index 0 relative to q index 0
    (0 for self-attention on aligned sequences).
    """
    pairs = []
    for i in range(n_q):
        q_lo, q_hi = i * block_q, (i + 1) * block_q - 1
        for j in range(n_kv):
            k_lo = j * block_kv + kv_offset
            k_hi = (j + 1) * block_kv - 1 + kv_offset
            if causal and k_lo > q_hi:
                continue  # entirely in the future
            if window > 0 and k_hi < q_lo - window + 1:
                continue  # entirely outside the sliding window
            pairs.append((i, j))
    if not pairs:
        pairs = [(0, 0)]
    return np.asarray(pairs, dtype=np.int32)


def blockwise_attention(
    q: jax.Array,  # (B, Sq, H, dh)
    k: jax.Array,  # (B, Skv, KV, dh)
    v: jax.Array,  # (B, Skv, KV, dh)
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 512,
    block_kv: int = 1024,
    kv_offset: int = 0,
) -> jax.Array:
    """Streaming softmax attention with GQA; accumulators in fp32.

    Only block pairs that can contain unmasked entries are visited, so the
    compiled FLOPs match the true masked-attention FLOPs (±block rounding).

    GQA KV heads are expanded to the full H query heads so the head dim
    shards cleanly over the ``model`` mesh axis even when n_kv_heads is not
    divisible by it (each TP shard materializes only the KV heads its query
    heads need).  Scan carries get explicit sharding constraints — GSPMD
    does not reliably propagate shardings into loop carries on its own.
    """
    out_dtype = q.dtype
    B, Sq, H, dh = q.shape
    _, Skv, KV, _ = k.shape
    assert H % KV == 0, (H, KV)
    G = H // KV

    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    pad_q = (-Sq) % block_q
    pad_kv = (-Skv) % block_kv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    Sq_p, Skv_p = Sq + pad_q, Skv + pad_kv
    n_q, n_kv = Sq_p // block_q, Skv_p // block_kv

    # (B, H, S, dh) layout with KV expanded to H (shardable over model axis)
    qh = q.transpose(0, 2, 1, 3)
    kh = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1)
    vh = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1)
    qh = constrain(qh, "batch", "heads", None, None)
    kh = constrain(kh, "batch", "heads", None, None)
    vh = constrain(vh, "batch", "heads", None, None)

    scale = 1.0 / np.sqrt(dh)
    pairs = jnp.asarray(_block_pairs(n_q, n_kv, block_q, block_kv, causal,
                                     window, kv_offset))

    m0 = constrain(jnp.full((B, H, Sq_p), -jnp.inf, jnp.float32),
                   "batch", "heads", None)
    l0 = constrain(jnp.zeros((B, H, Sq_p), jnp.float32),
                   "batch", "heads", None)
    a0 = constrain(jnp.zeros((B, H, Sq_p, dh), jnp.float32),
                   "batch", "heads", None, None)

    q_pos_in = jnp.arange(block_q, dtype=jnp.int32)
    k_pos_in = jnp.arange(block_kv, dtype=jnp.int32)

    def step(carry, pair):
        m, l, acc = carry
        i, j = pair[0], pair[1]
        qb = jax.lax.dynamic_slice_in_dim(qh, i * block_q, block_q, axis=2)
        kb = jax.lax.dynamic_slice_in_dim(kh, j * block_kv, block_kv, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(vh, j * block_kv, block_kv, axis=2)
        s = jnp.einsum("bhqd,bhsd->bhqs", qb, kb,
                       preferred_element_type=jnp.float32) * scale
        qpos = i * block_q + q_pos_in  # (bq,)
        kpos = j * block_kv + k_pos_in + kv_offset  # (bkv,)
        mask = kpos[None, :] < Skv + kv_offset  # kv padding
        if causal:
            mask = mask & (kpos[None, :] <= qpos[:, None])
        if window > 0:
            mask = mask & (kpos[None, :] > qpos[:, None] - window)
        s = jnp.where(mask[None, None], s, -jnp.inf)

        mb = jax.lax.dynamic_slice_in_dim(m, i * block_q, block_q, axis=2)
        lb = jax.lax.dynamic_slice_in_dim(l, i * block_q, block_q, axis=2)
        ab = jax.lax.dynamic_slice_in_dim(acc, i * block_q, block_q, axis=2)

        m_new = jnp.maximum(mb, jnp.max(s, axis=-1))
        # guard fully-masked rows (m_new == -inf)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        corr = jnp.where(jnp.isfinite(mb), jnp.exp(mb - m_safe), 0.0)
        l_new = corr * lb + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqs,bhsd->bhqd", p.astype(vb.dtype), vb,
                        preferred_element_type=jnp.float32)
        a_new = corr[..., None] * ab + pv

        m = jax.lax.dynamic_update_slice_in_dim(m, m_new, i * block_q, axis=2)
        l = jax.lax.dynamic_update_slice_in_dim(l, l_new, i * block_q, axis=2)
        acc = jax.lax.dynamic_update_slice_in_dim(acc, a_new, i * block_q, axis=2)
        return (m, l, acc), None

    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), pairs)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.transpose(0, 2, 1, 3)
    if pad_q:
        out = out[:, :Sq]
    return out.astype(out_dtype)


def plain_attention(q, k, v, *, causal=True, window=0, kv_offset=0):
    """Reference O(S^2)-memory attention (tests / oracle)."""
    out_dtype = q.dtype
    B, Sq, H, dh = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    qh = q.reshape(B, Sq, KV, G, dh)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qh.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(dh)
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Skv)[None, :] + kv_offset
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, dh).astype(out_dtype)


def decode_attention(
    q: jax.Array,       # (B, H, dh) single query token per sequence
    k_cache: jax.Array,  # (B, S, KV, dh)
    v_cache: jax.Array,  # (B, S, KV, dh)
    kv_positions: jax.Array,  # (B, S) int32 absolute positions; -1 = empty
    pos: jax.Array,      # (B,) or scalar: current query position
) -> jax.Array:
    out_dtype = q.dtype
    B, H, dh = q.shape
    _, S, KV, _ = k_cache.shape
    G = H // KV
    qh = q.reshape(B, KV, G, dh)
    s = jnp.einsum("bkgd,bskd->bkgs", qh.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) / np.sqrt(dh)
    pos = jnp.broadcast_to(jnp.asarray(pos), (B,))
    valid = (kv_positions >= 0) & (kv_positions <= pos[:, None])  # (B, S)
    s = jnp.where(valid[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, H, dh).astype(out_dtype)


# ---------------------------------------------------------------------------
# Initializers / small ops
# ---------------------------------------------------------------------------


def dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) >= 2 else shape[-1]
    if len(shape) == 3:  # (d, H, dh): fan_in is d
        fan_in = shape[0]
    std = (scale if scale is not None else 1.0) / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def scaled_normal(key, shape, dtype, fan_in: int, scale: float = 1.0):
    """Normal draws in ``dtype`` with std ``scale / sqrt(fan_in)``."""
    return (jax.random.normal(key, shape, dtype) * (scale / fan_in ** 0.5)
            ).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


def swiglu(x, w_gate, w_up, w_down):
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, w_down)


def geglu(x, w_gate, w_up, w_down):
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", jax.nn.gelu(g) * u, w_down)
