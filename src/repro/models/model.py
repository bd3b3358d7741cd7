"""Unified model API: family dispatch + loss + step functions.

Every architecture exposes:
  init_params(rng, cfg)                    -> params pytree
  forward(params, cfg, batch)              -> (logits, aux_loss)
  init_cache(cfg, batch, max_len)          -> serving cache pytree
  prefill(params, cfg, batch, max_len)     -> (logits | cache, ...)
  decode_step(params, cfg, cache, token)   -> (logits, cache)

`batch` is (B, S) int32 tokens for LM families, or
dict(frames (B,S,d), tokens (B,T)) for the enc-dec family.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec, mla, rglru, rwkv6, transformer


def _family_mod(cfg: ModelConfig):
    if cfg.attention == "mla":
        return mla
    if cfg.family in ("dense", "moe"):
        return transformer
    if cfg.family == "ssm":
        return rwkv6
    if cfg.family == "hybrid":
        return rglru
    if cfg.family == "encdec":
        return encdec
    raise ValueError(f"unknown family {cfg.family!r}")


def init_params(key, cfg: ModelConfig):
    return _family_mod(cfg).init_params(key, cfg)


def forward(params, cfg: ModelConfig, batch) -> Tuple[jax.Array, jax.Array]:
    return _family_mod(cfg).forward(params, cfg, batch)


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    return _family_mod(cfg).init_cache(cfg, batch, max_len)


def prefill(params, cfg: ModelConfig, batch, max_len: int, **kw):
    return _family_mod(cfg).prefill(params, cfg, batch, max_len, **kw)


def decode_step(params, cfg: ModelConfig, cache, token):
    return _family_mod(cfg).decode_step(params, cfg, cache, token)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def lm_loss(params, cfg: ModelConfig, batch) -> Tuple[jax.Array, dict]:
    """Next-token cross entropy. batch: dict with 'tokens' (B,S) (+ 'frames'
    for enc-dec); loss over positions [0, S-2] predicting [1, S-1]."""
    if cfg.family == "encdec":
        logits, aux = forward(params, cfg, batch)
        tokens = batch["tokens"]
    else:
        tokens = batch["tokens"]
        logits, aux = forward(params, cfg, tokens)
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    # sharded-vocab-safe cross entropy: no gather over the vocab dim (a
    # take_along_axis here would force GSPMD to all-gather (B,S,V) fp32).
    m = jnp.max(logits, axis=-1)
    e = jnp.exp(logits.astype(jnp.float32) - m.astype(jnp.float32)[..., None])
    lse = m.astype(jnp.float32) + jnp.log(jnp.sum(e, axis=-1))
    onehot = (jnp.arange(logits.shape[-1], dtype=targets.dtype)[None, None]
              == targets[..., None])
    label_logit = jnp.sum(
        jnp.where(onehot, logits.astype(jnp.float32), 0.0), axis=-1)
    nll = lse - label_logit
    mask = (targets >= 0).astype(jnp.float32)
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux_loss": aux,
                   "perplexity": jnp.exp(jnp.minimum(loss, 20.0))}


def count_params(params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
