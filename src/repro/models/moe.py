"""Mixture-of-Experts layer: top-k routing with capacity-based dispatch.

Two execution paths sharing the same math:

* ``_moe_local``  — single-device / no-mesh path: global scatter dispatch
  into (E, C, d) buffers.  Used on CPU (tests, smoke runs).

* ``_moe_sharded`` — expert-parallel path under an active mesh policy,
  written with ``jax.shard_map``: every (data, model) device routes ITS
  token shard to ITS expert shard with a purely local scatter, runs the
  local expert GEMMs, combines locally and ``psum``s the partial outputs
  over the ``model`` axis.  This avoids GSPMD's replicated-scatter fallback
  (which materializes (T*k, d) global buffers — 240 GB/device for the 1T
  MoE) and makes the collective cost explicit: exactly one psum of the
  (T_local, d) activations per MoE layer in forward (+ its transpose in
  backward), the same volume a dense TP MLP pays.

Capacity is rounded to a multiple of 128 so buffers stay MXU/shard friendly;
overflow tokens are dropped exactly like capacity-factor dropping in GShard.

A served expert may not drop tokens.  ``held_moe_block`` is the dropless
layer of the DeepSeek-V2 kind: it is told the ids of the routed experts
this device holds, routes every token over all of them, and computes its
own experts' share of the result with grouped matrix products over the
tokens sorted by expert, plus the shared experts that every token passes.
On one device it runs without the exchange that expert parallelism adds.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.core import spans
from repro.distributed.api import current_policy
from repro.models import layers


def init_moe(key, cfg, dtype) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    return {
        "router": layers.dense_init(ks[0], (d, e), jnp.float32),
        "w_gate": layers.dense_init(ks[1], (e, d, f), dtype),
        "w_up": layers.dense_init(ks[2], (e, d, f), dtype),
        "w_down": layers.dense_init(ks[3], (e, f, d), dtype),
    }


def route_topk(logits: jax.Array, top_k: int, normalize: bool = True,
               scale: float = 1.0) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (gates (T,k) fp32, expert_ids (T,k) int32, probs): the top-k
    softmax scores, renormalised to sum 1 where ``normalize``, times
    ``scale``."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, ids = jax.lax.top_k(probs, top_k)
    if normalize:
        gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True),
                                    1e-9)
    return gates * scale, ids.astype(jnp.int32), probs


def _slot_in_expert(expert_ids_flat: jax.Array, n_experts: int) -> jax.Array:
    """slot[i] = number of earlier assignments to the same expert (sort-free
    position assignment via run-position within the stable sort)."""
    a = expert_ids_flat.shape[0]
    order = jnp.argsort(expert_ids_flat, stable=True)
    sorted_ids = expert_ids_flat[order]
    counts = jnp.bincount(sorted_ids, length=n_experts)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    slots_sorted = jnp.arange(a, dtype=jnp.int32) - starts[sorted_ids].astype(jnp.int32)
    inv = jnp.zeros((a,), jnp.int32).at[order].set(jnp.arange(a, dtype=jnp.int32))
    return slots_sorted[inv]


def _capacity(T: int, cfg) -> int:
    c = int(max(cfg.top_k, (T * cfg.top_k * cfg.capacity_factor) / cfg.n_experts))
    return max(8, (c + 127) // 128 * 128) if T >= 1024 else c


def _aux_loss(probs: jax.Array, ids: jax.Array, e: int) -> jax.Array:
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(ids[:, 0], e, dtype=jnp.float32), axis=0)
    return e * jnp.sum(me * ce)


def _expert_ffn(xin, params):
    h = jnp.einsum("ecd,edf->ecf", xin, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xin, params["w_up"])
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, params["w_down"])


# ---------------------------------------------------------------------------
# Local (no-mesh) path
# ---------------------------------------------------------------------------


def _moe_local(params: dict, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    T, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    capacity = _capacity(T, cfg)

    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), params["router"])
    gates, ids, probs = route_topk(logits, k)
    aux = _aux_loss(probs, ids, e)

    ids_flat = ids.reshape(-1)
    gates_flat = gates.reshape(-1)
    slot = _slot_in_expert(ids_flat, e)
    token_idx = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    keep = slot < capacity

    xin = jnp.zeros((e, capacity, d), x.dtype)
    xin = xin.at[ids_flat, jnp.where(keep, slot, capacity)].set(
        x[token_idx], mode="drop")
    y = _expert_ffn(xin, params)
    y_tok = y.at[ids_flat, jnp.where(keep, slot, capacity)].get(
        mode="fill", fill_value=0)
    y_tok = y_tok * (gates_flat * keep.astype(jnp.float32))[:, None].astype(y_tok.dtype)
    out = jnp.zeros((T, d), y_tok.dtype).at[token_idx].add(y_tok)
    return out.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Expert-parallel shard_map path
# ---------------------------------------------------------------------------


def _moe_sharded(params: dict, x: jax.Array, cfg, mesh) -> Tuple[jax.Array, jax.Array]:
    T, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape[a]
    model_size = mesh.shape["model"]
    if e % model_size != 0 or T % n_data != 0:
        return _moe_local(params, x, cfg)
    e_local = e // model_size
    t_local = T // n_data
    cap_local = _capacity(t_local, cfg)

    def local_fn(router_w, w_gate, w_up, w_down, x_l):
        tl = x_l.shape[0]
        logits = jnp.einsum("td,de->te", x_l.astype(jnp.float32), router_w)
        gates, ids, probs = route_topk(logits, k)
        aux = _aux_loss(probs, ids, e)
        aux = jax.lax.pmean(aux, data_axes) if data_axes else aux

        ids_flat = ids.reshape(-1)
        gates_flat = gates.reshape(-1)
        slot = _slot_in_expert(ids_flat, e)
        token_idx = jnp.repeat(jnp.arange(tl, dtype=jnp.int32), k)
        keep = slot < cap_local

        m_idx = jax.lax.axis_index("model")
        local_e = ids_flat - m_idx * e_local
        mine = (local_e >= 0) & (local_e < e_local) & keep
        le = jnp.where(mine, local_e, e_local)
        sl = jnp.where(mine, slot, cap_local)

        xin = jnp.zeros((e_local, cap_local, x_l.shape[1]), x_l.dtype)
        xin = xin.at[le, sl].set(x_l[token_idx], mode="drop")
        y = _expert_ffn(xin, {"w_gate": w_gate, "w_up": w_up, "w_down": w_down})
        y_tok = y.at[le, sl].get(mode="fill", fill_value=0)
        w = gates_flat * mine.astype(jnp.float32)
        y_tok = y_tok * w[:, None].astype(y_tok.dtype)
        # combine-psum dtype: fp32 by default; bf16 halves the per-layer
        # all-reduce wire bytes (perf knob for collective-bound MoE)
        psum_dtype = jnp.dtype(getattr(cfg, "moe_psum_dtype", "float32"))
        partial = jnp.zeros((tl, x_l.shape[1]), psum_dtype
                            ).at[token_idx].add(y_tok.astype(psum_dtype))
        out = jax.lax.psum(partial, "model")
        return out.astype(x_l.dtype), aux

    dspec = data_axes if len(data_axes) > 1 else (data_axes[0] if data_axes else None)
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), P("model", None, None), P("model", None, None),
                  P("model", None, None), P(dspec, None)),
        out_specs=(P(dspec, None), P()),
        check_vma=False)
    out, aux = fn(params["router"], params["w_gate"], params["w_up"],
                  params["w_down"], x)
    return out, aux


def moe_block(params: dict, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """x: (T, d) token-major. Returns (out (T, d), aux_loss scalar)."""
    policy = current_policy()
    if policy is not None and "model" in policy.mesh.shape \
            and policy.mesh.shape["model"] > 1:
        return _moe_sharded(params, x, cfg, policy.mesh)
    return _moe_local(params, x, cfg)


# ---------------------------------------------------------------------------
# Dropless layer over the routed experts a device holds, with shared experts
# ---------------------------------------------------------------------------


def init_held_moe(key, cfg, dtype) -> dict:
    """Gate over all ``cfg.n_experts``, the shared experts as one MLP, and
    the routed experts whose ids ``cfg.held`` lists.  Expert ``e``'s weights
    come from ``fold_in(key, e)``, so every share of the experts holds the
    same weights for the same id."""
    d, fe, e = cfg.d_model, cfg.expert_width, cfg.n_experts
    fs = cfg.n_shared_experts * fe
    k_gate, k_shared, k_routed = jax.random.split(key, 3)
    ks = jax.random.split(k_shared, 3)
    down = 1.0 / (2 * cfg.n_layers) ** 0.5
    nrm = layers.scaled_normal

    def expert(eid):
        kk = jax.random.split(jax.random.fold_in(k_routed, eid), 3)
        return {"w_gate": nrm(kk[0], (d, fe), dtype, d),
                "w_up": nrm(kk[1], (d, fe), dtype, d),
                "w_down": nrm(kk[2], (fe, d), dtype, fe, down)}

    p = {"gate": nrm(k_gate, (d, e), dtype, d),
         "routed": jax.vmap(expert)(jnp.asarray(cfg.held, jnp.int32))}
    if fs:
        p["shared"] = {"w_gate": nrm(ks[0], (d, fs), dtype, d),
                       "w_up": nrm(ks[1], (d, fs), dtype, d),
                       "w_down": nrm(ks[2], (fs, d), dtype, fs, down)}
    return p


def held_expert_ffn(params: dict, x: jax.Array, ids: jax.Array,
                    gates: jax.Array, held: jax.Array):
    """The held experts' share of the routed output, dropping no token.

    x (T, d); ids, gates (T, k) over all experts; held (H,) ids.  The T*k
    assignments are sorted by held expert, those to experts held elsewhere
    last; each held expert's rows go through its SwiGLU in one grouped
    product (``lax.ragged_dot``), the rest are left out.  Returns the
    (T, d) share and the (H,) tokens each held expert took."""
    T, d = x.shape
    k = ids.shape[1]
    n_held = held.shape[0]
    match = ids.reshape(-1)[:, None] == held[None, :]          # (T*k, H)
    local = jnp.where(jnp.any(match, -1), jnp.argmax(match, -1), n_held)
    order = jnp.argsort(local, stable=True)
    sizes = jnp.sum(match, axis=0, dtype=jnp.int32)
    tok = order // k
    rows = x[tok]
    w = params["routed"]
    h = jax.lax.ragged_dot(rows, w["w_gate"], sizes)
    u = jax.lax.ragged_dot(rows, w["w_up"], sizes)
    y = jax.lax.ragged_dot(jax.nn.silu(h) * u, w["w_down"], sizes)
    mine = local[order] < n_held
    g = gates.reshape(-1)[order]
    y = jnp.where(mine[:, None], y.astype(jnp.float32) * g[:, None], 0.0)
    out = jnp.zeros((T, d), jnp.float32).at[tok].add(y)
    return out.astype(x.dtype), sizes


def held_moe_block(params: dict, x: jax.Array, cfg):
    """x (T, d) -> (out (T, d), tokens per held expert (H,)): the shared
    experts plus the held routed experts' share, gates as DeepSeek-V2's
    (softmax scores in float32, top-k, renormalised only where
    ``cfg.norm_topk_prob``, times ``cfg.routed_scaling_factor``)."""
    logits = jnp.dot(x.astype(jnp.float32),
                     params["gate"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    gates, ids, _ = route_topk(logits, cfg.top_k, cfg.norm_topk_prob,
                               cfg.routed_scaling_factor)
    with jax.named_scope(spans.ROUTED):
        out, sizes = held_expert_ffn(params, x, ids, gates,
                                     jnp.asarray(cfg.held, jnp.int32))
    if "shared" in params:
        with jax.named_scope(spans.SHARED):
            s = params["shared"]
            out = out + layers.swiglu(x, s["w_gate"], s["w_up"], s["w_down"])
    return out, sizes
