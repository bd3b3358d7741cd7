"""Bytes and model FLOPs of serving a DeepSeek-V2-kind model, counted from
the configuration (``bench/configs/<config>.json``, the source's keys).

Weights and cache are bfloat16, two bytes a value.  FLOPs count the
multiply-adds of matrix products as two operations each; elementwise work
(norms, rope, softmax, SwiGLU's gate) is left out.  The routed experts
count at the share of a token that reaches the experts held here, ``k *
held / E``: a token picks ``k`` of the ``E`` experts, and only the held
ones are computed on this chip.
"""
from __future__ import annotations

BYTES = 2


def _sizes(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, rp = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    fe = cfg["moe_intermediate_size"]
    held = len(cfg["held_expert_ids"])
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return dict(d=d, h=h, r=r, rp=rp, nope=nope, vd=vd, fe=fe, held=held,
                fs=cfg["n_shared_experts"] * fe, f=cfg["intermediate_size"],
                e=cfg["n_routed_experts_published"],
                k=cfg["num_experts_per_tok"], v=cfg["vocab_size"],
                n_moe=n_moe, n_dense=cfg["first_k_dense_replace"],
                layers=cfg["num_hidden_layers"])


def attention_params(s: dict) -> int:
    return (s["d"] * s["h"] * (s["nope"] + s["rp"]) + s["d"] * (s["r"] + s["rp"])
            + s["r"] + s["r"] * s["h"] * (s["nope"] + s["vd"])
            + s["h"] * s["vd"] * s["d"] + 2 * s["d"])


def held_params(cfg: dict) -> int:
    """Parameters this chip holds, the embedding table included."""
    s = _sizes(cfg)
    moe = (attention_params(s) + 3 * s["d"] * (s["held"] * s["fe"] + s["fs"])
           + s["d"] * s["e"])
    dense = attention_params(s) + 3 * s["d"] * s["f"]
    return (s["n_moe"] * moe + s["n_dense"] * dense + 2 * s["v"] * s["d"]
            + s["d"])


def decode_weight_bytes(cfg: dict, slots: int) -> int:
    """Weights a decode step of ``slots`` tokens must read: every weight
    held here but the embedding table, of which it reads one row a slot.
    Every held expert is read: at 48 slots each takes tokens."""
    s = _sizes(cfg)
    return BYTES * (held_params(cfg) - s["v"] * s["d"] + slots * s["d"])


def latent_cache_bytes(cfg: dict, positions: float) -> float:
    """The latent cache of ``positions`` occupied positions, all layers:
    ``kv_lora_rank + qk_rope_head_dim`` values a position a layer."""
    s = _sizes(cfg)
    return BYTES * positions * s["layers"] * (s["r"] + s["rp"])


def _linear_flops(s: dict) -> float:
    """Per token and all layers: the projections, the dense layer, the
    gate, the shared and the expected held routed experts.  The latent's
    expansion to keys and values (prefill) costs what the absorbed query
    and output (decode) cost."""
    attn = 2 * (s["d"] * s["h"] * (s["nope"] + s["rp"])
                + s["d"] * (s["r"] + s["rp"]) + s["h"] * s["vd"] * s["d"])
    attn += 2 * s["r"] * s["h"] * (s["nope"] + s["vd"])
    moe = (2 * s["d"] * s["e"] + 6 * s["d"] * s["fs"]
           + 6 * s["d"] * s["fe"] * s["k"] * s["held"] / s["e"])
    dense = 6 * s["d"] * s["f"]
    return (s["layers"] * attn + s["n_moe"] * moe + s["n_dense"] * dense)


def prefill_flops(cfg: dict, prompt: int) -> float:
    """One prompt of ``prompt`` tokens: every token's layers, causal
    attention over the expanded latent, and the head at the last token."""
    s = _sizes(cfg)
    pairs = prompt * (prompt + 1) / 2
    attn = 2 * s["h"] * pairs * (s["nope"] + s["rp"] + s["vd"])
    return (prompt * _linear_flops(s) + s["layers"] * attn
            + 2 * s["d"] * s["v"])


def decode_flops(cfg: dict, tokens: int, context: float) -> float:
    """A decode step of ``tokens`` slots attending to ``context`` positions
    in all (their own included): the absorbed attention reads ``kv_lora
    + rope`` values a position for the scores and ``kv_lora`` for the
    output, in every head."""
    s = _sizes(cfg)
    attn = 2 * s["h"] * context * (2 * s["r"] + s["rp"])
    return (tokens * (_linear_flops(s) + 2 * s["d"] * s["v"])
            + s["layers"] * attn)
