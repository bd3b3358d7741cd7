"""DeepSeek-V2-Lite 16B-A2.4B: latent attention (MLA) with YaRN rope, 64
routed experts top-6 with 2 shared experts, one leading dense layer
[hf:deepseek-ai/DeepSeek-V2-Lite, config.json].

``PUBLISHED`` holds the source's ``config.json`` keys that fix the shape;
``from_hf`` maps such a dict, or a cut-down one of the same kind, onto a
``ModelConfig``.
"""
from repro.configs.base import ModelConfig

PUBLISHED = {
    "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10944, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_routed_experts": 64, "n_shared_experts": 2,
    "norm_topk_prob": False, "num_attention_heads": 16,
    "num_experts_per_tok": 6, "num_hidden_layers": 27,
    "num_key_value_heads": 16, "q_lora_rank": None, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "tie_word_embeddings": False,
    "topk_method": "greedy", "v_head_dim": 128, "vocab_size": 102400,
}


def from_hf(hf: dict, name: str = "deepseek-v2-lite", **overrides) -> ModelConfig:
    """A ``ModelConfig`` of the DeepSeek-V2 kind from its ``config.json``
    keys.  Refuses what the serving path does not implement."""
    rs = hf["rope_scaling"] or {}
    unsupported = {
        "q_lora_rank": hf["q_lora_rank"] is not None,
        "hidden_act": hf["hidden_act"] != "silu",
        "scoring_func": hf["scoring_func"] != "softmax",
        "topk_method": hf["topk_method"] != "greedy",
        "moe_layer_freq": hf["moe_layer_freq"] != 1,
        "rope_scaling": bool(rs) and rs.get("type") != "yarn",
    }
    bad = [k for k, v in unsupported.items() if v]
    if bad:
        raise ValueError(f"unsupported DeepSeek-V2 settings: {bad}")
    nope, rope = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    fields = dict(
        name=name, family="moe", attention="mla",
        n_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], d_head=nope + rope,
        d_ff=hf["intermediate_size"], vocab=hf["vocab_size"],
        rope_theta=float(hf["rope_theta"]),
        kv_lora_rank=hf["kv_lora_rank"], qk_nope_head_dim=nope,
        qk_rope_head_dim=rope, v_head_dim=hf["v_head_dim"],
        n_experts=hf["n_routed_experts"], top_k=hf["num_experts_per_tok"],
        n_dense_layers=hf["first_k_dense_replace"],
        d_ff_expert=hf["moe_intermediate_size"],
        n_shared_experts=hf["n_shared_experts"],
        norm_topk_prob=hf["norm_topk_prob"],
        routed_scaling_factor=float(hf["routed_scaling_factor"]),
        tie_embeddings=hf["tie_word_embeddings"],
        norm_eps=hf["rms_norm_eps"], scan_layers=True, remat=False,
    )
    if rs:
        fields.update(
            yarn_factor=float(rs["factor"]),
            yarn_original_max_position=rs["original_max_position_embeddings"],
            yarn_beta_fast=float(rs["beta_fast"]),
            yarn_beta_slow=float(rs["beta_slow"]),
            yarn_mscale=float(rs["mscale"]),
            yarn_mscale_all_dim=float(rs["mscale_all_dim"]))
    fields.update(overrides)
    return ModelConfig(**fields)


CONFIG = from_hf(PUBLISHED, notes="MLA latent cache (512 + 64 per token a "
                 "layer), 64 routed experts top-6 + 2 shared, layer 0 dense.")
