"""Model FLOPs of the routing decisions in the window (the HAN and actor
forward per decision, ``counts.policy_forward_flops``) per second, as a
share of the chip's bf16 peak (default-precision float32 products)."""


def read(record):
    flops = record["counts"].get("policy_flops_per_decision")
    w = record["window"]
    if flops is None or "decisions" not in w:
        return None
    peak = record["peak"]["bf16_flops_per_s"] * record["device"]["count"]
    return 100.0 * flops * w["decisions"] / w["wall_s"] / peak
