"""Model FLOPs of the tokens prefilled and decoded in the window
(``serve_counts.prefill_flops`` and ``decode_flops``, from the server's
iteration log) per second, as a share of the chip's bf16 peak."""


def read(record):
    w = record["window"]
    if "model_flops" not in w:
        return None
    peak = record["peak"]["bf16_flops_per_s"] * record["device"]["count"]
    return 100.0 * w["model_flops"] / w["wall_s"] / peak
