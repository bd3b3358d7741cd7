"""Model FLOPs of a training iteration (``counts.train_iteration_flops``)
over the window's mean iteration time, as a share of the chip's bf16
peak: the router's float32 matrix products run at the default TPU
precision, one bfloat16 pass."""


def read(record):
    flops = record["counts"].get("flops_per_iteration")
    it = record["window"].get("iteration_s")
    if flops is None or it is None:
        return None
    peak = record["peak"]["bf16_flops_per_s"] * record["device"]["count"]
    return 100.0 * flops / it / peak
