"""The engine advance's share of its roofline: the least time the chip
could take for it, the larger of its bytes over peak HBM bandwidth and
its operations over peak (``counts.advance_bytes``/``advance_ops``),
over the measured ``advance_us``."""
from bench import xprof


def read(record):
    ex = record.get("extras") or {}
    if "advance_program" not in ex:
        return None
    secs, runs = xprof.module_seconds(record["profile"], ex["advance_program"])
    if runs == 0 or secs <= 0:
        return None
    per_call = secs / ex["advance_calls"]
    peak = record["peak"]
    least = max(ex["advance_bytes"] / peak["hbm_bytes_per_s"],
                ex["advance_ops"] / peak["bf16_flops_per_s"])
    return 100.0 * least / per_call
