"""The decode step's share of its roofline: the bytes it must read (the
weights held here, and the latent cache of the positions the traced
stretch's slots occupied, ``serve_counts``) over peak HBM bandwidth, over
the step's device time, read from the trace's program events."""
from bench import xprof


def read(record):
    ex = record.get("extras") or {}
    if "decode_program" not in ex:
        return None
    secs, runs = xprof.module_seconds(record["profile"], ex["decode_program"])
    if runs == 0 or secs <= 0:
        return None
    least = ex["decode_bytes"] / record["peak"]["hbm_bytes_per_s"]
    return 100.0 * least / (secs / runs)
