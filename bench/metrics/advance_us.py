"""Device time of one engine advance at the cell's fleet: the traced call
of the engine drive (``advance_drive``), read from the trace's program
events, over the windows it advanced."""
from bench import xprof


def read(record):
    ex = record.get("extras") or {}
    if "advance_program" not in ex:
        return None
    secs, runs = xprof.module_seconds(record["profile"], ex["advance_program"])
    if runs == 0:
        return None
    return secs / ex["advance_calls"] * 1e6
