"""Routing decisions through ``env.step`` in the window, summed over the
cell's envs, over the window's wall time (host clock, every chunk
finished)."""


def read(record):
    w = record["window"]
    return w["decisions"] / w["wall_s"] if "decisions" in w else None
