"""Transitions collected in the window, with every iteration's SAC
updates inside it, over the window's wall time (host clock, every
iteration finished)."""


def read(record):
    w = record["window"]
    return w["transitions"] / w["wall_s"] if "transitions" in w else None
