"""Trips of the engine's lockstep loop per advance: the executions of
one instruction of the loop body (``engine.trip`` scope) over those of
the loop (the ``while`` under ``engine.advance``), in whole runs of the
chunk in the traced stretch (``bench/scopes.py``).  ``None`` where the
loop runs inside one kernel (the Pallas backend): no instruction is one
trip of it."""
from bench import scopes


def read(record):
    r = scopes.reduce(record)
    return None if r is None else r["trips"]
