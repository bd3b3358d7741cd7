"""Device time per chunk of the engine: the busy time under the
``engine.advance`` scope (``engine.advance_all``, whichever backend runs)
in whole runs of the chunk in the traced stretch (``bench/scopes.py``)."""
from bench import scopes


def read(record):
    r = scopes.reduce(record)
    return None if r is None else r["engine.advance"]
