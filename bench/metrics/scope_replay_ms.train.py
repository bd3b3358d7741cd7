"""Device time per training iteration of the replay buffer: the busy
time under the ``replay.insert`` and ``replay.sample`` scopes in whole
runs of the iteration in the traced stretch (``bench/scopes.py``)."""
from bench import scopes


def read(record):
    r = scopes.reduce(record)
    return None if r is None else r["replay.insert"] + r["replay.sample"]
