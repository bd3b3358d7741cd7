"""Device time per training iteration under none of the program's
scopes: what the compiler adds around them, such as layout copies of the
replay buffer, in whole runs of the iteration in the traced stretch
(``bench/scopes.py``)."""
from bench import scopes


def read(record):
    r = scopes.reduce(record)
    return None if r is None else r["unscoped"]
