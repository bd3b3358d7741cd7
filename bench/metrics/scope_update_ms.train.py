"""Device time per training iteration of the SAC update: the busy time
under the ``sac.update`` scope (sample, losses, gradients, Adam, Polyak)
in whole runs of the iteration in the traced stretch
(``bench/scopes.py``)."""
from bench import scopes


def read(record):
    r = scopes.reduce(record)
    return None if r is None else r["sac.update"]
