"""Device time per training iteration of the collect: the busy time
under the ``train.collect`` scope (the collect scan of
``training.make_iteration``, the replay insert included) in whole runs of
the iteration in the traced stretch (``bench/scopes.py``)."""
from bench import scopes


def read(record):
    r = scopes.reduce(record)
    return None if r is None else r["train.collect"]
