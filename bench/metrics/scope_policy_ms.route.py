"""Device time per chunk of the router's forward: the busy time under
the ``step.policy`` scope (``sac.act``: the HAN and the actor of each
decision) in whole runs of the chunk in the traced stretch
(``bench/scopes.py``)."""
from bench import scopes


def read(record):
    r = scopes.reduce(record)
    return None if r is None else r["step.policy"]
