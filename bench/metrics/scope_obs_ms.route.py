"""Device time per chunk of the observation build: the busy time under
the ``step.obs`` scope (``features.build_obs``) in whole runs of the
chunk in the traced stretch (``bench/scopes.py``)."""
from bench import scopes


def read(record):
    r = scopes.reduce(record)
    return None if r is None else r["step.obs"]
