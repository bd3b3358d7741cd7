"""Device time per decode step of the served model: the busy time under
the ``serve.decode`` scope (``mla.decode_step``, every layer) in whole
runs of the decode step in the traced stretch (``bench/serve_scopes.py``)."""
from bench import serve_scopes


def read(record):
    r = serve_scopes.reduce(record)
    return None if r is None else r.get("serve.decode")
