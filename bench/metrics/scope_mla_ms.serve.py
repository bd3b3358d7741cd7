"""Device time per decode step of the latent attention: the busy time
under the ``mla.attn`` scope (the projections, the cache write and the
absorbed attention over the latent cache, all layers) in whole runs of
the decode step in the traced stretch (``bench/serve_scopes.py``)."""
from bench import serve_scopes


def read(record):
    r = serve_scopes.reduce(record)
    return None if r is None else r.get("mla.attn")
