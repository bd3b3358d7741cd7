"""Device time per decode step of the routed experts held here: the busy
time under the ``moe.routed`` scope (the sort by expert and the grouped
SwiGLU products, all MoE layers) in whole runs of the decode step in the
traced stretch (``bench/serve_scopes.py``)."""
from bench import serve_scopes


def read(record):
    r = serve_scopes.reduce(record)
    return None if r is None else r.get("moe.routed")
