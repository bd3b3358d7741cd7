"""Device time of the served model's scopes, for the ``dsv2lite.serve``
readers.

``bench/scopes.py`` joins each traced operation with its instruction's
``op_name`` path.  The TPU compiler rewrites every grouped product
(``lax.ragged_dot``, the routed experts' SwiGLU) into a Mosaic kernel and
gives it the metadata ``op_name="ragged-dot-none"``: a name with no path,
so those products would count under no scope, not even the decode
step's (the kernel that lays out their tiles, ``ragged-dot-metadata``,
likewise).  Here an instruction whose ``op_name`` is such a bare name
takes the path of the nearest instruction that uses its result, directly
or through instructions without a path (the SwiGLU product or the
gate-weighted sum, both under ``moe.routed``); everything else is
``bench/scopes.py``'s join and attribution, unchanged.
"""
from __future__ import annotations

import re

from bench import scopes, xprof

OPERAND = re.compile(r"%([\w.\-]+)")


def reduce(record: dict):
    """``bench/scopes.py``'s per-run numbers of the timed program, with the
    compiler's bare names given their user's path, or ``None``.  Computed
    once per record."""
    if "serve_scopes" not in record:
        record["serve_scopes"] = _reduce(record)
    return record["serve_scopes"]


def _reduce(record: dict):
    try:
        from repro.core import spans
    except ImportError:  # a program that names no layers
        return None
    pd = record.get("profile")
    if pd is None or not xprof.modules(pd):
        return None
    win = [s for s in xprof.host_spans(pd) if s[0] == scopes.WINDOW]
    ran, named = scopes.program_texts(record["cell"])
    joined = scopes.hlo_paths(ran, named)
    if joined is None:
        return None
    module, paths = joined
    return scopes.by_scope(pd, (win[-1][1], win[-1][2]), module,
                           user_paths(ran, paths), spans)


def user_paths(text: str, paths: dict) -> dict:
    """``paths`` with each instruction whose path is a bare name (no ``/``,
    set by the compiler) given the path of the nearest instruction of
    ``text`` downstream of it, through its users, that has one."""
    users = {}
    for line in text.splitlines():
        m = scopes.INSTRUCTION.match(line)
        if m:
            for op in OPERAND.findall(m.group(2)):
                users.setdefault(op, []).append(m.group(1)[1:])

    def downstream(name):
        seen, todo = {name}, [name]
        while todo:
            for u in users.get(todo.pop(0), ()):
                if "/" in paths.get(u, ""):
                    return paths[u]
                if u not in seen:
                    seen.add(u)
                    todo.append(u)
        return None

    return {n: (downstream(n) or p) if p and "/" not in p else p
            for n, p in paths.items()}
