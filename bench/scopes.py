"""Device time of the program's named scopes, from a profiler trace.

The program names its layers with ``jax.named_scope``
(``repro.core.spans``).  A scope lands in the ``op_name`` metadata of every
HLO instruction traced inside it, as one segment of a path such as
``jit(chunk)/vmap()/while/body/engine.advance/while/body/engine.trip/add``;
a transform wraps a segment (``vmap(engine.advance)``, ``jvp(...)``,
``transpose(...)``).  The TPU's ``XLA Ops`` events are named by their HLO
instruction, and the ``XLA Modules`` event around them by their program.
The path of each instruction is joined from that program's optimized HLO
text: the cell's timed program, lowered at the shapes ``jax.eval_shape``
gives and compiled twice.  Compiled as the run compiled it, under the
compile cache's default key, it is the executable the trace timed, with
the trace's instruction names, even where a cache shared across builds
served it from another build: that key leaves metadata out, and builds
that differ only in their scopes can name a few instructions
differently.  Compiled with metadata in the key, it carries this build's
``op_name`` paths.  The two texts are matched instruction by instruction,
and must agree in all but names and metadata.

Attribution:

* ``XLA Ops`` events nest (a ``while`` holds its body's operations), so at
  each instant the busy time goes to the innermost operation running,
  the one that started last, and no instant counts twice;
* a scope's time is the busy time whose innermost operation has the scope
  anywhere on its path, so nested scopes are inclusive; ``unscoped`` is
  the busy time under none of them.  An instruction without metadata,
  which the compiler added, takes the path of the operation it runs
  inside (a loop's body holds many), or none at the top level;
* only whole runs of the timed program inside the traced stretch
  (``bench.trace``) count, and every time is per run.

Where the trace has no TPU plane (a CPU run), the program has no scopes
(``repro.core.spans`` is missing), or no run of the timed program lies
whole inside the stretch, ``reduce`` returns ``None`` and each reader
reports nothing.  It returns ``None`` too, and says so on stderr, where
the two texts differ in more than names and metadata, or an operation of
a run names no instruction of the program text: its time would go to the
wrong scopes.
"""
from __future__ import annotations

import bisect
import collections
import os
import re
import sys

from bench import xprof

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW = "bench.trace"
WRAPPED = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")


def segments(path: str) -> list:
    """The scope segments of an ``op_name`` path, with transform wrappers
    stripped: ``jit(f)/vmap(step.obs)/add`` -> ``[f, step.obs, add]``."""
    out = []
    for seg in path.split("/"):
        m = WRAPPED.match(seg)
        while m:
            seg = m.group(1)
            m = WRAPPED.match(seg)
        out.append(seg)
    return out


INSTRUCTION = re.compile(r"\s*(?:ROOT )?(%[^\s=]+) = (.*)$")


def instructions(text: str) -> list:
    """(name, op_name path, form) of each instruction of an HLO text, where
    the form is the instruction without its metadata and with every name
    replaced by its order of first use; ``""`` where it has no path."""
    order, out = {}, []
    rename = lambda m: "%" + str(order.setdefault(m.group(0), len(order)))
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            o = re.search(r'op_name="([^"]*)"', line)
            form = re.sub(r",? ?metadata=\{[^}]*\}", "", m.group(0))
            out.append((m.group(1)[1:], o.group(1) if o else "",
                        re.sub(r"%[\w.\-]+", rename, form)))
    return out


def hlo_paths(ran: str, named: str):
    """(module name, {instruction: op_name path}) of the optimized HLO text
    ``ran``, the paths read from ``named``, the same program compiled with
    this build's metadata, instruction by instruction; an instruction
    without metadata maps to ``""``.  ``None`` where the two differ in
    more than names and metadata."""
    module = re.search(r"^HloModule ([^\s,]+)", ran, re.M).group(1)
    ran_i, named_i = instructions(ran), instructions(named)
    if [f for _, _, f in ran_i] != [f for _, _, f in named_i]:
        print(f"[scopes] the program text with this build's metadata is "
              f"not the program {module} that ran", file=sys.stderr,
              flush=True)
        return None
    return module, {a: p for (a, _, _), (_, p, _) in zip(ran_i, named_i)}


def timed_program(entry, kind: str) -> tuple:
    """(jitted function, argument shapes) of an entry's timed program:
    what its ``timed_program()`` method gives, where it has one, else the
    route entry's chunk or the train entry's iteration."""
    if hasattr(entry, "timed_program"):
        return entry.timed_program()
    import jax
    import jax.numpy as jnp
    members = {"route": ("_init", "_chunk"),
               "train": ("_init", "_iteration")}.get(kind)
    if members is None or not all(hasattr(entry, m) for m in members):
        raise TypeError(
            f"bench/scopes.py cannot find the timed program of the {kind!r} "
            "entry: give the entry a timed_program() method that returns "
            "the jitted function and its argument shapes")
    shapes = jax.eval_shape(entry._init, jax.random.PRNGKey(0))
    if kind == "route":
        return entry._chunk, shapes
    return entry._iteration, (*shapes, jax.ShapeDtypeStruct((2,), jnp.uint32),
                              jax.ShapeDtypeStruct((), jnp.int32))


def program_texts(cell: str) -> list:
    """Optimized HLO texts of the cell's timed program at its run's shapes
    (``compiled_texts``)."""
    from bench import run
    need = run.resolve(ROOT, cell)
    entry = run.load_module(need["entry"], "bench_scopes_entry").Entry(
        need["cfg"], need["traffic"])
    return compiled_texts(*timed_program(entry, need["traffic"]["entry"]))


def compiled_texts(fn, shapes) -> list:
    """Optimized HLO texts of ``fn`` at ``shapes``: compiled under the
    compile cache's default key, as the run compiled it, and with metadata
    in the key (module docstring).  A later traced run of this build loads
    both from the cache, where there is one."""
    import jax
    flag = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, flag)
    texts = []
    try:
        for metadata in (False, True):
            jax.config.update(flag, metadata)
            # the executables this process keeps in memory are keyed
            # without the flag: each compile has to reach the cache's key
            jax.clear_caches()
            texts.append(fn.lower(*shapes).compile().as_text())
    finally:
        jax.config.update(flag, was)
    return texts


def innermost(events) -> tuple:
    """(own, outer): the busy time of each (name, start, end) event in
    which it was the innermost operation running, the one that started
    last, and the index of the event it started inside (``None`` at top
    level).  The times sum to the union of the events' intervals."""
    own = [0.0] * len(events)
    outer = [None] * len(events)
    # at one instant, ends come before starts, and the longer of two
    # events that start together (the enclosing one) is entered first
    timed = [(i, s, e) for i, (_, s, e) in enumerate(events) if e > s]
    points = sorted([(s, 1, -e, i) for i, s, e in timed]
                    + [(e, 0, 0, i) for i, s, e in timed])
    stack, ended, prev = [], set(), None
    for t, starts, _, i in points:
        if stack and t > prev:
            own[stack[-1]] += t - prev
        prev = t
        if starts:
            outer[i] = stack[-1] if stack else None
            stack.append(i)
        else:
            ended.add(i)
            while stack and stack[-1] in ended:
                stack.pop()
    return own, outer


def overlap(a, b) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        out += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def reduce(record: dict):
    """Per-run device numbers of the timed program's scopes, or ``None``
    (module docstring).  Computed once per record."""
    if "scopes" not in record:
        record["scopes"] = _reduce(record)
    return record["scopes"]


def _reduce(record: dict):
    try:
        from repro.core import spans
    except ImportError:  # a program that names no layers
        return None
    pd = record.get("profile")
    if pd is None or not xprof.modules(pd):
        return None
    win = [s for s in xprof.host_spans(pd) if s[0] == WINDOW]
    joined = hlo_paths(*program_texts(record["cell"]))
    if joined is None:
        return None
    return by_scope(pd, (win[-1][1], win[-1][2]), *joined, spans)


def by_scope(pd, window: tuple, module: str, paths: dict, spans):
    """Per-run numbers of the whole runs of program ``module`` inside
    ``window`` (ns), its instructions' paths given by ``paths``, averaged
    over the chips: busy ms per scope of ``spans.SCOPES`` and
    ``unscoped``, trips of the lockstep loop per advance, and the share
    of the window with a run open and no operation running.  ``None``
    where no run lies whole inside the window, an operation of a run
    names no instruction in ``paths``, or no busy time joins with a
    scope."""
    lo, hi = window
    ops = xprof.device_ops(pd)
    planes = []
    for plane, runs in sorted(xprof.modules(pd).items()):
        mine = sorted((s, e) for n, s, e in runs
                      if n.split("(")[0] == module and lo <= s and e <= hi)
        if not mine:
            print(f"[scopes] no run of {module} lies whole in the stretch",
                  file=sys.stderr, flush=True)
            return None
        evs = ops.get(plane, [])
        inside = [(xprof.op_name(n), s, e) for n, s, e in evs
                  if _within(s, e, mine)]
        unknown = [(s, e) for n, s, e in inside if n not in paths]
        if unknown:
            ms = sum(e - s for s, e in xprof.merge(unknown)) / 1e6
            print(f"[scopes] {len(unknown)} operations ({ms:.3f} ms) of "
                  f"{module} on {plane} name no instruction of its program "
                  "text", file=sys.stderr, flush=True)
            return None
        out = _attribute(inside, paths, spans)
        if out["busy"] == out["unscoped"]:
            print(f"[scopes] no instruction of {module} has a scope",
                  file=sys.stderr, flush=True)
            return None
        for k in (*spans.SCOPES, "unscoped", "busy"):
            out[k] /= len(mine) * 1e6
        open_runs = xprof.merge(xprof.clip([(s, e) for _, s, e in runs],
                                           lo, hi))
        busy = xprof.merge(xprof.clip([(s, e) for _, s, e in evs], lo, hi))
        out["idle_within_run"] = (sum(e - s for s, e in open_runs)
                                  - overlap(busy, open_runs)) / (hi - lo)
        out["runs"] = len(mine)
        planes.append(out)
    res = {k: sum(p[k] for p in planes) / len(planes)
           for k in planes[0] if k != "trips"}
    trips = [p["trips"] for p in planes if p["trips"] is not None]
    res["trips"] = sum(trips) / len(trips) if trips else None
    return res


def _within(s, e, runs) -> bool:
    """Whether [s, e) lies inside one of the sorted, disjoint ``runs``."""
    i = bisect.bisect_right(runs, (s, float("inf"))) - 1
    return i >= 0 and runs[i][0] <= s and e <= runs[i][1]


def _attribute(inside, paths, spans) -> dict:
    """Busy ns by scope of (instruction, start, end) events, and the
    lockstep loop's trips per advance."""
    own, outer = innermost(inside)
    out = dict.fromkeys((*spans.SCOPES, "unscoped", "busy"), 0.0)
    counts = collections.Counter()
    loops = 0
    segs_of = {}
    path = [paths[name] for name, _, _ in inside]
    for i, ((name, _, _), t) in enumerate(zip(inside, own)):
        # an instruction the compiler added without metadata (a copy, a
        # bitcast) belongs to the operation it runs inside, such as the
        # loop whose body holds it
        j = i
        while not path[j] and outer[j] is not None:
            j = outer[j]
        path[i] = path[j]
        if path[i] not in segs_of:
            segs_of[path[i]] = set(segments(path[i]))
        segs = segs_of[path[i]]
        out["busy"] += t
        hit = segs.intersection(spans.SCOPES)
        for s in hit:
            out[s] += t
        if not hit:
            out["unscoped"] += t
        if spans.TRIP in segs:
            counts[name] += 1
        elif spans.ADVANCE in segs and name.split(".")[0] == "while":
            loops += 1
    out["trips"] = None
    if counts and loops:
        # every instruction of the loop body runs once a trip; the most
        # common count passes over one hoisted out of the loop or inside
        # a branch of its body
        trip = collections.Counter(counts.values()).most_common(1)[0][0]
        out["trips"] = trip / loops
    return out
