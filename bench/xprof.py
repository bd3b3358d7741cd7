"""Reduction of a JAX profiler trace to the benchmark's device numbers.

A trace holds one plane per TPU (``/device:TPU:<i>``) whose ``XLA Ops``
line has an event for every operation that ran on the chip and whose
``XLA Modules`` line has one for every program run, and a host plane whose
threads carry the harness's own spans (``jax.profiler.TraceAnnotation``,
named ``bench.*``) on the same clock.  On the CPU backend, where tests
record small traces, the operations run on the host plane's XLA client
threads instead, and those stand in for the device.
"""
from __future__ import annotations

import collections
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
CPU_OP_LINES = ("tf_XLAPjRtCpuClient", "tf_XLACpu")
SPAN_PREFIX = "bench."


def load(trace_dir: str):
    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return jax.profiler.ProfileData.from_file(files[-1])


def op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``: a TPU op
    event is named by its whole HLO instruction."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns, e.start_ns + e.duration_ns


def device_ops(pd) -> dict:
    """{device plane: [(op name, start_ns, end_ns)]}."""
    out = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out[plane.name] = list(_events(line))
    if out:
        return out
    ops = []
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith(CPU_OP_LINES):
                    ops += [e for e in _events(line)
                            if not e[0].startswith(("Threadpool", "ThunkExecutor"))
                            and e[2] > e[1]]
    return {"/host:CPU": ops} if ops else {}


def modules(pd) -> dict:
    """{device plane: [(program name, start_ns, end_ns)]} from the TPU's
    ``XLA Modules`` line."""
    out = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    out[plane.name] = list(_events(line))
    return out


def host_spans(pd, prefix: str = SPAN_PREFIX) -> list:
    """[(span name, start_ns, end_ns)] of the harness's host spans."""
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in _events(line) if e[0].startswith(prefix)]
    return sorted(spans, key=lambda s: s[1])


def merge(intervals) -> list:
    """Union of [start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def reduce(pd, window_span: str, top: int = 10) -> dict:
    """Busy time, idle gaps and the costliest operations of each device,
    inside the host span ``window_span``.

    busy_s is the union of the intervals in which an operation ran,
    averaged over the devices; idle_gaps names each gap between them by
    the innermost harness span open at the gap's middle and sums by name."""
    spans = host_spans(pd)
    win = [s for s in spans if s[0] == window_span]
    if not win:
        raise ValueError(f"no host span {window_span!r} in the trace")
    lo, hi = win[-1][1], win[-1][2]
    inner = [s for s in spans if s[0] != window_span and s[2] > lo and s[1] < hi]
    ops = device_ops(pd)
    if not ops:
        raise ValueError("no device operations in the trace")
    busy, by_op, gaps = [], collections.Counter(), collections.Counter()
    for plane, evs in ops.items():
        cut = [(n, s, e) for n, s, e in evs if e > lo and s < hi]
        for n, s, e in cut:
            by_op[op_name(n)] += (min(e, hi) - max(s, lo)) / 1e9 / len(ops)
        merged = merge(clip([(s, e) for _, s, e in cut], lo, hi))
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = (g0 + g1) / 2
            around = [s for s in inner if s[1] <= mid < s[2]]
            name = max(around, key=lambda s: s[1])[0] if around else window_span
            gaps[name] += (g1 - g0) / 1e9 / len(ops)
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy) / len(busy)
    return {"busy_s": busy_s, "window_s": window_s,
            "dropped_s": dropped_seconds(pd, lo, hi),
            "idle_share": 1.0 - busy_s / window_s,
            "device_ops": [[n, t] for n, t in by_op.most_common(top)],
            "idle_gaps": [[n, t] for n, t in gaps.most_common(top)],
            "devices": len(ops)}


def dropped_seconds(pd, lo, hi) -> float:
    """Seconds of [lo, hi) in which the profiler dropped device events (its
    buffers overflow when a program runs millions of tiny operations); the
    busy time undercounts there."""
    out = 0.0
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                for n, s, e in _events(line):
                    if n == "Trace Buffers Dropped":
                        out += max(0, min(e, hi) - max(s, lo)) / 1e9
    return out


def module_seconds(pd, name_part: str) -> tuple:
    """(seconds, runs) of the programs whose name holds ``name_part``, on
    the first device."""
    mods = modules(pd)
    if not mods:
        return 0.0, 0
    evs = [e for e in mods[sorted(mods)[0]] if name_part in e[0]]
    return sum(e - s for _, s, e in evs) / 1e9, len(evs)
