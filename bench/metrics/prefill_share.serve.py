"""Share of the device's busy time, in the traced stretch, spent in runs
of the prefill programs (one a bucket length): the union of the
operation intervals inside those programs' events on the trace's
``XLA Modules`` line over the union of all operation intervals."""
from bench import scopes, xprof


def read(record):
    ex = record.get("extras") or {}
    pd = record.get("profile")
    if "prefill_program" not in ex or pd is None:
        return None
    win = [s for s in xprof.host_spans(pd) if s[0] == "bench.trace"]
    mods = xprof.modules(pd)
    if not win or not mods:
        return None
    lo, hi = win[-1][1], win[-1][2]
    ops = xprof.device_ops(pd)
    shares = []
    for plane, runs in mods.items():
        busy = xprof.merge(xprof.clip([(s, e) for _, s, e in ops.get(plane, [])],
                                      lo, hi))
        pre = xprof.merge(xprof.clip([(s, e) for n, s, e in runs
                                      if ex["prefill_program"] in n], lo, hi))
        total = sum(e - s for s, e in busy)
        if total <= 0:
            return None
        shares.append(scopes.overlap(busy, pre) / total)
    return 100.0 * sum(shares) / len(shares)

