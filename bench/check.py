"""Comparison arithmetic of the correctness check: what the timed path
produced against the plain reference (``reference.py``)."""
from __future__ import annotations

import numpy as np

from bench import reference


def scaled_err(got, want) -> float:
    """Largest |got - want| / max(|want|, 1) over the elements: an absolute
    error for values under 1 (probabilities, shares), a relative one for
    clocks, lengths and running sums."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.size == 0:
        return 0.0
    err = np.abs(got - want) / np.maximum(np.abs(want), 1.0)
    return float(np.nan_to_num(err, nan=np.inf).max())


def state_numbers(got: dict, want: dict) -> tuple:
    """(queue_mismatch, state_err) between two named-field simulator
    states (``reference.Sim.from_program`` layout).

    queue_mismatch counts integer entries that differ: valid bits and the
    PRNG key everywhere, the other integer fields in slots that either
    side holds.  state_err is ``scaled_err`` over the float fields of held
    slots, the clocks, the pending request and the running statistics."""
    g, w = got["queues"], want["queues"]
    mism = int(np.sum(np.asarray(got["key"]) != np.asarray(want["key"])))
    err = 0.0
    for side, ints, floats in (("run", reference.I_RUN, reference.F_RUN),
                               ("wait", reference.I_WAIT, reference.F_WAIT)):
        gv = np.asarray(g[f"{side}_valid"]) > 0
        wv = np.asarray(w[f"{side}_valid"]) > 0
        mism += int(np.sum(gv != wv))
        held = gv | wv
        both = gv & wv
        for name in ints[1:]:
            a, b = np.asarray(g[f"{side}_{name}"]), np.asarray(w[f"{side}_{name}"])
            mism += int(np.sum((a != b) & held))
        for name in floats:
            a, b = np.asarray(g[f"{side}_{name}"]), np.asarray(w[f"{side}_{name}"])
            err = max(err, scaled_err(a[both], b[both]))
    for k in ("type", "p_len", "out_len"):
        mism += int(np.sum(np.asarray(got["pending"][k])
                           != np.asarray(want["pending"][k])))
    mism += int(np.sum(np.asarray(got["burst"]) != np.asarray(want["burst"])))
    for k in ("score", "pred_s", "pred_d"):
        err = max(err, scaled_err(got["pending"][k], want["pending"][k]))
    err = max(err, scaled_err(got["clock"], want["clock"]),
              scaled_err(got["expert_clock"], want["expert_clock"]))
    for k in reference.STATS:
        err = max(err, scaled_err(got["stats"][k], want["stats"][k]))
    return mism, err


def leaf_norm_gaps(got: list, want: list, keep=None) -> np.ndarray:
    """Per leaf, |norm(got) - norm(want)| over the larger of the reference
    leaf's norm and the median leaf's norm; leaves outside ``keep`` read 0."""
    gn = np.array([np.linalg.norm(np.asarray(x, np.float64)) for x in got])
    wn = np.array([np.linalg.norm(np.asarray(x, np.float64)) for x in want])
    den = np.maximum(wn, np.median(wn))
    gaps = np.nan_to_num(np.abs(gn - wn) / np.maximum(den, 1e-30), nan=np.inf)
    return gaps if keep is None else np.where(keep, gaps, 0.0)


def leaf_norm_gap(got: list, want: list, keep=None) -> float:
    """The worst leaf's ``leaf_norm_gaps``."""
    return float(leaf_norm_gaps(got, want, keep).max())


def worst_leaf(got: list, want: list, keep=None) -> int:
    return int(np.argmax(leaf_norm_gaps(got, want, keep)))


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, lines): every compared number at or under its limit.  A
    number that is missing or not finite fails."""
    ok = True
    lines = []
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and np.isfinite(value) and value <= limit
        ok &= bool(good)
        lines.append(f"{name} {value!r} limit {limit!r} "
                     f"{'ok' if good else 'FAIL'}")
    return ok, lines
