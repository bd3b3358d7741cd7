"""Request-level feature construction (Eq. 6) and raw-graph observation.

f_q = (p_j, s_hat, d_hat, e_{j,n,t}, d_{j,t}, l_{j,t}, retry)  — normalized.

The trailing ``retry`` channel (beyond Eq. 6) is the failure-aware
lifecycle's re-dispatch count (``repro.env.failover``) normalized by the
configured retry budget: 0.0 for first-dispatch requests and without
failover, approaching 1.0 as a request burns its budget — a router can
prefer placements that de-risk nearly-exhausted retries.

Expert nodes carry (e_n, |Q_run|/R, |Q_wait|/W) plus the pending request's
per-expert predictions (s_hat_{j,n}, d_hat_{j,n}), the profiled latency
gradients (k1, k2), and the scenario condition channels (up, current-cap
fraction) — the per-expert predictions ride on the expert node because
the arrived-request node connects to *all* experts (§V-B2); this is our
static-shape encoding of the arrived->expert edge features.  The scenario
channels expose ``repro.scenarios`` dynamics to the router: ``up`` is the
expert's availability at the current clock (1.0 with no scenario) and the
cap fraction is its current live slots over its baseline caps (1.0 until
a memory claim shrinks them), so a trained policy can steer around
failures and shrunken fleets instead of discovering them through
penalties alone.

Two layouts (``fmt=``):

  * ``"padded"``   — per-expert request tensors ``run (N, R, REQ_FEATS)``
    / ``wait (N, W, REQ_FEATS)`` with validity masks (the PR 1 encoding);
  * ``"segments"`` — the flat edge-list encoding for fleet-scale N: one
    request-node tensor ``req (E, REQ_FEATS)`` with a ``seg`` expert-id
    vector,
    consumed by ``han.forward_segments`` via segment-softmax attention.
    Request->expert edges are materialized once instead of once per
    (expert, meta-path) pad block, every HAN intermediate stays O(E*D) —
    never O(N^2).  On a uniform fleet E = N*(R+W): run edges occupy rows
    [0, N*R), wait edges [N*R, N*(R+W)), both expert-major, and the
    content is a pure reshape of the padded layout.  On a RAGGED fleet
    (``EnvConfig.run_caps``/``wait_caps``) the dead beyond-cap slots are
    dropped entirely — E = sum(run_caps) + sum(wait_caps), so obs
    intermediates scale with the fleet's TOTAL capacity, not
    N * max(cap); the expert-major row order is kept with each expert
    contributing exactly its cap's rows.  Equivalence with the padded
    (masked) layout in both regimes is asserted in
    tests/test_han_segments.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import scenarios
from repro.core import spans
from repro.env import engine_layout as layout

REQ_FEATS = 7
EXP_FEATS = 9

# request-node feature channels (same order in both layouts)
(REQ_P, REQ_PRED_S, REQ_PRED_D, REQ_MEM, REQ_D_CUR, REQ_LAT,
 REQ_RETRY) = range(7)


def fixed_order_sum(x: jax.Array, axis: int = -1) -> jax.Array:
    """Sum over ``axis`` with the float additions in one fixed order.

    A reduce leaves its accumulation order to the compiler, which picks it
    per program: a batch-1 and a batch-n vmap of the same observation, or
    the same SAC update inside and outside ``shard_map``, round
    differently, and the data-axis collect's bit-identity (``training``
    module docstring) breaks.  Halving the zero-padded axis with
    elementwise adds pins one pairwise order in every program."""
    x = jnp.moveaxis(x, axis, -1)
    n = x.shape[-1]
    width = 1 << max(n - 1, 0).bit_length()
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - n)])
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        x = x[..., :h] + x[..., h:]
    return x[..., 0]


def fixed_order_mean(x: jax.Array) -> jax.Array:
    """Mean over the last axis in :func:`fixed_order_sum`'s order."""
    return fixed_order_sum(x) / x.shape[-1]


@jax.named_scope(spans.OBS)
def build_obs(cfg, pool, state: dict, *, fmt: str = "padded") -> dict:
    """Returns the heterogeneous-graph observation in the given layout."""
    if fmt not in ("padded", "segments"):
        raise ValueError(f"unknown obs fmt {fmt!r}")
    q = state["queues"]
    t = state["clock"]
    L = cfg.latency_L
    mo = float(cfg.max_output)
    mp = float(cfg.max_prompt)
    r = state["pending"]
    run_valid = layout.run_valid(q)
    wait_valid = layout.wait_valid(q)
    run_p = layout.run_p(q)
    run_d_cur = layout.run_d_cur(q)
    wait_pred_d = layout.wait_pred_d(q)
    # retry channel normalizer: the failover retry budget (1.0 floor so
    # the channel is well-defined — and identically zero — without
    # failover, where every retry count is 0)
    fo = getattr(cfg, "failover", None)
    retry_norm = float(max(fo.retry_budget, 1)) if fo is not None else 1.0
    # tokens -> memory-fraction as ONE constant-folded ratio: `x * mpt /
    # cap` leaves XLA free to reassociate per compilation (batch-1 vs
    # batch-n vmaps round differently by 1 ulp), which breaks the
    # data-axis collect's bit-identity guarantee; `x * const` has a
    # single IEEE rounding everywhere
    mem_frac = pool.mem_per_token / pool.mem_capacity

    # --- running request nodes (N, R, REQ_FEATS) ---
    d_cur = run_d_cur.astype(jnp.float32)
    run_mem = (run_p + run_d_cur).astype(jnp.float32) * mem_frac[:, None]
    l_cur = (t - layout.run_t_arrive(q)) / jnp.maximum(d_cur, 1.0)
    run_f = jnp.stack([
        run_p.astype(jnp.float32) / mp,
        layout.run_pred_s(q),
        layout.run_pred_d(q) / mo,
        run_mem,
        d_cur / mo,
        l_cur / L,
        layout.run_retry(q).astype(jnp.float32) / retry_norm,
    ], axis=-1)
    run_f = jnp.where(run_valid[..., None], run_f, 0.0)

    # --- waiting request nodes (N, W, REQ_FEATS) ---
    w_wait = (t - layout.wait_t_arrive(q)) / jnp.maximum(wait_pred_d, 1.0)
    wait_f = jnp.stack([
        layout.wait_p(q).astype(jnp.float32) / mp,
        layout.wait_pred_s(q),
        wait_pred_d / mo,
        jnp.zeros_like(w_wait),            # not yet resident in memory
        jnp.zeros_like(w_wait),            # d_{j,t} = 0
        w_wait / L,                        # projected per-token wait
        layout.wait_retry(q).astype(jnp.float32) / retry_norm,
    ], axis=-1)
    wait_f = jnp.where(wait_valid[..., None], wait_f, 0.0)

    # --- expert nodes (N, EXP_FEATS) ---
    tok = jnp.where(run_valid, run_p + run_d_cur, 0)
    e_n = jnp.sum(tok, -1).astype(jnp.float32) * mem_frac
    n_exp = run_valid.shape[0]
    run_caps = getattr(cfg, "run_caps", None)
    wait_caps = getattr(cfg, "wait_caps", None)
    # per-expert BASELINE caps (packed widths on a uniform fleet): the
    # occupancy normalizer on ragged fleets and the cap-fraction
    # denominator under scenarios
    base_rc = jnp.asarray(run_caps if run_caps is not None
                          else (run_valid.shape[1],) * n_exp, jnp.float32)
    base_wc = jnp.asarray(wait_caps if wait_caps is not None
                          else (wait_valid.shape[1],) * n_exp, jnp.float32)
    if run_caps is None and wait_caps is None:
        # uniform fleet: occupancy = |Q| / packed width (the seed encoding)
        occ_run = jnp.mean(run_valid.astype(jnp.float32), -1)
        occ_wait = jnp.mean(wait_valid.astype(jnp.float32), -1)
    else:
        # ragged fleet: occupancy is relative to each expert's OWN cap, so
        # "full" means the same thing for a 1-slot and a 5-slot expert
        occ_run = jnp.sum(run_valid.astype(jnp.float32), -1) / base_rc
        occ_wait = jnp.sum(wait_valid.astype(jnp.float32), -1) / base_wc

    # --- scenario condition channels (up, current-cap fraction) ---
    st = scenarios.for_cfg(cfg)
    if st is None:
        up_f = jnp.ones((n_exp,), jnp.float32)
        cap_frac = jnp.ones((n_exp,), jnp.float32)
    else:
        cur = scenarios.at_time(st, t)
        up_f = cur["up"].astype(jnp.float32)
        cap_frac = ((cur["run_cap"] + cur["wait_cap"]).astype(jnp.float32)
                    / (base_rc + base_wc))

    exp_f = jnp.stack([
        e_n,
        occ_run,
        occ_wait,
        r["pred_s"],
        r["pred_d"] / mo,
        pool.k1 * 1e3,
        pool.k2 * 1e4,
        up_f,
        cap_frac,
    ], axis=-1)

    # --- arrived request node (REQ_FEATS,) ---
    arr_f = jnp.stack([
        r["p_len"].astype(jnp.float32) / mp,
        fixed_order_mean(r["pred_s"]),
        fixed_order_mean(r["pred_d"]) / mo,
        jnp.zeros(()),
        jnp.zeros(()),
        jnp.zeros(()),
        jnp.zeros(()),                     # fresh arrival: retry = 0
    ])

    obs = {
        "expert": exp_f, "run": run_f, "wait": wait_f,
        "run_mask": run_valid, "wait_mask": wait_valid,
        "arrived": arr_f,
    }
    if fmt == "padded":
        return obs
    return to_segments(obs, run_caps=run_caps, wait_caps=wait_caps)


def _ragged_rows(caps, width: int) -> np.ndarray:
    """Static flat row indices into an expert-major (N*width,) layout that
    keep only each expert's first cap[n] slots (the live ones)."""
    caps = np.asarray(caps, np.int64)
    return np.concatenate(
        [n * width + np.arange(c) for n, c in enumerate(caps)])


def to_segments(obs: dict, *, run_caps=None, wait_caps=None) -> dict:
    """Flatten a padded observation into the segment (edge-list) layout:
    run edges first, then wait edges, both expert-major.  The expert-id
    segment vector is NOT stored — it is a static function of (N, caps)
    that ``han.forward_segments`` rebuilds (``han.segment_ids``), which
    keeps replay-buffer transitions free of constant tensors.

    Uniform fleet (caps None): a pure reshape, rows [0, N*R) run and
    [N*R, N*(R+W)) wait.  Ragged fleet: ``run_caps``/``wait_caps`` must be
    CONCRETE per-expert capacities (tuple / numpy, not traced — they are
    shape data); beyond-cap rows are dropped by a static gather, so the
    result holds sum(run_caps) + sum(wait_caps) rows and no dead edges."""
    n, r = obs["run"].shape[:2]
    w = obs["wait"].shape[1]
    run_flat = obs["run"].reshape(n * r, -1)
    wait_flat = obs["wait"].reshape(n * w, -1)
    run_mask = obs["run_mask"].reshape(-1)
    wait_mask = obs["wait_mask"].reshape(-1)
    if run_caps is not None:
        rows = _ragged_rows(run_caps, r)
        run_flat, run_mask = run_flat[rows], run_mask[rows]
    if wait_caps is not None:
        rows = _ragged_rows(wait_caps, w)
        wait_flat, wait_mask = wait_flat[rows], wait_mask[rows]
    return {"expert": obs["expert"],
            "req": jnp.concatenate([run_flat, wait_flat]),
            "req_mask": jnp.concatenate([run_mask, wait_mask]),
            "arrived": obs["arrived"]}


def seg_run_rows(cfg) -> int:
    """Static count of run-edge rows at the head of ``obs["req"]`` for an
    env config (``sac.SACConfig.n_run_edges`` is set from this): the sum
    of the per-expert run capacities on a ragged fleet, N * run_cap on a
    uniform one."""
    caps = getattr(cfg, "run_caps", None)
    if caps is not None:
        return int(sum(caps))
    return cfg.n_experts * cfg.run_cap


def flat_expert_obs(obs: dict) -> jax.Array:
    """Baseline-RL state: raw expert-level features only (paper §VI-A),
    i.e. (e_n, |run|, |wait|) per expert — no request-level detail.
    Layout-agnostic: both obs formats carry the ``expert`` tensor."""
    return obs["expert"][:, :3].reshape(-1)
