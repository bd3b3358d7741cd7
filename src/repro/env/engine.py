"""Iteration-level scheduling engine (jittable, per the paper's §III-A and
Fig. 3 / Orca [11] semantics):

Each expert keeps a fixed-capacity waiting queue and running queue (masked
arrays).  One engine iteration either

  1. *prefills* a waiting request (if a running slot is free and GPU
     memory admits it): local clock += k1 * p; request joins the running
     queue having produced its first token, or
  2. *decodes* every running request in parallel:
     local clock += k2 * sum(p_i + d_i,t); each d_i,t += 1; finished
     requests leave, recording QoS phi = s * 1[l <= L], or
  3. idles to the next arrival time.

Memory model: C_{j,n,t} = mem_per_token * (p_j + d_{j,t})  (Eq. 4).

Engine layer split
------------------
  * ``repro.env.engine_layout``     — packed SoA channel layout, accessors,
    ``empty_queues``/``push_wait``/``mem_used`` (re-exported here).
  * ``repro.env.engine`` (this)     — the lockstep semantics as a pure
    per-shard function ``advance_shard`` plus the backend dispatch
    ``advance_all(..., backend=...)``.
  * ``repro.kernels.lockstep_advance`` — Pallas kernel fusing the masked
    admit/decode/idle body over an expert block (``backend="pallas"``).

Backends
--------
``advance_all(..., backend=...)`` selects how the lockstep loop runs:

  * ``"xla"``       — one ``lax.while_loop`` over all N experts on the
    current device (the PR 1 engine; default).
  * ``"pallas"``    — the fused ``lockstep_advance`` kernel, gridded over
    expert blocks (interpret mode off-TPU).
  * ``"shard_map"`` — the expert axis is split across the devices of an
    ``("expert",)`` mesh (``launch.mesh.make_expert_mesh``, multi-host
    aware); each device runs the fused lockstep kernel on its rows
    (``shard_body="pallas"``, the default — ``shard_body="xla"`` keeps
    the plain ``advance_shard`` loop as a bit-identical escape hatch)
    and only the per-expert completion accumulators are all-gathered back
    to every device.  Queue tensors and clocks stay device-local between
    calls.

All backends are bit-identical to ``engine_ref`` (the seed vmap engine);
asserted in ``tests/test_engine_equiv.py``.

Admission order
---------------
``admit_order`` picks which waiting request an admission pops:

  * ``"fifo"``     — the oldest waiter (smallest ``t_arrive``; the paper's
    and the seed engine's behaviour),
  * ``"qos"``      — the waiter with the highest predicted score ``pred_s``
    (QoS-weighted admission, a paper follow-on), or
  * ``"qos_aged"`` — the waiter with the highest age-weighted score
    ``pred_s + QOS_AGE_BETA * (clock - t_arrive)``: pure-qos admission can
    starve old low-score waiters behind a stream of fresh high-score ones;
    the aging term guarantees every waiter's priority grows without bound,
    so starvation is impossible.  Because all waiters of an expert are
    compared at the same clock, the ordering is equivalent to minimizing
    the loop-invariant key ``QOS_AGE_BETA * t_arrive - pred_s``, or
  * ``"edf"``      — earliest deadline first: the waiter closest to
    violating the latency requirement.  A request violates once its
    per-token latency ``(t_finish - t_arrive) / d_true`` exceeds
    ``latency_L``, i.e. its deadline is ``t_arrive + latency_L * d``;
    with ``pred_d`` standing in for the unknown ``d_true`` the admission
    minimizes the loop-invariant key ``t_arrive + latency_L * pred_d``.
    Starvation-free like fifo (every waiter's deadline is fixed and time
    only moves toward it).

  Ties fall back to the lowest slot index in all four modes.

Per-expert capacities
---------------------
``advance_all(..., run_caps=, wait_caps=)`` takes optional (N,) int32
capacity vectors bounding how many run/wait slots each expert may use
(the heterogeneous-fleet contract in ``engine_layout``'s docstring; derive
them from pool memory with ``profiles.memory_caps``).  Admission masks
both the free-run-slot search and the waiter selection against the caps
inside the pure ``advance_shard`` body, so all three backends inherit the
semantics; with uniform caps (== the packed widths) every mask is
all-True and the engine is byte-for-byte identical to the capacity-free
path.

Scenario conditions (availability / stragglers)
-----------------------------------------------
``advance_all(..., up=, k_scale=)`` threads the scenario subsystem's
time-varying fleet conditions (``repro.scenarios``) through the same
pool-params tree the capacity vectors ride:

  * ``up`` (N,) bool — a DOWN expert admits nothing and decodes nothing:
    its only permitted action is idle, so its clock jumps to ``t_next``
    and queued work freezes in place (latency keeps accruing until
    recovery).  Callers gate new pushes on ``up`` as well
    (``env._admit``), so a down expert's queues only ever drain-by-
    freezing, never grow.
  * ``k_scale`` (N,) f32 — straggler multiplier folded into ``k1``/``k2``
    before dispatch, so the backends (including the Pallas kernel's
    packed parameter operand) see pre-scaled gradients and need no extra
    channel.

Failover conditions (retry channel / overload shedding)
-------------------------------------------------------
The failure-aware lifecycle (``repro.env.failover``) adds two engine-level
pieces, both living in the pure ``advance_shard`` body so every backend
inherits them:

  * the packed layout's ``retry`` channel (``RI_RETRY``/``WI_RETRY``)
    rides through admission — the admitted waiter's re-dispatch count is
    copied into its running slot;
  * ``advance_all(..., admit_min=)`` (N,) f32 is an overload-shedding
    admission floor: waiters whose stored ``pred_s`` falls below their
    expert's floor are *deferred* — still queued, but excluded from the
    waiter pick (like the capacity masks, the floor is loop-invariant
    within a window).  ``-INF``/None disables the floor.

With ``up`` all-True and ``k_scale`` all-ones (the always-up scenario)
every mask is all-True and every multiply is by 1.0, so the engine is
byte-for-byte identical to the scenario-free path; likewise with the
retry channel all-zero and no admission floor it is byte-identical to
the failover-free engine.  Caps that vary over
time are just the existing ``run_caps``/``wait_caps`` arguments passed
per advance; the scenario runtime evicts beyond-cap occupants at the
step boundary (``scenarios.evict_beyond_cap``) so the dead-slot contract
holds with the current caps throughout the window.

Lockstep advance
----------------
``advance_shard`` runs a SINGLE ``lax.while_loop`` over its shard's experts
in lockstep (instead of the seed's vmap-of-while_loop whose body built two
full candidate queue dicts and merged them with 3-way ``jnp.where`` over
the whole tree).  Invariants:

  * per iteration each expert takes exactly one masked action —
    admit / decode / idle — or is untouched when inactive
    (``clock >= t_next`` or no work);
  * actions only touch an expert's own rows, so the per-expert action
    sequence is identical to running the seed's per-expert loop, and the
    loop trip count is the max over the shard (same as vmap-of-while);
  * updates are masked in-place channel writes; no candidate queue
    dicts are materialized;
  * the wait side is loop-invariant except its valid bit (admission pops
    one waiter; new entries only arrive between advances via the env), so
    the while-loop carries just the (N, W) wait-valid mask and closes
    over the wait tensors;
  * after the loop every clock is clamped to ``t_next`` (idle experts
    jump forward).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import spans
from repro.env.engine_layout import (  # noqa: F401  (re-exported layout API)
    RI_VALID, RI_P, RI_D_TRUE, RI_D_CUR, RI_RETRY, RUN_I_CH,
    RF_SCORE, RF_PRED_S, RF_PRED_D, RF_T_ARRIVE, RF_T_ADMIT, RUN_F_CH,
    WI_VALID, WI_P, WI_D_TRUE, WI_RETRY, WAIT_I_CH,
    WF_SCORE, WF_PRED_S, WF_PRED_D, WF_T_ARRIVE, WAIT_F_CH,
    PAR_K1, PAR_K2, PAR_MEM_CAP, PAR_MPT, PAR_RUN_CAP, PAR_WAIT_CAP,
    PAR_UP, PAR_ADMIT_MIN, PAR_CH, PAR_CAP_FREE,
    empty_queues, push_wait, mem_used, slot_valid,
    run_valid, run_p, run_d_true, run_d_cur, run_retry, run_score,
    run_pred_s, run_pred_d, run_t_arrive, run_t_admit,
    wait_valid, wait_p, wait_d_true, wait_retry, wait_score, wait_pred_s,
    wait_pred_d, wait_t_arrive,
)
from repro.env.profiles import ExpertPool

# a python float, not a jax array: a module-level array becomes a hidden
# constant argument of every program that uses it, and calling an
# ahead-of-time compiled program (``jit(f).lower(...).compile()``) can then
# fail with "compiled for N inputs but called with N-1"
INF = 1e30

BACKENDS = ("xla", "pallas", "shard_map")
ADMIT_ORDERS = ("fifo", "qos", "qos_aged", "edf")

# qos_aged admission: priority = pred_s + QOS_AGE_BETA * wait_time.  At
# 0.5 score-units per second, two seconds of waiting outweigh any possible
# pred_s gap (pred_s spans [0, 1]), bounding starvation to a few seconds
# under the paper's arrival rates.
QOS_AGE_BETA = 0.5


def pool_params(pool: ExpertPool, run_caps=None, wait_caps=None,
                up=None, k_scale=None, admit_min=None) -> dict:
    """The per-expert (N,) scalars the lockstep body needs.  Optional
    ``run_caps``/``wait_caps`` (N,) int32 capacity vectors and the
    scenario ``up`` availability mask join the tree (same leading expert
    axis, so they shard identically); a ``k_scale`` straggler multiplier
    is folded straight into ``k1``/``k2``; ``admit_min`` (N,) f32 is the
    overload-shedding admission floor (waiters with ``pred_s`` below it
    are deferred; ``-INF``/absent disables the floor).

    Also builds the kernel's dense (N, PAR_CH) float32 parameter pack
    under ``"par"`` (``engine_layout.PAR_*`` channel order) ONCE per
    window, so ``ops.lockstep_advance`` never restacks it in the hot
    loop: the pool channels (k1/k2/mem) are loop-invariant and only the
    scenario-varying channels (caps, up, admit_min) change between
    windows.  Absent caps use the ``PAR_CAP_FREE`` sentinel, which keeps
    every slot-mask all-True — bit-identical to explicit full-width
    caps."""
    k1, k2 = pool.k1, pool.k2
    if k_scale is not None:
        scale = jnp.asarray(k_scale, jnp.float32)
        k1, k2 = k1 * scale, k2 * scale
    params = {"k1": k1, "k2": k2,
              "mem_capacity": pool.mem_capacity,
              "mem_per_token": pool.mem_per_token}
    if run_caps is not None:
        params["run_cap"] = jnp.asarray(run_caps, jnp.int32)
    if wait_caps is not None:
        params["wait_cap"] = jnp.asarray(wait_caps, jnp.int32)
    if up is not None:
        params["up"] = jnp.asarray(up, jnp.bool_)
    if admit_min is not None:
        params["admit_min"] = jnp.asarray(admit_min, jnp.float32)
    chans = [None] * PAR_CH
    chans[PAR_K1], chans[PAR_K2] = k1, k2
    chans[PAR_MEM_CAP] = pool.mem_capacity
    chans[PAR_MPT] = pool.mem_per_token
    free = jnp.full_like(jnp.asarray(k1, jnp.float32), PAR_CAP_FREE)
    chans[PAR_RUN_CAP] = (params["run_cap"].astype(jnp.float32)
                          if run_caps is not None else free)
    chans[PAR_WAIT_CAP] = (params["wait_cap"].astype(jnp.float32)
                           if wait_caps is not None else free)
    chans[PAR_UP] = (params["up"].astype(jnp.float32)
                     if up is not None else jnp.ones_like(free))
    chans[PAR_ADMIT_MIN] = (params["admit_min"]
                            if admit_min is not None
                            else jnp.full_like(free, -1e30))
    params["par"] = jnp.stack(
        [jnp.asarray(c, jnp.float32) for c in chans], axis=-1)
    return params


def admit_key(t_arrive: jax.Array, pred_s: jax.Array, pred_d: jax.Array,
              admit_order: str, latency_L: float = 0.0) -> jax.Array:
    """The loop-invariant key an admission MINIMIZES over live waiters,
    from the waiters' ``t_arrive``/``pred_s``/``pred_d`` fields (the
    Pallas kernel reads them as separate channel planes).  ``latency_L``
    only matters for ``"edf"``."""
    if admit_order == "fifo":
        return t_arrive
    if admit_order == "qos":
        return -pred_s
    if admit_order == "edf":
        # earliest (predicted) deadline t_arrive + L * pred_d first
        return t_arrive + latency_L * pred_d
    # qos_aged: argmax over waiters of pred_s + beta*(clock - t_arrive) ==
    # argmin of beta*t_arrive - pred_s (clock is common per expert).
    return QOS_AGE_BETA * t_arrive - pred_s


def admit_sort_key(wait_f: jax.Array, admit_order: str,
                   latency_L: float = 0.0) -> jax.Array:
    """:func:`admit_key` of a packed (..., WAIT_F_CH) float tensor (the
    XLA body and the failover retry buffer; the Pallas kernel calls
    :func:`admit_key` on its channel planes), so every admission ranks
    waiters identically."""
    return admit_key(wait_f[..., WF_T_ARRIVE], wait_f[..., WF_PRED_S],
                     wait_f[..., WF_PRED_D], admit_order, latency_L)


def slot_sum(x: jax.Array, *, keepdims: bool = False) -> jax.Array:
    """Sum over the slot (last) axis, adding slots left to right.

    A float reduce leaves its accumulation order to the compiler, and the
    XLA backend and the Pallas kernel are compiled by different ones; the
    completion accumulators are float sums over up to R finishing slots,
    so both backends add them in this one explicit order to stay
    bit-identical on every platform."""
    total = x[..., 0:1]
    for j in range(1, x.shape[-1]):
        total = total + x[..., j:j + 1]
    return total if keepdims else total[..., 0]


def advance_shard(params: dict, latency_L: float, queues: dict,
                  clocks: jax.Array, t_next: jax.Array, *,
                  admit_order: str = "fifo") -> Tuple[dict, jax.Array, dict]:
    """Advance one shard of experts in lockstep until every clock reaches
    ``t_next``.  Pure function of (N,)-leading tensors — N here is the
    shard's expert count, so the same body serves the single-device
    ``"xla"`` backend and the per-device body under ``shard_map``.

    Returns (queues, clocks, acc) with acc entries shaped (N,) summing
    completion stats in the window: phi / lat / score / wait / done / viol.
    """
    assert admit_order in ADMIT_ORDERS, admit_order
    k1, k2 = params["k1"], params["k2"]                    # (N,)
    cap, mpt = params["mem_capacity"], params["mem_per_token"]
    n = k1.shape[0]
    r_cap = queues["run_i"].shape[1]
    w_cap = queues["wait_i"].shape[1]
    run_slots = jnp.arange(r_cap)[None, :]                 # (1, R)
    wait_slots = jnp.arange(w_cap)[None, :]                # (1, W)
    # per-expert capacity masks; absent caps mean every packed slot is
    # live, which makes every mask below all-True (the capacity-free path)
    run_capv = params.get("run_cap", jnp.full((n,), r_cap, jnp.int32))
    wait_capv = params.get("wait_cap", jnp.full((n,), w_cap, jnp.int32))
    run_ok = slot_valid(run_capv, r_cap)                   # (N, R)
    wait_ok = slot_valid(wait_capv, w_cap)                 # (N, W)
    # scenario availability: a down expert admits nothing and decodes
    # nothing — its only permitted action is idle (all-True when absent)
    upv = params.get("up", jnp.ones((n,), jnp.bool_))      # (N,)
    # overload-shedding admission floor: waiters with pred_s below their
    # expert's floor are deferred — they stay queued but are invisible to
    # the waiter pick this window (-INF/absent = everything admissible)
    admit_min = params.get("admit_min", jnp.full((n,), -INF))  # (N,)

    acc0 = {key: jnp.zeros((n,), jnp.float32)
            for key in ("phi", "lat", "score", "wait", "done", "viol")}

    # Everything except the wait VALID bit is loop-invariant on the wait
    # side (admission only clears valid; fields are written by the env
    # between advances), so the loop closes over wait_i/wait_f and carries
    # only the (N, W) valid mask.
    wait_i0, wait_f0 = queues["wait_i"], queues["wait_f"]
    w_sort_key = admit_sort_key(wait_f0, admit_order, latency_L)
    # loop-invariant like the sort key: the floor compares against stored
    # pred_s, so it folds into the same per-window admissibility mask
    w_admissible = wait_f0[..., WF_PRED_S] >= admit_min[:, None]  # (N, W)

    def active_mask(run_i, wvalidb, clocks):
        has_work = jnp.any(run_i[..., RI_VALID] > 0, -1) | jnp.any(wvalidb, -1)
        return (clocks < t_next) & has_work

    def cond(c):
        return jnp.any(c[5])  # carried active mask

    @jax.named_scope(spans.TRIP)
    def body(c):
        run_i, run_f, wvalidb, clocks, acc, active = c
        validb = run_i[..., RI_VALID] > 0                  # (N, R)
        p = run_i[..., RI_P]
        d_true = run_i[..., RI_D_TRUE]
        d_cur = run_i[..., RI_D_CUR]

        run_tokens = jnp.sum(jnp.where(validb, p + d_cur, 0), -1)   # (N,)
        mem = run_tokens * mpt

        # choose action per expert: admit > decode > idle (dead beyond-cap
        # slots are masked out of both the waiter pick and the free-slot
        # search; with uniform caps the masks are all-True)
        w_live = wvalidb & wait_ok & w_admissible
        w_key = jnp.where(w_live, w_sort_key, INF)
        w_idx = jnp.argmin(w_key, -1)                      # (N,) next waiter
        w_has = jnp.any(w_live, -1)
        r_free = jnp.argmin(validb | ~run_ok, -1)          # first live empty slot
        r_has_space = ~jnp.all(validb | ~run_ok, -1)
        head_i = jnp.take_along_axis(wait_i0, w_idx[:, None, None], 1)[:, 0]
        head_f = jnp.take_along_axis(wait_f0, w_idx[:, None, None], 1)[:, 0]
        head_p = head_i[:, WI_P]
        fits = mem + mpt * (head_p.astype(jnp.float32) + 1.0) <= cap
        can_admit = w_has & r_has_space & fits & upv
        r_has = jnp.any(validb, -1)

        adm = active & can_admit
        dec = active & ~can_admit & r_has & upv
        idle = active & ~can_admit & ~(r_has & upv)

        # --- decode: masked in-place over this iteration's decoding rows ---
        dec_rows = dec[:, None] & validb                   # (N, R)
        d_new = d_cur + dec_rows.astype(jnp.int32)
        finished = dec_rows & (d_new >= d_true)
        clock_dec = clocks + k2 * run_tokens.astype(jnp.float32)
        lat = (clock_dec[:, None] - run_f[..., RF_T_ARRIVE]) / jnp.maximum(
            d_true.astype(jnp.float32), 1.0)
        ok = (lat <= latency_L).astype(jnp.float32)
        fin = finished.astype(jnp.float32)
        score = run_f[..., RF_SCORE]
        acc = {
            "phi": acc["phi"] + slot_sum(fin * (score * ok)),
            "lat": acc["lat"] + slot_sum(fin * lat),
            "score": acc["score"] + slot_sum(fin * score),
            "done": acc["done"] + slot_sum(fin),
            "viol": acc["viol"] + slot_sum(fin * (1.0 - ok)),
            "wait": acc["wait"] + slot_sum(
                fin * (run_f[..., RF_T_ADMIT] - run_f[..., RF_T_ARRIVE])),
        }
        valid_after = validb & ~finished

        # --- admit: masked scatter of the chosen waiter into slot r_free ---
        slot_oh = adm[:, None] & (run_slots == r_free[:, None])     # (N, R)
        run_i = jnp.stack([
            (valid_after | slot_oh).astype(jnp.int32),
            jnp.where(slot_oh, head_p[:, None], p),
            jnp.where(slot_oh, head_i[:, WI_D_TRUE][:, None], d_true),
            jnp.where(slot_oh, 1, d_new),                  # prefill emits y1
            jnp.where(slot_oh, head_i[:, WI_RETRY][:, None],
                      run_i[..., RI_RETRY]),               # failover count
        ], axis=-1)
        adm_f = jnp.stack([head_f[:, WF_SCORE], head_f[:, WF_PRED_S],
                           head_f[:, WF_PRED_D], head_f[:, WF_T_ARRIVE],
                           clocks], axis=-1)               # (N, RUN_F_CH)
        run_f = jnp.where(slot_oh[..., None], adm_f[:, None, :], run_f)
        head_oh = adm[:, None] & (wait_slots == w_idx[:, None])     # (N, W)
        wvalidb = wvalidb & ~head_oh

        clock_adm = clocks + k1 * head_p.astype(jnp.float32)
        clocks = jnp.where(adm, clock_adm,
                           jnp.where(dec, clock_dec,
                                     jnp.where(idle, t_next, clocks)))
        return (run_i, run_f, wvalidb, clocks, acc,
                active_mask(run_i, wvalidb, clocks))

    wvalid0 = queues["wait_i"][..., WI_VALID] > 0
    run_i, run_f, wvalidb, clocks, acc, _ = jax.lax.while_loop(
        cond, body, (queues["run_i"], queues["run_f"], wvalid0, clocks, acc0,
                     active_mask(queues["run_i"], wvalid0, clocks)))
    clocks = jnp.maximum(clocks, t_next)  # idle experts jump forward
    queues = {"run_i": run_i, "run_f": run_f,
              "wait_i": wait_i0.at[..., WI_VALID].set(wvalidb.astype(jnp.int32)),
              "wait_f": wait_f0}
    return queues, clocks, acc


def _advance_shard_map(params: dict, latency_L: float, queues: dict,
                       clocks: jax.Array, t_next: jax.Array, *,
                       admit_order: str, mesh, shard_body: str = "pallas",
                       block_n=None) -> Tuple[dict, jax.Array, dict]:
    """Expert-axis sharded advance: each device of the mesh's ``expert``
    axis runs the fused lockstep kernel (``shard_body="pallas"``, the
    default — interpret mode off-TPU) or the plain ``advance_shard`` XLA
    loop (``shard_body="xla"``) on its (N/devices)-row shard; only the
    per-expert completion accumulators cross devices (one tiled
    all-gather), queue tensors and clocks stay device-local.  Both bodies
    are bit-identical, so the escape hatch exists for lowering inspection
    and debugging, not semantics."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding

    axis = sharding.EXPERT
    n = clocks.shape[0]
    n_shards = mesh.shape[axis]
    if n % n_shards != 0:
        raise ValueError(
            f"n_experts={n} not divisible by mesh axis '{axis}'={n_shards}")
    if shard_body not in ("pallas", "xla"):
        raise ValueError(f"unknown shard_body {shard_body!r}")

    e_spec = lambda x: sharding.expert_spec(mesh, n, x.ndim)

    def body(params, queues, clocks, t_next):
        if shard_body == "pallas":
            from repro.kernels.lockstep_advance.ops import lockstep_advance
            q, c, acc = lockstep_advance(params, queues, clocks, t_next,
                                         latency_L=float(latency_L),
                                         admit_order=admit_order,
                                         block_n=block_n)
        else:
            q, c, acc = advance_shard(params, latency_L, queues, clocks,
                                      t_next, admit_order=admit_order)
        acc = jax.tree.map(
            lambda x: jax.lax.all_gather(x, axis, tiled=True), acc)
        return q, c, acc

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree.map(e_spec, params), jax.tree.map(e_spec, queues),
                  e_spec(clocks), P()),
        out_specs=(jax.tree.map(e_spec, queues), e_spec(clocks),
                   {k: P() for k in
                    ("phi", "lat", "score", "wait", "done", "viol")}),
        check_vma=False)
    return fn(params, queues, clocks, t_next)


def advance_all(pool: ExpertPool, latency_L: float, queues: dict,
                clocks: jax.Array, t_next: jax.Array, *,
                backend: str = "xla", admit_order: str = "fifo",
                run_caps=None, wait_caps=None, up=None, k_scale=None,
                admit_min=None, mesh=None, block_n=None,
                shard_body: str = "pallas",
                ) -> Tuple[dict, jax.Array, dict]:
    """Advance all N experts to ``t_next`` on the selected backend (see the
    module docstring).  ``run_caps``/``wait_caps`` (N,) bound each
    expert's live slots for heterogeneous fleets (None = every packed
    slot); ``up`` (N,) bool marks available experts and ``k_scale`` (N,)
    scales the latency gradients (scenario conditions; None = all up, no
    scaling); ``admit_min`` (N,) f32 defers waiters whose ``pred_s`` is
    below the floor (overload shedding, ``repro.env.failover``; None = no
    floor); ``mesh`` (shard_map only) defaults to a 1-D ``("expert",)``
    mesh over all visible devices (multi-host aware, process-major order);
    ``block_n`` is the kernel's expert block size (None auto-tunes per
    backend, ``ops.default_block_n``); ``shard_body`` selects the
    per-shard body under shard_map — the fused Pallas kernel (default)
    or the plain XLA loop (bit-identical escape hatch).

    Returns (queues, clocks, acc) with acc entries shaped (N,).
    """
    if admit_order not in ADMIT_ORDERS:  # validate before any dispatch: the
        # pallas path compares the raw string, so a typo must not silently
        # fall through to the last ordering
        raise ValueError(f"unknown admit_order {admit_order!r}; "
                         f"expected one of {ADMIT_ORDERS}")
    with jax.named_scope(spans.ADVANCE):
        params = pool_params(pool, run_caps, wait_caps, up, k_scale, admit_min)
        if backend == "xla":
            return advance_shard(params, latency_L, queues, clocks, t_next,
                                 admit_order=admit_order)
        if backend == "pallas":
            from repro.kernels.lockstep_advance.ops import lockstep_advance
            return lockstep_advance(params, queues, clocks, t_next,
                                    latency_L=float(latency_L),
                                    admit_order=admit_order, block_n=block_n)
        if backend == "shard_map":
            if mesh is None:
                from repro.launch.mesh import make_expert_mesh
                mesh = make_expert_mesh()
            return _advance_shard_map(
                params, latency_L, queues, clocks, t_next,
                admit_order=admit_order, mesh=mesh, shard_body=shard_body,
                block_n=block_n)
    raise ValueError(f"unknown engine backend {backend!r}; "
                     f"expected one of {BACKENDS}")
