"""Pallas lockstep-advance kernel for the scheduling engine.

Fuses the masked admit/decode/idle body of ``repro.env.engine.advance_shard``
over an expert block: grid is (N / block_n,) and each program runs the
whole data-dependent ``while_loop`` for its block with every queue tensor
resident in VMEM — the XLA backend instead streams the (N, R/W, CH)
tensors through HBM on every loop iteration.  Because lockstep actions
only touch an expert's own rows, a per-block loop (trip count = max over
the block) replays exactly the same per-expert action sequence as the
global loop (trip count = max over all N), so results are bit-identical;
blocks with fast-draining experts simply stop earlier, doing strictly
less masked work than the global loop.

What the TPU compiler (Mosaic) requires of the body, vs the jnp body in
``engine.advance_shard``:

  * queue operands arrive CHANNEL-MAJOR — (CH, N, S) via
    ``engine_layout.channel_major`` — and the body reads channel ``c`` as
    the (block_n, S) plane ``ref[c]`` (experts on sublanes, slots on
    lanes).  No vector is ever reshaped: Mosaic refuses a reshape that
    splits the lane dim.  The loop carries each channel as its own plane;
  * every per-expert value is kept 2-D as (block_n, 1) (``keepdims``
    reductions, ``ref[:, c:c+1]`` column reads of the parameter pack);
  * the loop carries only int32 and float32 vectors — masks ride as 0/1
    int32, because Mosaic cannot carry i1 vectors through ``scf.yield``,
    and boolean reductions are int32 max reductions;
  * ``argmin`` / ``take_along_axis`` are replaced with broadcasted-iota
    min-index selection and one-hot masked reductions (no gathers), with
    the same first-index tie-breaking;
  * float slot sums use ``engine.slot_sum``'s explicit left-to-right
    order, shared with the XLA body, so the two compilers cannot round
    the completion accumulators differently;
  * the per-expert pool scalars, the ragged capacity vectors, the
    scenario availability mask and the shedding floor travel in one dense
    (block_n, PAR_CH) float32 operand (``engine_layout.PAR_*`` channel
    order, built once per window by ``engine.pool_params``) — caps are
    small ints and up is 0/1, exactly representable in float32, and a
    uniform always-up fleet (caps == packed widths or the
    ``PAR_CAP_FREE`` sentinel, up all-ones) makes every mask all-True.
    A down expert (up == 0) admits nothing and decodes nothing: its only
    permitted action is idle.  Straggler ``k_scale`` factors arrive
    pre-folded into k1/k2 (``engine.pool_params``);
  * the six accumulators leave as one (N, 6) float32 tensor (channel
    order ``ops.ACC_KEYS``) and clocks ride as (N, 1).

Off-TPU the kernel runs in interpret mode (see ``ops.lockstep_advance``,
which also carries the ``use_pallas`` escape hatch, the default
``block_n`` and the ``ref.py`` oracle = the engine's XLA loop).  The sharded engine backend dispatches here per shard
(``engine._advance_shard_map``), so multi-device fleets inherit the
fused body too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.env.engine import admit_key, slot_sum
from repro.env.engine_layout import (
    RI_VALID, RI_P, RI_D_TRUE, RI_D_CUR, RI_RETRY, RUN_I_CH,
    RF_SCORE, RF_T_ARRIVE, RF_T_ADMIT, RUN_F_CH,
    WI_VALID, WI_P, WI_D_TRUE, WI_RETRY, WAIT_I_CH,
    WF_SCORE, WF_PRED_S, WF_PRED_D, WF_T_ARRIVE, WAIT_F_CH,
    PAR_K1, PAR_K2, PAR_MEM_CAP, PAR_MPT, PAR_RUN_CAP, PAR_WAIT_CAP,
    PAR_UP, PAR_ADMIT_MIN, PAR_CH,
)

# python float (not a jnp scalar: pallas_call forbids captured constants)
INF = 1e30
N_ACC = 6  # phi, lat, score, wait, done, viol  (ops.ACC_KEYS order)


def _any(mask: jax.Array) -> jax.Array:
    """(B, S) bool -> (B, 1) bool "any over slots", as an int32 max."""
    return jnp.max(mask.astype(jnp.int32), axis=-1, keepdims=True) > 0


def _first_index(mask: jax.Array, iota: jax.Array, size: int) -> jax.Array:
    """(B, 1) lowest slot with mask True (== argmax semantics on bool),
    else ``size``.  Gather-free; safe on the TPU vector unit."""
    return jnp.min(jnp.where(mask, iota, size), axis=-1, keepdims=True)


def _onehot_pick(sel: jax.Array, field: jax.Array) -> jax.Array:
    """(B, S) one-hot selector x (B, S) field -> (B, 1) selected value."""
    zero = jnp.zeros((), field.dtype)
    return jnp.sum(jnp.where(sel, field, zero), axis=-1, keepdims=True)


def _lockstep_kernel(tn_ref, run_i_ref, run_f_ref, wait_i_ref, wait_f_ref,
                     par_ref, clk_ref,
                     run_i_out, run_f_out, wvalid_out, clk_out, acc_out,
                     *, latency_L: float, admit_order: str):
    t_next = tn_ref[0, 0]
    _, bn, r_cap = run_i_ref.shape                         # (CH, B, R)
    w_cap = wait_i_ref.shape[2]
    par = lambda c: par_ref[:, c:c + 1]                    # (B, 1) f32
    k1, k2 = par(PAR_K1), par(PAR_K2)
    cap, mpt = par(PAR_MEM_CAP), par(PAR_MPT)
    upv = par(PAR_UP) > 0.5                                # availability
    admit_min = par(PAR_ADMIT_MIN)                         # shed floor

    run_iota = jax.lax.broadcasted_iota(jnp.int32, (bn, r_cap), 1)
    wait_iota = jax.lax.broadcasted_iota(jnp.int32, (bn, w_cap), 1)
    run_ok = run_iota < par(PAR_RUN_CAP).astype(jnp.int32)   # (B, R) live
    wait_ok = wait_iota < par(PAR_WAIT_CAP).astype(jnp.int32)  # (B, W)

    # wait side: fields are loop-invariant, only the valid bit is carried
    wait_p0 = wait_i_ref[WI_P]
    wait_d_true0 = wait_i_ref[WI_D_TRUE]
    wait_retry0 = wait_i_ref[WI_RETRY]
    wait_f0 = [wait_f_ref[c] for c in range(WAIT_F_CH)]    # (B, W) f32 each
    w_sort_key = admit_key(wait_f0[WF_T_ARRIVE], wait_f0[WF_PRED_S],
                           wait_f0[WF_PRED_D], admit_order, latency_L)
    # overload-shedding floor: like the sort key, loop-invariant per window
    w_admissible = wait_f0[WF_PRED_S] >= admit_min         # (B, W)

    def active_mask(valid, wvalid, clocks):
        has_work = _any(valid > 0) | _any(wvalid > 0)
        return ((clocks < t_next) & has_work).astype(jnp.int32)

    def cond(c):
        return jnp.max(c[-1]) > 0

    def body(c):
        run_i, run_f, wvalid, clocks, acc, active = c
        active = active > 0
        validb = run_i[RI_VALID] > 0                       # (B, R)
        p = run_i[RI_P]
        d_true = run_i[RI_D_TRUE]
        d_cur = run_i[RI_D_CUR]

        run_tokens = jnp.sum(jnp.where(validb, p + d_cur, 0), -1,
                             keepdims=True)                # (B, 1)
        mem = run_tokens * mpt

        # choose action per expert: admit > decode > idle (beyond-cap
        # slots are dead: masked out of the waiter pick and slot search)
        w_live = (wvalid > 0) & wait_ok & w_admissible
        w_key = jnp.where(w_live, w_sort_key, INF)
        min_key = jnp.min(w_key, axis=-1, keepdims=True)
        w_idx = _first_index(w_key == min_key, wait_iota, w_cap)    # (B, 1)
        w_has = _any(w_live)
        free = ~validb & run_ok
        r_free = _first_index(free, run_iota, r_cap)                # (B, 1)
        r_has_space = _any(free)
        head_sel = wait_iota == w_idx                               # (B, W)
        head_p = _onehot_pick(head_sel, wait_p0)
        fits = mem + mpt * (head_p.astype(jnp.float32) + 1.0) <= cap
        can_admit = w_has & r_has_space & fits & upv
        r_has = _any(validb)

        adm = active & can_admit
        dec = active & ~can_admit & r_has & upv
        idle = active & ~can_admit & ~(r_has & upv)

        # --- decode: masked in-place over this iteration's decoding rows ---
        dec_rows = dec & validb                            # (B, R)
        d_new = d_cur + dec_rows.astype(jnp.int32)
        finished = dec_rows & (d_new >= d_true)
        clock_dec = clocks + k2 * run_tokens.astype(jnp.float32)
        lat = (clock_dec - run_f[RF_T_ARRIVE]) / jnp.maximum(
            d_true.astype(jnp.float32), 1.0)
        ok = (lat <= latency_L).astype(jnp.float32)
        fin = finished.astype(jnp.float32)
        score = run_f[RF_SCORE]
        wait_t = run_f[RF_T_ADMIT] - run_f[RF_T_ARRIVE]
        add = lambda x: slot_sum(x, keepdims=True)
        acc = (acc[0] + add(fin * (score * ok)),           # ops.ACC_KEYS
               acc[1] + add(fin * lat),
               acc[2] + add(fin * score),
               acc[3] + add(fin * wait_t),
               acc[4] + add(fin),
               acc[5] + add(fin * (1.0 - ok)))
        valid_after = validb & ~finished

        # --- admit: masked scatter of the chosen waiter into slot r_free ---
        slot_oh = adm & (run_iota == r_free)                        # (B, R)
        head_d_true = _onehot_pick(head_sel, wait_d_true0)
        head_retry = _onehot_pick(head_sel, wait_retry0)
        run_i = (
            (valid_after | slot_oh).astype(jnp.int32),
            jnp.where(slot_oh, head_p, p),
            jnp.where(slot_oh, head_d_true, d_true),
            jnp.where(slot_oh, 1, d_new),                  # prefill emits y1
            jnp.where(slot_oh, head_retry, run_i[RI_RETRY]),  # failover
        )
        adm_f = (_onehot_pick(head_sel, wait_f0[WF_SCORE]),   # RF_* order
                 _onehot_pick(head_sel, wait_f0[WF_PRED_S]),
                 _onehot_pick(head_sel, wait_f0[WF_PRED_D]),
                 _onehot_pick(head_sel, wait_f0[WF_T_ARRIVE]),
                 clocks)
        run_f = tuple(jnp.where(slot_oh, a, f) for a, f in zip(adm_f, run_f))
        wvalid = jnp.where(adm & head_sel, 0, wvalid)

        clock_adm = clocks + k1 * head_p.astype(jnp.float32)
        clocks = jnp.where(adm, clock_adm,
                           jnp.where(dec, clock_dec,
                                     jnp.where(idle, t_next, clocks)))
        return (run_i, run_f, wvalid, clocks, acc,
                active_mask(run_i[RI_VALID], wvalid, clocks))

    run_i0 = tuple(run_i_ref[c] for c in range(RUN_I_CH))  # (B, R) each
    run_f0 = tuple(run_f_ref[c] for c in range(RUN_F_CH))
    wvalid0 = (wait_i_ref[WI_VALID] > 0).astype(jnp.int32)
    clocks0 = clk_ref[...]                                 # (B, 1)
    acc0 = tuple(jnp.zeros((bn, 1), jnp.float32) for _ in range(N_ACC))
    run_i, run_f, wvalid, clocks, acc, _ = jax.lax.while_loop(
        cond, body, (run_i0, run_f0, wvalid0, clocks0, acc0,
                     active_mask(run_i0[RI_VALID], wvalid0, clocks0)))

    for c in range(RUN_I_CH):
        run_i_out[c] = run_i[c]
    for c in range(RUN_F_CH):
        run_f_out[c] = run_f[c]
    wvalid_out[...] = wvalid
    clk_out[...] = jnp.maximum(clocks, t_next)             # idle jump forward
    for k in range(N_ACC):
        acc_out[:, k:k + 1] = acc[k]


def lockstep_advance_call(run_i, run_f, wait_i, wait_f, par, clocks, t_next,
                          *, latency_L: float, admit_order: str,
                          block_n: int, interpret: bool = False):
    """Raw pallas_call over expert blocks — CHANNEL-MAJOR operand layout.

    run_i (CI, N, R) i32 | run_f (CF, N, R) f32 | wait_i (WCI, N, W) i32 |
    wait_f (WCF, N, W) f32 (``engine_layout.channel_major`` of the packed
    queues) | par (N, PAR_CH) f32 [k1, k2, cap, mpt, run_cap, wait_cap,
    up, admit_min] | clocks (N, 1) f32 | t_next (1, 1) f32.  N must divide
    by block_n, and block_n must be N or a multiple of 8 (the sublane
    tile).

    Returns (run_i (CI, N, R), run_f (CF, N, R), wait_valid (N, W) i32,
    clocks (N, 1), acc (N, 6) f32 in ``ops.ACC_KEYS`` order).
    """
    assert run_i.shape[0] == RUN_I_CH and run_f.shape[0] == RUN_F_CH
    assert wait_i.shape[0] == WAIT_I_CH and wait_f.shape[0] == WAIT_F_CH
    _, n, r_cap = run_i.shape
    w_cap = wait_i.shape[2]
    assert n % block_n == 0, (n, block_n)

    kernel = functools.partial(_lockstep_kernel, latency_L=latency_L,
                               admit_order=admit_order)
    rows = lambda w: pl.BlockSpec((block_n, w), lambda i: (i, 0))
    planes = lambda ch, w: pl.BlockSpec((ch, block_n, w),
                                        lambda i: (0, i, 0))
    return pl.pallas_call(
        kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            planes(RUN_I_CH, r_cap), planes(RUN_F_CH, r_cap),
            planes(WAIT_I_CH, w_cap), planes(WAIT_F_CH, w_cap),
            rows(PAR_CH), rows(1),
        ],
        out_specs=[
            planes(RUN_I_CH, r_cap), planes(RUN_F_CH, r_cap),
            rows(w_cap), rows(1), rows(N_ACC),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((RUN_I_CH, n, r_cap), jnp.int32),
            jax.ShapeDtypeStruct((RUN_F_CH, n, r_cap), jnp.float32),
            jax.ShapeDtypeStruct((n, w_cap), jnp.int32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, N_ACC), jnp.float32),
        ],
        interpret=interpret,
        # a stable name: trace reductions find the kernel by it
        name="lockstep_advance",
    )(t_next, run_i, run_f, wait_i, wait_f, par, clocks)
