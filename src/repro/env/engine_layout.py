"""Packed SoA queue layout for the scheduling engine (layout layer).

Queue state is four tensors instead of the seed's 17 named arrays
(preserved in ``repro.env.engine_ref`` as the semantic oracle):

    run_i   (N, R, RUN_I_CH)  int32    [valid, p, d_true, d_cur, retry]
    run_f   (N, R, RUN_F_CH)  float32  [score, pred_s, pred_d, t_arrive, t_admit]
    wait_i  (N, W, WAIT_I_CH) int32    [valid, p, d_true, retry]
    wait_f  (N, W, WAIT_F_CH) float32  [score, pred_s, pred_d, t_arrive]

``retry`` counts failover re-dispatches (``repro.env.failover``): 0 for a
first-dispatch request, incremented each time the request is drained off
a failed expert and re-admitted elsewhere.  It rides through admission
(wait → run) unchanged and is surfaced to routers as an observation
channel (``features.REQ_RETRY``).  With failover disabled it is
identically zero everywhere, which keeps the packed tensors byte-identical
to the retry-free engine.

``valid`` is stored as 0/1 int32; the ``run_valid``/``wait_valid`` accessors
below return bools.  Invalid slots may hold stale field values — every
consumer must mask through the valid channel, never read raw slots.

Per-expert capacities (ragged fleets)
-------------------------------------
Queue tensors are packed to a SINGLE slot width per side (R = max run
capacity, W = max wait capacity) so jit shapes stay static, but each
expert may own fewer slots than the packed width: capacity vectors
``run_cap (N,)`` / ``wait_cap (N,)`` int32 bound the slots an expert may
ever use, and ``slot_valid(caps, width)`` gives the (N, width) bool mask
of live slots.  The layout contract is therefore:

  * slot j of expert n exists iff ``j < cap[n]``; slots at or beyond the
    cap are DEAD — never valid, never written, and masked out of every
    admission/selection (``engine.advance_shard``, ``push_wait``) and out
    of the ragged observation encoding (``features.build_obs``);
  * a fleet with uniform caps (cap[n] == width for all n) is byte-for-byte
    identical to the capacity-free layout — all masks are all-True and
    every consumer reduces to the pre-caps computation;
  * capacity vectors ride with the per-expert pool scalars (leading N
    axis), so they shard over the ``expert`` mesh axis exactly like
    ``k1``/``k2``/``mem_capacity`` (``distributed.sharding.expert_spec``).

``profiles.memory_caps`` derives ragged capacities from the pool's
per-expert memory by default.

This module is the ONLY place that knows the channel order.  Everything
outside the engine/kernel layer (``core/features.py``, ``core/routers.py``,
``env.impact_penalty``, tests) consumes queues exclusively through the
accessors, so the leading expert axis can be sharded across devices
(``engine.advance_all(backend="shard_map")``) without those consumers
caring where the rows live.

The lockstep semantics live in ``repro.env.engine``; the fused Pallas body
lives in ``repro.kernels.lockstep_advance``.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

# Channel indices for the packed layout (see module docstring).
RI_VALID, RI_P, RI_D_TRUE, RI_D_CUR, RI_RETRY = 0, 1, 2, 3, 4
RUN_I_CH = 5
RF_SCORE, RF_PRED_S, RF_PRED_D, RF_T_ARRIVE, RF_T_ADMIT = 0, 1, 2, 3, 4
RUN_F_CH = 5
WI_VALID, WI_P, WI_D_TRUE, WI_RETRY = 0, 1, 2, 3
WAIT_I_CH = 4
WF_SCORE, WF_PRED_S, WF_PRED_D, WF_T_ARRIVE = 0, 1, 2, 3
WAIT_F_CH = 4

# Channel order of the dense per-expert parameter pack consumed by the
# lockstep kernel (``kernels.lockstep_advance``): pool scalars, ragged
# capacity vectors, scenario availability and the overload-shedding
# admission floor travel as ONE (N, PAR_CH) float32 operand.  Caps are
# small ints and ``up`` is 0/1, both exactly representable in float32;
# ``engine.pool_params`` builds the pack once per window so the kernel's
# hot loop never restacks it.
(PAR_K1, PAR_K2, PAR_MEM_CAP, PAR_MPT, PAR_RUN_CAP, PAR_WAIT_CAP,
 PAR_UP, PAR_ADMIT_MIN) = range(8)
PAR_CH = 8
# Capacity sentinel for capacity-free packs: 2**24 is exactly
# representable in float32 and far above any packed slot width, so
# ``iota < PAR_CAP_FREE`` is all-True exactly like ``iota < width`` —
# pool_params can build the pack without knowing the queue widths while
# staying bit-identical to explicit full-width caps.
PAR_CAP_FREE = float(2 ** 24)


def channel_major(x: jax.Array) -> jax.Array:
    """(..., N, S, CH) -> (..., CH, N, S): one (N, S) plane per channel.

    The channel-major form is the lockstep kernel's operand layout: the
    kernel reads channel ``c`` of a block as ``ref[c]``, a (block_n, S)
    plane with experts on sublanes and slots on lanes, so its body never
    reshapes a vector.  Mosaic cannot lower a reshape that splits the lane
    dim (``(B, S*CH) -> (B, S, CH)`` is refused as an unsupported shape
    cast), and a (B, S, CH) block would put the 4-5 channels on lanes and
    index them one lane at a time.  Being an axis permutation the
    transform is bit-identical; only the kernel's call boundary
    (``ops.lockstep_advance``) sees it — everything else keeps the
    (N, S, CH) accessors above.  Leading batch dims (a vmapped env) pass
    through.
    """
    return jnp.moveaxis(x, -1, -3)


def channel_minor(x: jax.Array) -> jax.Array:
    """Inverse of :func:`channel_major`: (..., CH, N, S) -> (..., N, S, CH)."""
    return jnp.moveaxis(x, -3, -1)


def empty_queues(n: int, r: int, w: int) -> dict:
    return {
        "run_i": jnp.zeros((n, r, RUN_I_CH), jnp.int32),
        "run_f": jnp.zeros((n, r, RUN_F_CH), jnp.float32),
        "wait_i": jnp.zeros((n, w, WAIT_I_CH), jnp.int32),
        "wait_f": jnp.zeros((n, w, WAIT_F_CH), jnp.float32),
    }


def slot_valid(caps: jax.Array, width: int) -> jax.Array:
    """(N, width) bool mask of live slots for per-expert capacities
    ``caps (N,)``: slot j of expert n exists iff j < caps[n] (see the
    module docstring's ragged-capacity contract)."""
    return jnp.arange(width)[None, :] < jnp.asarray(caps, jnp.int32)[:, None]


# ---------------------------------------------------------------------------
# Thin accessors — keep features.build_obs, routers and tests readable.
# ---------------------------------------------------------------------------


def run_valid(q: dict) -> jax.Array:
    return q["run_i"][..., RI_VALID].astype(jnp.bool_)


def run_p(q: dict) -> jax.Array:
    return q["run_i"][..., RI_P]


def run_d_true(q: dict) -> jax.Array:
    return q["run_i"][..., RI_D_TRUE]


def run_d_cur(q: dict) -> jax.Array:
    return q["run_i"][..., RI_D_CUR]


def run_retry(q: dict) -> jax.Array:
    return q["run_i"][..., RI_RETRY]


def run_score(q: dict) -> jax.Array:
    return q["run_f"][..., RF_SCORE]


def run_pred_s(q: dict) -> jax.Array:
    return q["run_f"][..., RF_PRED_S]


def run_pred_d(q: dict) -> jax.Array:
    return q["run_f"][..., RF_PRED_D]


def run_t_arrive(q: dict) -> jax.Array:
    return q["run_f"][..., RF_T_ARRIVE]


def run_t_admit(q: dict) -> jax.Array:
    return q["run_f"][..., RF_T_ADMIT]


def wait_valid(q: dict) -> jax.Array:
    return q["wait_i"][..., WI_VALID].astype(jnp.bool_)


def wait_p(q: dict) -> jax.Array:
    return q["wait_i"][..., WI_P]


def wait_d_true(q: dict) -> jax.Array:
    return q["wait_i"][..., WI_D_TRUE]


def wait_retry(q: dict) -> jax.Array:
    return q["wait_i"][..., WI_RETRY]


def wait_score(q: dict) -> jax.Array:
    return q["wait_f"][..., WF_SCORE]


def wait_pred_s(q: dict) -> jax.Array:
    return q["wait_f"][..., WF_PRED_S]


def wait_pred_d(q: dict) -> jax.Array:
    return q["wait_f"][..., WF_PRED_D]


def wait_t_arrive(q: dict) -> jax.Array:
    return q["wait_f"][..., WF_T_ARRIVE]


def push_wait(q: dict, n: jax.Array, *, p: jax.Array, d_true: jax.Array,
              score: jax.Array, pred_s: jax.Array, pred_d: jax.Array,
              t: jax.Array, gate=True, wait_cap=None,
              retry=0) -> Tuple[dict, jax.Array]:
    """Masked push of one request into expert ``n``'s first free waiting
    slot (no-op when the queue is full or ``gate`` is False).  With a
    per-expert capacity vector ``wait_cap (N,)``, only slots below expert
    ``n``'s cap count as free — a full in-cap queue rejects the push even
    when dead padded slots remain.  ``retry`` is the failover re-dispatch
    count (0 for fresh arrivals).  The single place that knows the
    wait-side channel order; returns (queues, pushed)."""
    free = ~wait_valid(q)[n]
    if wait_cap is not None:
        free = free & slot_valid(wait_cap, q["wait_i"].shape[1])[n]
    pushed = jnp.any(free) & gate
    slot = jnp.argmax(free)
    new_i = jnp.stack([pushed.astype(jnp.int32),
                       jnp.asarray(p, jnp.int32),
                       jnp.asarray(d_true, jnp.int32),
                       jnp.asarray(retry, jnp.int32)])
    new_f = jnp.stack([jnp.asarray(score, jnp.float32),
                       jnp.asarray(pred_s, jnp.float32),
                       jnp.asarray(pred_d, jnp.float32),
                       jnp.asarray(t, jnp.float32)])
    q = {
        **q,
        "wait_i": q["wait_i"].at[n, slot].set(
            jnp.where(pushed, new_i, q["wait_i"][n, slot])),
        "wait_f": q["wait_f"].at[n, slot].set(
            jnp.where(pushed, new_f, q["wait_f"][n, slot])),
    }
    return q, pushed


def mem_used(q: dict, mem_per_token: jax.Array) -> jax.Array:
    """(N,) bytes currently resident per expert."""
    tok = jnp.where(run_valid(q), run_p(q) + run_d_cur(q), 0)
    return jnp.sum(tok, axis=-1).astype(jnp.float32) * mem_per_token
