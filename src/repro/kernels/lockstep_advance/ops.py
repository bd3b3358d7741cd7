"""Jitted wrapper for the lockstep-advance kernel (engine ``"pallas"``
backend).

Dispatch mirrors the repo's kernel idiom: ``use_pallas=False`` falls back
to ``ref.lockstep_advance_ref`` (the engine's XLA while-loop), and off-TPU
the kernel runs in interpret mode (``resolve_interpret``).  N is padded
to a multiple of ``block_n`` with inert experts (no work, zero params —
including zero run/wait capacity) that the lockstep loop never touches;
their rows are dropped before returning.

The kernel consumes the queues CHANNEL-MAJOR (``engine_layout.
channel_major``: (N, S, CH) -> (CH, N, S)); the permutation and its
inverse happen here at the call boundary, so callers keep the packed
(N, S, CH) layout and the kernel layout is invisible outside this
module.

``params`` normally carries the prebuilt (N, PAR_CH) float32 parameter
pack under ``"par"`` (``engine.pool_params`` stacks it once per window —
the hot loop never restacks; ``engine_layout.PAR_*`` channel order).
Hand-built param dicts without ``"par"`` fall back to stacking here from
the optional per-expert ``run_cap``/``wait_cap`` (N,) capacity vectors
(ragged heterogeneous fleets), ``up`` (N,) bool availability mask
(scenario fleets) and ``admit_min`` (N,) f32 overload-shedding admission
floor (failover fleets); absent entries default to the ``PAR_CAP_FREE``
sentinel (every slot live) / all-up / no floor (-INF).  Padded inert
experts get a zero admit_min, which is harmless: they own zero capacity
and no waiters.

``block_n=None`` picks the expert block
(``default_block_n``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.env.engine_layout import (
    PAR_CAP_FREE, WI_VALID, channel_major, channel_minor,
)
from repro.kernels.lockstep_advance.kernel import lockstep_advance_call

ACC_KEYS = ("phi", "lat", "score", "wait", "done", "viol")


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Kernel execution mode: an explicit flag wins; ``None`` auto-selects
    interpret everywhere except a real TPU backend.  Exposed so the
    benchmark harness can stamp the resolved flag into every emitted row
    — interpret-mode timings must never be compared against real-TPU
    baselines (``benchmarks/common.check_against_baseline``)."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"


def default_block_n(n: int) -> int:
    """The expert block used when callers pass ``block_n=None``.

    In interpret mode the block only bounds per-block while-loop trip
    counts.  On a TPU the bound is VMEM.  Every (block_n, S) channel
    plane pads its S = 4..5 lanes to a 128-lane tile, i.e. 512 bytes per
    expert row, and a grid step holds 33 such planes (20 in, 13 out, each
    double-buffered by the pipeline) plus the loop's carried planes and
    temporaries.  The v5e compiler sizes the kernel's scoped VMEM at
    16.5 MiB for block_n = 256 at R = W = 5, over its 16 MiB default
    limit, so the block is 128: a multiple of the 8-row sublane tile, so
    blocks of a padded fleet stay aligned.  ``tests/test_tpu_compile.py``
    compiles it for v5e at N in {6, 1024, 4096}.
    """
    return min(n, 128)


@functools.partial(jax.jit, static_argnames=("latency_L", "admit_order",
                                             "block_n", "use_pallas",
                                             "interpret"))
def lockstep_advance(params: dict, queues: dict, clocks: jax.Array,
                     t_next: jax.Array, *, latency_L: float,
                     admit_order: str = "fifo",
                     block_n: Optional[int] = None,
                     use_pallas: bool = True,
                     interpret: Optional[bool] = None,
                     ) -> Tuple[dict, jax.Array, dict]:
    """Same contract as ``engine.advance_shard`` (and bit-identical to it):
    (params, queues, clocks, t_next) -> (queues, clocks, acc)."""
    if not use_pallas:
        from repro.kernels.lockstep_advance.ref import lockstep_advance_ref
        return lockstep_advance_ref(params, queues, clocks, t_next,
                                    latency_L=latency_L,
                                    admit_order=admit_order)
    interpret = resolve_interpret(interpret)

    n = clocks.shape[0]
    if block_n is None:
        block_n = default_block_n(n)
    bn = min(block_n, n)
    pad = (-n) % bn
    par = params.get("par")
    if par is None:
        # Hand-built params (tests, ref harnesses) — pack here.  The
        # PAR_CAP_FREE sentinel is bit-identical to full-width caps:
        # every slot-iota comparison stays all-True.
        run_cap = params.get("run_cap",
                             jnp.full((n,), PAR_CAP_FREE, jnp.float32))
        wait_cap = params.get("wait_cap",
                              jnp.full((n,), PAR_CAP_FREE, jnp.float32))
        up = params.get("up", jnp.ones((n,), jnp.bool_))
        admit_min = params.get("admit_min",
                               jnp.full((n,), -1e30, jnp.float32))
        par = jnp.stack([params["k1"], params["k2"], params["mem_capacity"],
                         params["mem_per_token"],
                         run_cap.astype(jnp.float32),
                         wait_cap.astype(jnp.float32),
                         up.astype(jnp.float32),
                         admit_min.astype(jnp.float32)],
                        axis=-1)
    par = par.astype(jnp.float32)
    run_i = channel_major(queues["run_i"])
    run_f = channel_major(queues["run_f"])
    wait_i = channel_major(queues["wait_i"])
    wait_f = channel_major(queues["wait_f"])
    clk = clocks[:, None].astype(jnp.float32)
    if pad:
        grow = lambda x: jnp.pad(x, [(0, 0)] * (x.ndim - 2)
                                 + [(0, pad), (0, 0)])
        run_i, run_f, wait_i, wait_f, par, clk = map(
            grow, (run_i, run_f, wait_i, wait_f, par, clk))

    run_i, run_f, wvalid, clk, acc = lockstep_advance_call(
        run_i, run_f, wait_i, wait_f, par, clk,
        jnp.reshape(t_next, (1, 1)).astype(jnp.float32),
        latency_L=latency_L, admit_order=admit_order, block_n=bn,
        interpret=interpret)

    cut = lambda x: x[..., :n, :] if pad else x
    queues = {
        "run_i": channel_minor(cut(run_i)),
        "run_f": channel_minor(cut(run_f)),
        "wait_i": queues["wait_i"].at[..., WI_VALID].set(cut(wvalid)),
        "wait_f": queues["wait_f"],
    }
    acc = {k: cut(acc)[:, i] for i, k in enumerate(ACC_KEYS)}
    return queues, cut(clk)[:, 0], acc
