"""Microbenchmark: `advance_all` alone — two sections:

  1. lockstep packed engine (backend="xla") vs the seed reference
     (`repro.env.engine_ref`), N ∈ {6, 16, 64}, and
  2. backend sweep at fleet scale, N ∈ {64, 256, 512, 1024}: "xla" vs
     "pallas" (fused lockstep_advance kernel; interpret mode off-TPU) vs
     "shard_map" (expert axis over the local device mesh).

Each benchmark step injects one request into a round-robin expert's waiting
queue (so the engine never drains) and advances all experts to the next
Poisson arrival; steps/sec is the whole scan's throughput.

    PYTHONPATH=src python -m benchmarks.bench_engine [--json]
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp

from benchmarks import common
from repro.env import engine, engine_ref, profiles

BIG_N = (64, 256, 512, 1024)

R, W = 5, 5
LAT_L = 0.030
LAM = 5.0
REQ = {"p": 160, "d_true": 48, "score": 0.7, "pred_s": 0.7, "pred_d": 48.0}


def _inject_packed(q, n, t):
    q, _ = engine.push_wait(q, n, p=REQ["p"], d_true=REQ["d_true"],
                            score=REQ["score"], pred_s=REQ["pred_s"],
                            pred_d=REQ["pred_d"], t=t)
    return q


def _inject_named(q, n, t):
    free = ~q["wait_valid"][n]
    do = jnp.any(free)
    slot = jnp.argmax(free)
    set_at = lambda arr, val: arr.at[n, slot].set(
        jnp.where(do, val, arr[n, slot]))
    q = dict(q)
    q["wait_valid"] = set_at(q["wait_valid"], do)
    q["wait_p"] = set_at(q["wait_p"], jnp.asarray(REQ["p"], jnp.int32))
    q["wait_d_true"] = set_at(q["wait_d_true"],
                              jnp.asarray(REQ["d_true"], jnp.int32))
    q["wait_score"] = set_at(q["wait_score"],
                             jnp.asarray(REQ["score"], jnp.float32))
    q["wait_pred_s"] = set_at(q["wait_pred_s"],
                              jnp.asarray(REQ["pred_s"], jnp.float32))
    q["wait_pred_d"] = set_at(q["wait_pred_d"],
                              jnp.asarray(REQ["pred_d"], jnp.float32))
    q["wait_t_arrive"] = set_at(q["wait_t_arrive"], t)
    return q


def _make_runner(pool, n_experts, n_steps, empty_queues, inject, advance):
    dts = jax.random.exponential(jax.random.PRNGKey(0), (n_steps,)) / LAM
    experts = jnp.arange(n_steps) % n_experts

    @jax.jit
    def run():
        def step(carry, x):
            q, clocks, t = carry
            dt, n = x
            q = inject(q, n.astype(jnp.int32), t)
            t_next = t + dt
            q, clocks, acc = advance(pool, LAT_L, q, clocks, t_next)
            return (q, clocks, t_next), acc["done"]
        init = (empty_queues(n_experts, R, W),
                jnp.zeros((n_experts,), jnp.float32), jnp.float32(0.0))
        (q, clocks, _), done = jax.lax.scan(step, init, (dts, experts))
        return clocks, jnp.sum(done)

    return run


def _time(run, repeats: int = 3):
    """Returns (best seconds, the warm-up call's result) — callers read
    derived counters from the result instead of re-running the scan."""
    out = run()
    jax.block_until_ready(out)  # compile + warm up
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        best = min(best, time.perf_counter() - t0)
    return best, out


def backend_sweep(n_list=BIG_N, n_steps: int = 200,
                  prefix: str = "engine/advance_all",
                  backends=engine.BACKENDS) -> None:
    """Advance-throughput rows for every engine backend at fleet scale.
    Reused by bench_scaling (large-N sweep) — the acceptance gate is that
    at N=512 the sharded/kernel rows are no slower than backend="xla".
    n_steps is part of the measurement (it sets how many experts ever see
    work), so `run.py --check` runs must keep the default to stay
    comparable with the committed BENCH_engine.json baseline.  Rows that
    execute the lockstep kernel (pallas; shard_map's per-shard body) carry
    the RESOLVED interpret flag and auto-tuned block_n so a baseline
    recorded in interpret mode is never diffed against real-TPU numbers
    (common.check_against_baseline enforces this via the file-level
    ``engine_interpret`` field)."""
    from repro.kernels.lockstep_advance import ops as lockstep_ops

    interp = lockstep_ops.resolve_interpret(None)
    for n_experts in n_list:
        pool = profiles.make_pool(n_experts)
        block_n = lockstep_ops.default_block_n(n_experts)
        kflags = f";interpret={int(interp)};block_n={block_n}"
        secs = {}
        for backend in backends:
            adv = functools.partial(engine.advance_all, backend=backend)
            runner = _make_runner(pool, n_experts, n_steps,
                                  engine.empty_queues, _inject_packed, adv)
            secs[backend], (_, done) = _time(runner)
            common.emit(
                f"{prefix}/N{n_experts}/{backend}",
                secs[backend] / n_steps * 1e6,
                f"steps_per_s={n_steps / secs[backend]:.1f};"
                f"done={float(done):.0f}"
                + (kflags if backend != "xla" else ""))
        if "xla" in secs:
            for backend in (b for b in backends if b != "xla"):
                common.emit(f"{prefix}/N{n_experts}/{backend}_vs_xla", 0.0,
                            f"x={secs['xla'] / secs[backend]:.2f}")


def run(n_steps: int = 2000, json_out: bool = False) -> None:
    for n_experts in (6, 16, 64):
        pool = profiles.make_pool(n_experts)
        new_run = _make_runner(pool, n_experts, n_steps,
                               engine.empty_queues, _inject_packed,
                               engine.advance_all)
        ref_run = _make_runner(pool, n_experts, n_steps,
                               engine_ref.empty_queues, _inject_named,
                               engine_ref.advance_all)
        new_s, (_, done_new) = _time(new_run)
        ref_s, (_, done_ref) = _time(ref_run)
        for label, secs, done in (("lockstep", new_s, done_new),
                                  ("seed_ref", ref_s, done_ref)):
            common.emit(
                f"engine/advance_all/N{n_experts}/{label}",
                secs / n_steps * 1e6,
                f"steps_per_s={n_steps / secs:.1f};done={float(done):.0f}")
        common.emit(f"engine/advance_all/N{n_experts}/speedup", 0.0,
                    f"x={ref_s / new_s:.2f}")
    backend_sweep()  # fixed 200 steps: rows must match the --check baseline
    if json_out:
        common.write_json("engine")


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--json", action="store_true")
    p.add_argument("--steps", type=int, default=2000)
    args = p.parse_args()
    run(n_steps=args.steps, json_out=args.json)
