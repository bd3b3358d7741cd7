#!/usr/bin/env python3
"""Smoke run of the main path on a TPU, through the entry points users call.

    python chip_smoke.py [--seed 0]      # one chip: the three phases below
    python chip_smoke.py --chips 4       # four chips: the sharded checks only

One chip (the default):

  1. kernel  — ``engine.advance_all`` at N=1024 experts over 200 windows of
     seeded arrivals, with memory-derived ragged queue caps, one expert
     down and a per-expert admission floor, once with ``backend="pallas"``
     (the compiled ``lockstep_advance`` kernel) and once with
     ``backend="xla"``.  Queues, clocks and accumulators must be bit-
     identical, and the compiled pallas program must hold the kernel
     (``tpu_custom_call``).
  2. train   — ``training.train_router`` at the paper's configuration
     (``EnvConfig``/``SACConfig``/``TrainConfig`` defaults, 6 experts,
     R=W=5, L=30 ms) on the pallas engine, for enough iterations that SAC
     updates run past the replay warm-up.  Rewards and losses must be
     finite and the critic loss nonzero.
  3. eval    — ``training.evaluate`` of the trained router, 2,000 steps x 2
     envs, on the pallas and on the xla engine: finite QoS, completed
     requests, identical metrics.

``--chips 4``: ``advance_all(backend="shard_map")`` on a 4-device expert
mesh at N=4096 against single-device ``backend="pallas"``, and 3 router-
training iterations on a 2x2 ("data", "expert") mesh against the
unsharded run; both bit-identical.

Every input is built from ``--seed``.  The script exits nonzero, printing
no result line, unless JAX runs on a TPU with the kernel compiled (not
interpreted).  The last line of a passing run is one JSON object with the
device JAX reports.  Times printed on the way are from this one smoke run
and are not a benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

R = W = 5            # the paper's run/wait queue widths
LAT_L = 0.030        # the paper's latency requirement, s/token
LAM_PER_EXPERT = 5.0 / 6.0   # the paper's lambda=5/s over six experts
WINDOWS, ARRIVALS = 200, 64  # engine drive: windows x arrivals per window


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def same_trees(name: str, a, b) -> bool:
    """Bit-for-bit equality of two pytrees; prints what differs."""
    import jax
    import numpy as np

    la = jax.tree_util.tree_leaves_with_path(a)
    lb = jax.tree.leaves(b)
    if len(la) != len(lb):
        log(f"{name}: {len(la)} vs {len(lb)} leaves")
        return False
    ok = True
    for (path, x), y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype:
            log(f"{name}{jax.tree_util.keystr(path)}: {x.shape}/{x.dtype} "
                f"vs {y.shape}/{y.dtype}")
            ok = False
        elif not np.array_equal(x, y):
            bad = x != y
            diff = np.abs(x.astype(np.float64) - y.astype(np.float64))
            log(f"{name}{jax.tree_util.keystr(path)}: {int(bad.sum())} of "
                f"{x.size} differ, max |diff| {float(diff.max())!r}")
            ok = False
    return ok


# ---------------------------------------------------------------------------
# Fleet-scale engine drive
# ---------------------------------------------------------------------------


def make_fleet(n: int, seed: int):
    """A heterogeneous N-expert pool, its memory-derived ragged caps, one
    expert down, a 0.3 admission floor on about half the experts, and
    WINDOWS windows of ARRIVALS seeded requests each, spaced at the
    paper's per-expert arrival rate scaled to N."""
    import jax
    import jax.numpy as jnp

    from repro.env import profiles

    pool = profiles.make_pool(n, seed=seed)
    run_caps, wait_caps = profiles.memory_caps(pool, R, W)
    k_down, k_floor, k_stream = jax.random.split(jax.random.PRNGKey(seed), 3)
    down = jax.random.randint(k_down, (), 0, n)
    up = jnp.ones((n,), jnp.bool_).at[down].set(False)
    admit_min = jnp.where(jax.random.uniform(k_floor, (n,)) < 0.5,
                          jnp.float32(0.3), jnp.float32(-1e30))
    ks = jax.random.split(k_stream, 7)
    shape = (WINDOWS, ARRIVALS)
    stream = {
        "dt": jax.random.exponential(ks[0], (WINDOWS,))
        * (ARRIVALS / (LAM_PER_EXPERT * n)),
        "expert": jax.random.randint(ks[1], shape, 0, n),
        "p": jax.random.randint(ks[2], shape, 16, 512),
        "d_true": jax.random.randint(ks[3], shape, 8, 300),
        "score": jax.random.uniform(ks[4], shape, minval=0.2, maxval=0.95),
        "pred_s": jax.random.uniform(ks[5], shape, minval=0.2, maxval=0.95),
        "pred_d": jax.random.uniform(ks[6], shape, minval=8.0, maxval=300.0),
    }
    fleet = {"run_caps": jnp.asarray(run_caps), "wait_caps":
             jnp.asarray(wait_caps), "up": up, "admit_min": admit_min}
    return pool, fleet, stream


def fleet_drive(pool, backend: str, arrays: dict, fleet: dict, stream: dict,
                mesh=None):
    """Push each window's arrivals (a down expert takes none), then
    advance every expert to the window's end.  ``arrays`` are the pool's
    array fields, passed as arguments so the compiled drive holds no
    constants.  Returns the final queues and the per-window clocks and
    accumulators."""
    import jax
    import jax.numpy as jnp

    from repro.env import engine

    pool = dataclasses.replace(pool, **arrays)
    n = pool.k1.shape[0]

    def window(carry, x):
        q, clocks, t = carry

        def push(i, q):
            e = x["expert"][i]
            q, _ = engine.push_wait(
                q, e, p=x["p"][i], d_true=x["d_true"][i],
                score=x["score"][i], pred_s=x["pred_s"][i],
                pred_d=x["pred_d"][i], t=t, gate=fleet["up"][e],
                wait_cap=fleet["wait_caps"])
            return q

        q = jax.lax.fori_loop(0, x["expert"].shape[0], push, q)
        t_next = t + x["dt"]
        q, clocks, acc = engine.advance_all(
            pool, LAT_L, q, clocks, t_next, backend=backend,
            run_caps=fleet["run_caps"], wait_caps=fleet["wait_caps"],
            up=fleet["up"], admit_min=fleet["admit_min"], mesh=mesh)
        return (q, clocks, t_next), (clocks, acc)

    init = (engine.empty_queues(n, R, W), jnp.zeros((n,), jnp.float32),
            jnp.float32(0.0))
    (q, _, _), (clock_trace, acc_trace) = jax.lax.scan(window, init, stream)
    return q, clock_trace, acc_trace


def run_drive(label: str, pool, backend: str, fleet, stream, mesh=None):
    """Compile and run one drive; returns (result, compiled HLO text)."""
    import jax

    arrays = {f.name: getattr(pool, f.name)
              for f in dataclasses.fields(pool)
              if isinstance(getattr(pool, f.name), jax.Array)}
    fn = jax.jit(functools.partial(fleet_drive, pool, backend, mesh=mesh))
    t0 = time.perf_counter()
    compiled = fn.lower(arrays, fleet, stream).compile()
    t_compile = time.perf_counter() - t0
    out = jax.block_until_ready(compiled(arrays, fleet, stream))
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(arrays, fleet, stream))
    secs = time.perf_counter() - t0
    windows = stream["dt"].shape[0]
    log(f"{label}: compile {t_compile:.1f} s, {windows / secs:.1f} windows/s "
        f"(one smoke run, not a benchmark)")
    return out, compiled.as_text()


def phase_kernel(seed: int, n: int = 1024) -> bool:
    import numpy as np

    pool, fleet, stream = make_fleet(n, seed)
    got, hlo = run_drive(f"kernel N={n} pallas", pool, "pallas", fleet,
                         stream)
    want, _ = run_drive(f"kernel N={n} xla", pool, "xla", fleet, stream)
    kernel = "tpu_custom_call" in hlo
    same = same_trees("kernel pallas vs xla", got, want)
    done = float(np.sum(np.asarray(want[2]["done"])))
    log(f"kernel: tpu_custom_call in the compiled pallas drive: {kernel}; "
        f"{int(done)} requests completed over {WINDOWS} windows; "
        f"pallas == xla bit for bit: {same}")
    return kernel and same and done > 0


# ---------------------------------------------------------------------------
# Router training and evaluation at the paper's configuration
# ---------------------------------------------------------------------------


def paper_configs(seed: int, backend: str = "pallas"):
    from repro.core import sac as sac_lib
    from repro.env import env as env_lib

    env_cfg = dataclasses.replace(env_lib.EnvConfig(), seed=seed,
                                  engine_backend=backend)
    return env_cfg, env_lib.make_env_pool(env_cfg), sac_lib.SACConfig()


def phase_train(seed: int, iterations: int = 24):
    """Returns (ok, trained params).  With the TrainConfig defaults the
    replay warm-up (2,000 transitions at 128 per iteration) ends in
    iteration 15, so 24 iterations run 9 with SAC updates."""
    from repro.core import training

    env_cfg, pool, sac_cfg = paper_configs(seed)
    tc = dataclasses.replace(training.TrainConfig(), iterations=iterations,
                             seed=seed, log_every=1)
    t0 = time.perf_counter()
    params, hist = training.train_router(env_cfg, sac_cfg, tc, pool=pool)
    total = time.perf_counter() - t0
    keys = ("collect_reward", "critic_loss", "actor_loss", "alpha")
    finite = all(math.isfinite(h[k]) for h in hist for k in keys)
    updated = [h["iteration"] for h in hist if h["critic_loss"] != 0.0]
    last = hist[-1]
    steady = (hist[-1]["elapsed_s"] - hist[0]["elapsed_s"]) / max(
        len(hist) - 1, 1)
    log(f"train: {iterations} iterations in {total:.1f} s (first, with "
        f"compile, {hist[0]['elapsed_s']} s; then ~{steady:.2f} s/iteration; "
        f"one smoke run, not a benchmark)")
    log(f"train: SAC updates in iterations {updated}; last: reward "
        f"{last['collect_reward']!r} critic {last['critic_loss']!r} actor "
        f"{last['actor_loss']!r} alpha {last['alpha']!r}")
    ok = finite and bool(updated) and last["critic_loss"] != 0.0
    if not ok:
        log(f"train: finite={finite} updated={updated}")
    return ok, params


def phase_eval(seed: int, params, n_steps: int = 2000) -> bool:
    from repro.core import routers, training

    metrics = {}
    for backend in ("pallas", "xla"):
        env_cfg, pool, sac_cfg = paper_configs(seed, backend)
        policy = routers.sac_policy("sac", sac_cfg, params)
        t0 = time.perf_counter()
        metrics[backend] = training.evaluate(env_cfg, pool, policy,
                                             n_steps=n_steps, seed=seed,
                                             n_envs=2)
        log(f"eval {backend}: {time.perf_counter() - t0:.1f} s with compile; "
            f"{metrics[backend]}")
    m = metrics["pallas"]
    ok = math.isfinite(m["avg_qos"]) and m["completed"] > 0
    same = metrics["pallas"] == metrics["xla"]
    if not same:
        log("eval: pallas and xla metrics differ")
    return ok and same


# ---------------------------------------------------------------------------
# Four chips: the expert-sharded engine and mesh-sharded router training
# ---------------------------------------------------------------------------


def phase_shard_map(seed: int, n: int = 4096, chips: int = 4) -> bool:
    import numpy as np

    from repro.launch.mesh import make_expert_mesh

    mesh = make_expert_mesh(chips)
    pool, fleet, stream = make_fleet(n, seed)
    got, hlo = run_drive(f"shard_map N={n} on {chips} devices", pool,
                         "shard_map", fleet, stream, mesh=mesh)
    want, _ = run_drive(f"pallas N={n} on one device", pool, "pallas",
                        fleet, stream)
    kernel = "tpu_custom_call" in hlo
    same = same_trees("shard_map vs pallas", got, want)
    placed = got[0]["run_i"].sharding
    done = float(np.sum(np.asarray(want[2]["done"])))
    log(f"shard_map: tpu_custom_call in the compiled drive: {kernel}; "
        f"{int(done)} requests completed; queues on "
        f"{len(placed.device_set)} devices (replicated: "
        f"{placed.is_fully_replicated}); bit-identical to one device: {same}")
    return kernel and same and len(placed.device_set) == chips and done > 0


def phase_train_mesh(seed: int, data: int = 2, chips: int = 4,
                     iterations: int = 3) -> bool:
    import jax
    import jax.numpy as jnp

    from repro.core import training
    from repro.launch.mesh import make_train_mesh

    env_cfg, pool, sac_cfg = paper_configs(seed)
    tc = dataclasses.replace(training.TrainConfig(), iterations=iterations,
                             seed=seed)
    # warm-up ends after two iterations' transitions, so the last two
    # iterations run SAC updates through the sharded replay sample
    tc = dataclasses.replace(
        tc, warmup_transitions=2 * tc.n_envs * tc.collect_steps)

    def run(mesh):
        params, opt, opt_state, env_states, buf = training.init_train_state(
            env_cfg, sac_cfg, tc, pool, jax.random.PRNGKey(seed), mesh=mesh)
        it = training.make_iteration(env_cfg, sac_cfg, tc, pool, opt,
                                     mesh=mesh)
        key = jax.random.PRNGKey(seed + 1)
        t0 = time.perf_counter()
        for i in range(tc.iterations):
            step = jnp.asarray(i * tc.updates_per_iter, jnp.int32)
            params, opt_state, env_states, buf, key, aux = it(
                params, opt_state, env_states, buf, key, step)
        jax.block_until_ready((params, buf))
        log(f"train mesh={None if mesh is None else dict(mesh.shape)}: "
            f"{tc.iterations} iterations in {time.perf_counter() - t0:.1f} s "
            f"with compile")
        return params, buf, aux, env_states

    p1, b1, a1, _ = run(None)
    mesh = make_train_mesh(chips, data=data)
    p2, b2, a2, e2 = run(mesh)
    ok = same_trees("train mesh vs one device", (p1, b1, a1), (p2, b2, a2))
    buf_devs = len(b2["action"].sharding.device_set)
    env_devs = len(jax.tree.leaves(e2)[0].sharding.device_set)
    log(f"train mesh: buffer on {buf_devs} devices "
        f"({b2['action'].sharding.spec}), envs on {env_devs}, critic "
        f"{float(a2['critic_loss'])!r}, bit-identical: {ok}")
    return (ok and buf_devs == chips and env_devs == chips
            and float(a2["critic_loss"]) != 0.0)


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4 runs only the expert-sharded engine and the "
                        "2x2 training mesh against their one-device runs")
    args = p.parse_args(argv)

    try:
        from repro.launch import compile_cache
    except ImportError as e:
        log(f"cannot import the repro package from {REPO}/src: {e}")
        return 2
    cache = compile_cache.configure()

    import jax

    from repro.kernels.lockstep_advance import ops

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"needs a TPU, but JAX found platform {dev.platform!r} "
            f"({dev.device_kind}, {len(devices)} devices)")
        return 1
    if ops.resolve_interpret(None):
        log("the lockstep kernel would run in interpret mode")
        return 1
    if len(devices) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices, "
            f"found {len(devices)}")
        return 1
    log(f"device {dev.device_kind}, {len(devices)} devices, jax "
        f"{jax.__version__}, compile cache {cache}")

    if args.chips == 4:
        phases = [("shard_map", lambda: phase_shard_map(args.seed)),
                  ("train_mesh", lambda: phase_train_mesh(args.seed))]
    else:
        trained = {}

        def train():
            ok, trained["params"] = phase_train(args.seed)
            return ok

        phases = [("kernel", lambda: phase_kernel(args.seed)),
                  ("train", train),
                  ("eval", lambda: phase_eval(args.seed, trained["params"]))]

    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            ok = fn()
        except Exception:  # the phase fails; report it and run the others
            traceback.print_exc()
            ok = False
        log(f"phase {name}: {'ok' if ok else 'FAILED'} "
            f"({time.perf_counter() - t0:.1f} s)")
        if not ok:
            failed.append(name)
    if failed:
        log(f"failed phases: {failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
