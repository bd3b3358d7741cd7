"""The engine advance alone, at a cell's fleet: the layer under
``env.step`` that moves every expert's queues to the next arrival.

Each window pushes one seeded arrival into a random expert's waiting
queue, as a routing step does, then calls ``engine.advance_all`` (without
naming a backend, so it runs whatever the engine picks) to the next
arrival time at the cell's arrival rate.  The drive is ``vmap``-ped over
the cell's envs and its windows run in one ``lax.scan``, so one call is
one program on the device whose time the trace reads.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


def make_stream(key, n_envs: int, windows: int, n: int, rate: float) -> dict:
    """Seeded arrivals: (n_envs, windows) inter-arrival times at ``rate``
    per second, target experts and request fields."""
    ks = jax.random.split(key, 7)
    shape = (n_envs, windows)
    return {
        "dt": jax.random.exponential(ks[0], shape) / rate,
        "expert": jax.random.randint(ks[1], shape, 0, n),
        "p": jax.random.randint(ks[2], shape, 16, 512),
        "d_true": jax.random.randint(ks[3], shape, 8, 300),
        "score": jax.random.uniform(ks[4], shape, minval=0.2, maxval=0.95),
        "pred_s": jax.random.uniform(ks[5], shape, minval=0.2, maxval=0.95),
        "pred_d": jax.random.uniform(ks[6], shape, minval=8.0, maxval=300.0),
    }


def make_drive(env_cfg, pool):
    """Returns ``bench_advance_drive(arrays, carry, stream) -> carry``; the
    carry is (queues, clocks, t) per env."""
    from repro.env import engine, env

    run_caps, wait_caps = env.queue_caps(env_cfg)
    arrays = {f.name: getattr(pool, f.name) for f in dataclasses.fields(pool)
              if isinstance(getattr(pool, f.name), jax.Array)}

    def bench_advance_drive(arrays, carry, stream):
        p = dataclasses.replace(pool, **arrays)

        def one(carry, stream):
            def window(carry, x):
                q, clocks, t = carry
                q, _ = engine.push_wait(
                    q, x["expert"], p=x["p"], d_true=x["d_true"],
                    score=x["score"], pred_s=x["pred_s"], pred_d=x["pred_d"],
                    t=t, wait_cap=wait_caps)
                t_next = t + x["dt"]
                q, clocks, _ = engine.advance_all(
                    p, env_cfg.latency_L, q, clocks, t_next,
                    run_caps=run_caps, wait_caps=wait_caps)
                return (q, clocks, t_next), None

            return jax.lax.scan(window, carry, stream)[0]

        return jax.vmap(one)(carry, stream)

    def empty(n_envs):
        n = env_cfg.n_experts
        q = engine.empty_queues(n, env_cfg.run_cap, env_cfg.wait_cap)
        one = (q, jnp.zeros((n,), jnp.float32), jnp.float32(0.0))
        return jax.tree.map(lambda x: jnp.broadcast_to(x, (n_envs,) + x.shape),
                            one)

    return jax.jit(bench_advance_drive), arrays, empty
