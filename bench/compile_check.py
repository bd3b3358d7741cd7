#!/usr/bin/env python3
"""Compile each cell's timed program at its real size for a described TPU
v5e, with no chip attached, and print the compiler's memory analysis.

    JAX_PLATFORMS=cpu python3 bench/compile_check.py [cell ...] [--fleet256-train]

The TPU compiler that ships with libtpu compiles for a chip that is
described (``jax.experimental.topologies``) and not attached: it refuses
what the chip would refuse (layouts, scoped memory, a program that does
not fit) and reports the bytes each program needs.  Nothing runs.

``--fleet256-train`` compiles router training at N=256 (ragged caps,
segment observations, the 100k-transition buffer) on a described
``v5e:2x2`` with the buffer sharded over the ``expert`` axis and the
collect over ``data``: the open question of whether that training fits
four chips.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def memory(compiled) -> dict:
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    return {k: int(getattr(m, k)) for k in keys}


def placed(tree, sharding):
    import jax
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


def cell_program(cell: str, one_chip) -> dict:
    import jax
    import jax.numpy as jnp

    from bench import run
    need = run.resolve(ROOT, cell)
    entry = run.load_module(need["entry"], "bench_entry").Entry(
        need["cfg"], need["traffic"])
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    shapes = placed(jax.eval_shape(entry._init, jax.random.PRNGKey(0)),
                    one_chip)
    t0 = time.perf_counter()
    if need["traffic"]["entry"] == "route":
        lowered = entry._chunk.lower(*shapes)
    else:
        step = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        lowered = entry._iteration.lower(*shapes, key, step)
    compiled = lowered.compile()
    return {"cell": cell, "compile_s": time.perf_counter() - t0,
            **memory(compiled)}


def fleet256_train(topo) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bench import run, system
    from repro.core import training
    from repro.distributed import sharding

    need = run.resolve(ROOT, "paper6.train")
    # the paper's per-expert setup at N=256, with the repo's fleet-scale
    # layout: memory-derived ragged caps and segment observations
    cfg = dict(need["cfg"], n_experts=256, queue_caps="memory",
               obs_fmt="segments")
    env_cfg, pool, sac_cfg = system.program_configs(cfg, need["traffic"])
    t = need["traffic"]
    tc = training.TrainConfig(
        n_envs=t["n_envs"], collect_steps=t["collect_steps"],
        updates_per_iter=t["updates_per_iter"], batch_size=t["batch_size"],
        buffer_capacity=t["buffer_capacity"],
        warmup_transitions=t["warmup_transitions"],
        iterations=t["iterations"], lr=t["lr"], obs_fmt="segments")
    mesh = Mesh(np.array(topo.devices).reshape(2, 2),
                (sharding.DATA, sharding.EXPERT))
    made = {}

    def init(key):
        params, opt, opt_state, env_states, buf = training.init_train_state(
            env_cfg, sac_cfg, tc, pool, key)
        made["opt"] = opt
        return params, opt_state, env_states, buf

    params, opt_state, env_states, buf = jax.eval_shape(
        init, jax.random.PRNGKey(0))
    rep = NamedSharding(mesh, P())
    buf = dict(buf)
    buf["capacity"] = jax.ShapeDtypeStruct((), jnp.int32)
    specs = sharding.replay_specs()
    buf = {k: placed(v, NamedSharding(mesh, specs[k])) for k, v in buf.items()}
    args = (placed(params, rep), placed(opt_state, rep),
            placed(env_states, NamedSharding(mesh, P(sharding.DATA))), buf,
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep))
    iteration = training.make_iteration(env_cfg, sac_cfg, tc, pool,
                                        made["opt"], mesh=mesh)
    t0 = time.perf_counter()
    compiled = iteration.lower(*args).compile()
    buf_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                    for x in jax.tree.leaves(buf))
    return {"cell": "fleet256.train.4chip", "mesh": "v5e:2x2 data x expert",
            "compile_s": time.perf_counter() - t0,
            "replay_bytes_total": buf_bytes, **memory(compiled)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cells", nargs="*")
    ap.add_argument("--fleet256-train", action="store_true")
    args = ap.parse_args(argv)
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for cell in args.cells:
        print(json.dumps(cell_program(cell, one_chip)), flush=True)
    if args.fleet256_train:
        print(json.dumps(fleet256_train(topo)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
