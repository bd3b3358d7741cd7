"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --steps 200 --reduced --ckpt-dir /tmp/ckpt

On real pods this runs under `jax.distributed.initialize()` with the
production mesh; on CPU (--reduced) it trains the reduced config of the
same family on the host mesh — the end-to-end path (data pipeline ->
microbatched step -> checkpoint/restart -> straggler detection) is
identical.

Router mode shards the DRL router's replay buffer over the expert mesh
(``make_train_mesh``) and runs the collect->insert->update iteration under
``shard_map`` — bit-identical to single-device training:

    PYTHONPATH=src python -m repro.launch.train --router --iters 200 \
        --router-mesh

``--ragged-caps`` additionally runs the env as a ragged heterogeneous
fleet (per-expert queue capacities from pool memory); with
``--obs-fmt segments`` the observation edge lists then scale with the
fleet's total capacity instead of N * max(cap).

``--scenario <name>`` trains against a scripted time-varying scenario
from the ``repro.scenarios`` registry (flash crowds, expert failures,
stragglers, memory claim/release) instead of a stationary workload:

    PYTHONPATH=src python -m repro.launch.train --router --iters 200 \
        --scenario flash_crowd

``--failover`` arms the failure-aware request lifecycle
(``repro.env.failover``): requests stranded on a failed expert drain
into a bounded retry buffer with exponential backoff and re-admit to
healthy experts, with overload shedding via ``--shed-watermark``;
``--straggler-z`` flags anomalously slow training iterations through
``fault_tolerance.StragglerDetector``:

    PYTHONPATH=src python -m repro.launch.train --router --iters 200 \
        --scenario rolling_outage --failover --shed-watermark 0.9 \
        --straggler-z 4.0
"""
from __future__ import annotations

import argparse
import dataclasses

import jax

from repro.configs import get_config, reduce_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch import compile_cache
from repro.launch.mesh import (make_host_mesh, make_production_mesh,
                               make_train_mesh)
from repro.train.trainer import Trainer, TrainerConfig


def train_router_main(args) -> None:
    """Train the QoS router, optionally with the capacity-sharded replay
    buffer on the expert mesh (``--router-mesh``) and/or a ragged
    heterogeneous-capacity fleet (``--ragged-caps``: per-expert queue
    capacities derived from pool memory via ``profiles.memory_caps``)."""
    from repro.core import features, sac as sac_lib, training
    from repro.env import env as env_lib

    env_cfg = env_lib.EnvConfig()
    pool = env_lib.make_env_pool(env_cfg)
    if args.ragged_caps:
        env_cfg = env_lib.with_ragged_caps(env_cfg, pool)
        print(f"[train] ragged fleet: run_caps={env_cfg.run_caps} "
              f"wait_caps={env_cfg.wait_caps}")
    if args.scenario:
        from repro import scenarios
        env_cfg = dataclasses.replace(env_cfg, scenario=args.scenario)
        spec = scenarios.get(args.scenario)  # fail loudly on a bad name
        print(f"[train] scenario {spec.name!r}: horizon={spec.horizon:g}s, "
              f"{len(spec.events)} events")
    if args.failover:
        from repro.env import failover as failover_lib
        fo = failover_lib.FailoverConfig(
            retry_budget=args.retry_budget,
            shed_watermark=(args.shed_watermark
                            if args.shed_watermark > 0 else None))
        env_cfg = dataclasses.replace(env_cfg, failover=fo)
        print(f"[train] failover: retry_budget={fo.retry_budget} "
              f"backoff={fo.backoff_base:g}s buffer={fo.buffer_cap} "
              f"watermark={fo.shed_watermark}")
    sac_cfg = sac_lib.SACConfig(
        n_actions=env_cfg.n_experts + 1,
        flat_dim=env_cfg.n_experts * 3,
        n_run_edges=(features.seg_run_rows(env_cfg)
                     if args.obs_fmt == "segments" else None),
        run_caps=(env_cfg.run_caps if args.obs_fmt == "segments" else None),
        wait_caps=(env_cfg.wait_caps if args.obs_fmt == "segments" else None))
    tc = training.TrainConfig(iterations=args.iters, obs_fmt=args.obs_fmt,
                              straggler_z=args.straggler_z)
    mesh = make_train_mesh() if args.router_mesh else None
    if mesh is not None:
        print(f"[train] replay capacity sharded over {mesh}")

    def log_fn(m):
        if m.get("straggler"):
            print(f"  [straggler] it={m['iteration']} "
                  f"step={m['step_s']:.3f}s vs mean={m['mean_s']:.3f}s")
            return
        flags = (f" stragglers={m['straggler_flags']}"
                 if "straggler_flags" in m else "")
        print(f"  it={m['iteration']} rew={m['collect_reward']:.3f}{flags}")

    params, history = training.train_router(
        env_cfg, sac_cfg, tc, pool=pool, mesh=mesh, log_fn=log_fn)
    print(f"[train] router done: final reward "
          f"{history[-1]['collect_reward']:.3f}")


def build_parser() -> argparse.ArgumentParser:
    """The launcher's command line (``train_router_main`` reads the
    ``--router`` options; the rest drive LM training)."""
    p = argparse.ArgumentParser()
    p.add_argument("--router", action="store_true",
                   help="train the QoS DRL router instead of an LM")
    p.add_argument("--router-mesh", action="store_true",
                   help="shard the replay buffer over the expert mesh")
    p.add_argument("--obs-fmt", default="padded",
                   choices=["padded", "segments"])
    p.add_argument("--ragged-caps", action="store_true",
                   help="heterogeneous fleet: per-expert queue capacities "
                        "derived from pool memory (profiles.memory_caps)")
    p.add_argument("--scenario", default="",
                   help="named scripted scenario (repro.scenarios registry: "
                        "flash_crowd, rolling_outage, memory_pressure, "
                        "stress, ...) for time-varying workload/fleet "
                        "conditions")
    p.add_argument("--failover", action="store_true",
                   help="failure-aware request lifecycle (repro.env."
                        "failover): drain stranded requests off down "
                        "experts into a retry buffer with exponential "
                        "backoff, re-admit to healthy experts, shed on "
                        "exhausted budget/deadline")
    p.add_argument("--retry-budget", type=int, default=2,
                   help="max re-dispatches per request before shedding")
    p.add_argument("--shed-watermark", type=float, default=0.0,
                   help="fleet occupancy in (0,1] that arms overload "
                        "shedding of low-predicted-score admits "
                        "(0 disables; requires --failover)")
    p.add_argument("--straggler-z", type=float, default=None,
                   help="flag router-training iterations whose wall time "
                        "z-score exceeds this (fault_tolerance."
                        "StragglerDetector); logged + counted in history")
    p.add_argument("--iters", type=int, default=400)
    p.add_argument("--arch", default="qwen1.5-0.5b")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--reduced", action="store_true",
                   help="reduced same-family config (CPU-runnable)")
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--data-parallel", type=int, default=1)
    p.add_argument("--model-parallel", type=int, default=1)
    p.add_argument("--production-mesh", action="store_true")
    return p


def main() -> None:
    args = build_parser().parse_args()
    compile_cache.configure()

    if args.router:
        train_router_main(args)
        return

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    if args.global_batch % max(1, cfg.microbatches):
        cfg = dataclasses.replace(cfg, microbatches=1)

    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh(args.data_parallel, args.model_parallel))
    tcfg = TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                         ckpt_every=args.ckpt_every)
    trainer = Trainer(cfg, tcfg, mesh=mesh if mesh.size > 1 else None)

    from repro.distributed import sharding
    data_shard = None
    if mesh.size > 1:
        data_shard = sharding.data_spec(mesh, args.global_batch, 2)
    data = SyntheticLM(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq_len,
        global_batch=args.global_batch, microbatches=cfg.microbatches),
        mesh=mesh, sharding_=data_shard)

    state = trainer.init_or_restore(jax.random.PRNGKey(0))
    state = trainer.run(state, iter(data))
    print(f"[train] done at step {int(state['step'])}")


if __name__ == "__main__":
    main()
