"""The program under test, configured from a benchmark configuration and a
traffic mix (the files under ``configs/`` and ``traffic/``)."""
from __future__ import annotations

import dataclasses


def arrival_config(cfg: dict, traffic: dict) -> dict:
    """The traffic mix's arrival process with its rate resolved: a mix
    may state the rate per expert, which scales with the fleet."""
    wl = dict(traffic["arrivals"])
    if "rate_per_expert" in wl:
        wl["rate"] = wl.pop("rate_per_expert") * cfg["n_experts"]
    return wl


def program_configs(cfg: dict, traffic: dict):
    """Returns (EnvConfig, ExpertPool, SACConfig) of the program."""
    from repro.core import features, han, sac
    from repro.env import env, workload

    env_cfg = env.EnvConfig(
        n_experts=cfg["n_experts"], run_cap=cfg["run_cap"],
        wait_cap=cfg["wait_cap"], latency_L=cfg["latency_L"],
        n_types=cfg["n_types"], n_buckets=cfg["n_buckets"],
        max_output=cfg["max_output"], max_prompt=cfg["max_prompt"],
        score_pred_noise=cfg["score_pred_noise"],
        len_pred_noise=cfg["len_pred_noise"],
        drop_penalty=cfg["drop_penalty"], impact_mode=cfg["impact_mode"],
        admit_order=cfg["admit_order"],
        workload=workload.WorkloadConfig(**arrival_config(cfg, traffic)),
        seed=cfg["pool_seed"])
    pool = env.make_env_pool(env_cfg)
    if cfg["queue_caps"] == "memory":
        env_cfg = env.with_ragged_caps(env_cfg, pool)
    sac_cfg = sac.SACConfig(
        n_actions=cfg["n_experts"] + 1, **cfg["sac"],
        han=han.HANConfig(**cfg["han"]))
    if cfg["obs_fmt"] == "segments":
        sac_cfg = dataclasses.replace(
            sac_cfg, n_run_edges=features.seg_run_rows(env_cfg),
            run_caps=env_cfg.run_caps, wait_caps=env_cfg.wait_caps)
    return env_cfg, pool, sac_cfg
