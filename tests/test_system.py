"""End-to-end behaviour tests for the paper's system."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # multi-minute; scripts/ci.sh skips these

from repro.core import features, predictors, routers, sac as sac_lib, training
from repro.env import env as env_lib
from repro.env.env import EnvConfig


@pytest.fixture(scope="module")
def setup():
    cfg = EnvConfig()
    pool = env_lib.make_env_pool(cfg)
    return cfg, pool


def test_heuristic_ordering(setup):
    """Paper §VI regime: BR (quality-greedy) must congest and lose to
    load-aware routing; SQF must have near-zero violations."""
    cfg, pool = setup
    res = {}
    for pol in (routers.bert_router(), routers.round_robin(cfg.n_experts),
                routers.shortest_queue(cfg.n_experts)):
        res[pol.name] = training.evaluate(cfg, pool, pol, n_steps=2500,
                                          n_envs=2)
    assert res["SQF"]["violation_rate"] < 0.05
    assert res["BR"]["violation_rate"] > res["SQF"]["violation_rate"]
    assert res["SQF"]["avg_qos"] > res["BR"]["avg_qos"]


def test_sac_training_runs_and_produces_policy(setup):
    """Short SAC run must stay finite and produce a usable policy (reward
    *trajectory* assertions need >10x this budget and are covered by the
    benchmark harness, not unit tests)."""
    cfg, pool = setup
    sac_cfg = sac_lib.SACConfig(n_actions=cfg.n_experts + 1)
    tc = training.TrainConfig(iterations=60, n_envs=8, collect_steps=8,
                              warmup_transitions=500, log_every=10)
    hist = []
    params, history = training.train_router(
        cfg, sac_cfg, tc, pool=pool, log_fn=lambda m: hist.append(m))
    import math
    assert all(math.isfinite(h["collect_reward"]) for h in hist)
    assert all(math.isfinite(h["critic_loss"]) for h in hist)
    pol = routers.sac_policy("qos", sac_cfg, params)
    m = training.evaluate(cfg, pool, pol, n_steps=1500, n_envs=2)
    assert m["completed"] + m["dropped"] > 0


def test_baseline_rl_uses_flat_features(setup):
    cfg, pool = setup
    sac_cfg = sac_lib.SACConfig(n_actions=cfg.n_experts + 1, use_han=False,
                                flat_dim=cfg.n_experts * 3)
    params = sac_lib.init_params(jax.random.PRNGKey(0), sac_cfg)
    assert "han" not in params
    state = env_lib.reset(cfg, pool, jax.random.PRNGKey(1))
    obs = features.build_obs(cfg, pool, state)
    a = sac_lib.act(params, sac_cfg, obs, jax.random.PRNGKey(2))
    assert 0 <= int(a) <= cfg.n_experts


def test_predictor_learns_above_chance(setup):
    cfg, pool = setup
    pcfg = predictors.PredictorConfig()
    params, m = predictors.train(pcfg, pool, steps=150, log_fn=None)
    assert m["score_top1"] > 0.25    # chance = 0.1
    assert m["score_top3"] > 0.6
    assert m["len_top1"] > 0.2


def test_heterogeneous_caps_end_to_end(setup):
    """Ragged fleet through the full env loop: memory-derived caps, engine
    masking, occupancy-aware heuristics — and the capacity ordering must
    show up as bigger experts doing more of the work."""
    cfg, pool = setup
    rcfg = env_lib.with_ragged_caps(cfg, pool)
    assert min(rcfg.run_caps) < cfg.run_cap  # the pool's spread is real
    pol = routers.quality_least_loaded(caps=(rcfg.run_caps, rcfg.wait_caps))
    m = training.evaluate(rcfg, pool, pol, n_steps=1500, n_envs=2)
    assert m["completed"] > 0
    assert m["avg_qos"] > 0


def test_examples_run_heterogeneous_fleet():
    """Smoke: both examples run a heterogeneous-caps pool end to end with
    tiny budgets (the ISSUE-4 examples contract), plus the scripted
    flash-crowd scenario phase (the ISSUE-5 demo contract)."""
    import os
    import sys

    ex_dir = os.path.join(os.path.dirname(__file__), os.pardir, "examples")
    sys.path.insert(0, ex_dir)
    try:
        import edge_routing_demo
        import quickstart
        quickstart.main(["--steps", "2", "--route-steps", "60"])
        edge_routing_demo.main(["--steps", "60", "--ragged-caps",
                                "--quick-iters", "1"])
        edge_routing_demo.main(["--steps", "60", "--scenario",
                                "flash_crowd", "--quick-iters", "1"])
    finally:
        sys.path.remove(ex_dir)


def test_launch_train_router_on_scenarios():
    """launch.train --router --scenario <name> end to end (tiny budgets)
    for three registry scenarios — the ISSUE-5 acceptance criterion."""
    from repro.launch import train as train_launch

    for name in ("flash_crowd", "rolling_outage", "memory_pressure"):
        args = train_launch.build_parser().parse_args(
            ["--router", "--scenario", name, "--iters", "2"])
        train_launch.train_router_main(args)


def test_serving_engine_end_to_end():
    """Real JAX engine: requests flow through continuous batching and the
    latency calibration returns sane gradients."""
    from repro.configs import get_config, reduce_config
    from repro.env.serve_engine import ExpertServer, Request, calibrate
    from repro.models import model
    cfg = reduce_config(get_config("qwen1.5-0.5b"))
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    srv = ExpertServer("e0", cfg, params, slots=2, max_len=96)
    rng = np.random.default_rng(0)
    for i in range(5):
        srv.submit(Request(rid=i, tokens=rng.integers(2, 200, 12 + 7 * i),
                           max_new=5))
    done = []
    for _ in range(400):
        done.extend(srv.step())
        if not srv.has_work():
            break
    assert len(done) == 5
    assert all(len(r.generated) >= 1 for r in done)
    assert all(r.latency_per_token is not None for r in done)
    fit = calibrate(srv)
    assert fit["n_decode"] > 0
