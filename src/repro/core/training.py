"""SAC training loop for the QoS-aware router: vectorized envs, replay,
jitted collect+update iterations — the whole loop lives inside XLA.

Baseline RL (paper §VI-A) trains through the same loop with
``use_han=False`` and ``qos_reward=False`` (plain completion reward, raw
expert-level features).

Mesh-sharded training (``make_iteration(..., mesh=...)``)
---------------------------------------------------------
On a mesh with an ``expert`` axis the whole iteration (collect -> buffer
insert -> SAC update) runs under one ``shard_map``: the replay buffer's
capacity axis is split across devices (``distributed.sharding.
replay_specs``; inserts stay donated/zero-copy per shard) while params /
opt_state / env_states / rng are replicated, so collect and the SAC update
execute identically on every device and only the sampled batch crosses
devices (one ``psum`` of per-shard gather contributions).  The sharded
iteration is bit-identical to the single-device path — asserted by
``tests/test_replay_sharded.py`` (shard logic) and
``tests/test_multidevice.py::test_sharded_training_iteration_multidevice``
(real 8-device mesh).

On a 2-D ``("data", "expert")`` mesh (``launch.mesh.make_train_mesh(
data=k)``) the collect batch additionally shards over ``data``: each
data-row of devices steps only its ``n_envs / k`` envs, then the
transition batch is all-gathered (tiled, participant order = env order)
before the buffer insert.  Bit-identity with the 1-D path needs one
care: ``sac.act`` consumes a PRNG key whose gumbel draw covers the whole
(n_envs, N) logits tensor, so actions are computed from the FULL
gathered observations on every data shard (identical everywhere) and
each shard slices out its local envs' actions for stepping.  The buffer
insert then sees the identical full batch on every data shard, keeping
the expert-sharded buffer replicated-consistent across ``data``.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import features, replay, sac as sac_lib, spans
from repro.env import env as env_lib
from repro.train import optimizer as opt_lib


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    n_envs: int = 16
    collect_steps: int = 8        # env steps per env per iteration
    updates_per_iter: int = 8
    batch_size: int = 256
    buffer_capacity: int = 100_000
    warmup_transitions: int = 2_000
    iterations: int = 400
    lr: float = 3e-4
    qos_reward: bool = True       # False -> Baseline RL reward (no penalty)
    zero_score_pred: bool = False  # Fig. 18 ablations
    zero_len_pred: bool = False
    seed: int = 0
    log_every: int = 25
    # straggler detection: z-score threshold on per-iteration wall time
    # (repro.distributed.fault_tolerance.StragglerDetector); None disables.
    # Flagged iterations are counted into the history metrics
    # (``straggler_flags``) and reported through ``log_fn`` so a hung
    # device / noisy host shows up in training logs instead of silently
    # stretching the run.
    straggler_z: Optional[float] = None
    # observation encoding fed to the HAN: "padded" (N, R/W, F) per-expert
    # request tensors, or "segments" — the flat edge-list layout that holds
    # the HAN obs path linear in N at fleet scale (repro.core.features).
    obs_fmt: str = "padded"


def _maybe_zero_preds(tc: TrainConfig, obs: dict) -> dict:
    if not (tc.zero_score_pred or tc.zero_len_pred):
        return obs
    obs = dict(obs)
    exp = obs["expert"]
    arr = obs["arrived"]
    # request-node channels 1/2 are (pred_s, pred_d) in BOTH layouts
    # (features.REQ_PRED_S / REQ_PRED_D); segments carry one flat tensor.
    req_keys = ("req",) if "req" in obs else ("run", "wait")
    if tc.zero_score_pred:
        exp = exp.at[..., 3].set(0.0)
        arr = arr.at[..., 1].set(0.0)
        for k in req_keys:
            obs[k] = obs[k].at[..., features.REQ_PRED_S].set(0.0)
    if tc.zero_len_pred:
        exp = exp.at[..., 4].set(0.0)
        arr = arr.at[..., 2].set(0.0)
        for k in req_keys:
            obs[k] = obs[k].at[..., features.REQ_PRED_D].set(0.0)
    obs.update(expert=exp, arrived=arr)
    return obs


def make_reward_fn(env_cfg: env_lib.EnvConfig, pool, tc: TrainConfig):
    """QoS-aware (Eq. 16) vs plain completion reward."""
    def reward(env_state, action, info):
        if tc.qos_reward:
            return info["reward"]  # phi_sum - penalty - drop
        return info["phi"]         # Baseline RL: completions only
    return reward


def init_train_state(env_cfg: env_lib.EnvConfig, sac_cfg: sac_lib.SACConfig,
                     tc: TrainConfig, pool, key, *, mesh=None):
    """Build (params, opt, opt_state, env_states, buf) for the jitted loop.
    With ``mesh``, the replay buffer is placed capacity-sharded over the
    ``expert`` axis and everything else replicated."""
    k_init, k_env = jax.random.split(key)
    params = sac_lib.init_params(k_init, sac_cfg)
    opt = opt_lib.make_optimizer(
        "adamw", peak_lr=tc.lr, warmup_steps=100,
        total_steps=tc.iterations * tc.updates_per_iter,
        weight_decay=0.0, grad_clip=10.0)
    opt_state = opt.init(sac_lib.trainable(params))
    env_keys = jax.random.split(k_env, tc.n_envs)
    env_states = jax.vmap(lambda k: env_lib.reset(env_cfg, pool, k))(env_keys)
    obs0 = features.build_obs(env_cfg, pool, env_lib.reset(
        env_cfg, pool, jax.random.PRNGKey(0)), fmt=tc.obs_fmt)
    buf = replay.init(tc.buffer_capacity, obs0)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.distributed import sharding
        buf = sharding.shard_replay_buffer(buf, mesh)
        rep = NamedSharding(mesh, PartitionSpec())
        put_rep = lambda t: jax.tree.map(
            lambda x: jax.device_put(jnp.asarray(x), rep), t)
        params, opt_state = put_rep(params), put_rep(opt_state)
        env_sh = rep
        if sharding.DATA in mesh.shape:
            # 2-D training mesh: envs live sharded over the data axis
            # (dim 0 = env axis; data_shards validates divisibility)
            sharding.data_shards(mesh, tc.n_envs)
            env_sh = NamedSharding(mesh, PartitionSpec(sharding.DATA))
        env_states = jax.tree.map(
            lambda x: jax.device_put(jnp.asarray(x), env_sh), env_states)
    return params, opt, opt_state, env_states, buf


def make_iteration(env_cfg: env_lib.EnvConfig, sac_cfg: sac_lib.SACConfig,
                   tc: TrainConfig, pool, opt, *, mesh=None):
    """One jitted collect+update iteration.

    ``params / opt_state / env_states / buf`` are DONATED: the ~capacity-
    sized replay buffer (hundreds of MB of obs/next_obs) is updated in
    place instead of being copied every iteration.  Callers must rebind
    their references to the returned values (``train_router`` does).

    ``mesh=None`` runs single-device (the reference path); with a mesh the
    same body runs under ``shard_map`` with the buffer capacity-sharded
    over the ``expert`` axis (see module docstring) and only the replay
    insert/sample bodies differ.  A 2-D ``("data", "expert")`` mesh
    additionally shards env stepping over ``data`` (collect-batch
    sharding; bit-identical — see module docstring).
    """
    reward_fn = make_reward_fn(env_cfg, pool, tc)

    def obs_of(env_states):
        o = jax.vmap(lambda s: features.build_obs(
            env_cfg, pool, s, fmt=tc.obs_fmt))(env_states)
        return _maybe_zero_preds(tc, o)

    def iteration_body(params, opt_state, env_states, buf, key, step, *,
                       insert_fn, sample_fn, gather_fn=None, slice_fn=None):
        # Data-axis parametrization (both identity on the plain and 1-D
        # mesh paths, so those stay textually the same computation):
        # ``gather_fn`` all-gathers env-axis tensors to the full batch,
        # ``slice_fn`` cuts a data shard's local envs back out.  Actions
        # are always computed from FULL observations so the PRNG draw in
        # sac.act covers the same logits tensor on every shard.
        gather = gather_fn if gather_fn is not None else (lambda t: t)
        take = slice_fn if slice_fn is not None else (lambda t: t)

        def collect(carry, _):
            # obs rides in the carry so build_obs runs ONCE per env step
            # (the seed recomputed next_obs as obs on the following step);
            # it is the FULL gathered batch, env_states stay local.
            env_states, obs, buf, key = carry
            key, k_act = jax.random.split(key)
            actions = sac_lib.act(params, sac_cfg, obs, k_act)
            a_loc = take(actions)

            def one(s, a):
                s2, r, info = env_lib.step(env_cfg, pool, s, a)
                return s2, (r, info)

            env_states2, (rewards, infos) = jax.vmap(one)(env_states, a_loc)
            rew = gather(jax.vmap(lambda s, a, i: reward_fn(s, a, i))(
                env_states, a_loc, infos))
            next_obs = gather(obs_of(env_states2))
            buf = insert_fn(buf, obs, actions, rew,
                            jnp.ones_like(rew), next_obs)
            return (env_states2, next_obs, buf, key), jnp.mean(rew)

        with jax.named_scope(spans.COLLECT):
            (env_states, _, buf, key), rews = jax.lax.scan(
                collect, (env_states, gather(obs_of(env_states)), buf, key),
                None, length=tc.collect_steps)

        def update(carry, _):
            params, opt_state, key = carry
            key, k_s = jax.random.split(key)
            batch = sample_fn(buf, k_s, tc.batch_size)

            def loss_fn(tr):
                p = sac_lib.merge_trainable(params, tr)
                return sac_lib.losses(p, sac_cfg, batch)

            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(sac_lib.trainable(params))
            new_tr, opt_state, _ = opt.update(
                grads, opt_state, sac_lib.trainable(params), step)
            params = sac_lib.merge_trainable(params, new_tr)
            params = sac_lib.polyak(params, sac_cfg)
            return (params, opt_state, key), aux

        do_update = buf["size"] >= tc.warmup_transitions
        def run_updates(args):
            params, opt_state, key = args
            (params, opt_state, key), auxs = jax.lax.scan(
                update, (params, opt_state, key), None,
                length=tc.updates_per_iter)
            return params, opt_state, key, jax.tree.map(jnp.mean, auxs)

        def skip_updates(args):
            params, opt_state, key = args
            dummy = {"critic_loss": jnp.float32(0), "actor_loss": jnp.float32(0),
                     "alpha": jnp.exp(params["log_alpha"]),
                     "entropy": jnp.float32(0), "q_mean": jnp.float32(0)}
            return params, opt_state, key, dummy

        with jax.named_scope(spans.UPDATE):
            params, opt_state, key, aux = jax.lax.cond(
                do_update, run_updates, skip_updates, (params, opt_state, key))
        aux["collect_reward"] = jnp.mean(rews)
        return params, opt_state, env_states, buf, key, aux

    if mesh is None:
        def iteration(params, opt_state, env_states, buf, key, step):
            return iteration_body(params, opt_state, env_states, buf, key,
                                  step, insert_fn=replay.add_batch,
                                  sample_fn=replay.sample)
        return jax.jit(iteration, donate_argnums=(0, 1, 2, 3))

    # --- sharded path: the whole iteration under one shard_map ---
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding

    if env_cfg.engine_backend == "shard_map":
        raise ValueError(
            "engine_backend='shard_map' cannot nest inside the sharded "
            "training iteration; use 'xla' or 'pallas' for the env engine")
    ax = sharding.EXPERT
    if ax not in mesh.shape:
        raise ValueError(f"training mesh has no '{ax}' axis: {mesh}")
    n_shards = sharding.replay_shards(mesh, tc.buffer_capacity)
    buf_specs = sharding.replay_specs()
    # 2-D ("data", "expert") mesh: collect-batch sharding over data
    # (see module docstring); 1-D meshes leave dax None -> identity fns.
    dax = sharding.DATA if sharding.DATA in mesh.shape else None
    n_data = sharding.data_shards(mesh, tc.n_envs)

    def body(params, opt_state, env_states, buf, key, step):
        shard_idx = jax.lax.axis_index(ax)
        insert_fn = functools.partial(replay.shard_add_batch,
                                      shard_idx=shard_idx, n_shards=n_shards)

        def sample_fn(b, k, batch_size):
            contrib = replay.shard_sample_local(
                b, k, batch_size, shard_idx=shard_idx, n_shards=n_shards)
            return jax.lax.psum(contrib, ax)

        gather_fn = slice_fn = None
        if dax is not None:
            per = tc.n_envs // n_data

            def gather_fn(t):
                return jax.tree.map(
                    lambda x: jax.lax.all_gather(x, dax, tiled=True), t)

            def slice_fn(t):
                i0 = jax.lax.axis_index(dax) * per
                return jax.tree.map(
                    lambda x: jax.lax.dynamic_slice_in_dim(x, i0, per, 0), t)

        return iteration_body(params, opt_state, env_states, buf, key, step,
                              insert_fn=insert_fn, sample_fn=sample_fn,
                              gather_fn=gather_fn, slice_fn=slice_fn)

    rep = P()
    env_spec = P(dax) if dax is not None else rep
    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(rep, rep, env_spec, buf_specs, rep, rep),
        out_specs=(rep, rep, env_spec, buf_specs, rep, rep),
        check_vma=False)
    return jax.jit(sharded, donate_argnums=(0, 1, 2, 3))


def train_router(env_cfg: env_lib.EnvConfig, sac_cfg: sac_lib.SACConfig,
                 tc: TrainConfig, *, pool=None, mesh=None,
                 log_fn: Optional[Callable] = None) -> Tuple[dict, list]:
    """Returns (trained params, history of metric dicts)."""
    pool = pool if pool is not None else env_lib.make_env_pool(env_cfg)
    key = jax.random.PRNGKey(tc.seed)
    k_state, key = jax.random.split(key)
    params, opt, opt_state, env_states, buf = init_train_state(
        env_cfg, sac_cfg, tc, pool, k_state, mesh=mesh)
    iteration = make_iteration(env_cfg, sac_cfg, tc, pool, opt, mesh=mesh)

    detector = None
    if tc.straggler_z is not None:
        from repro.distributed.fault_tolerance import StragglerDetector
        detector = StragglerDetector(z_threshold=tc.straggler_z)
    straggler_flags = 0

    history = []
    t0 = time.time()
    for it in range(tc.iterations):
        t_it = time.time()
        step = jnp.asarray(it * tc.updates_per_iter, jnp.int32)
        params, opt_state, env_states, buf, key, aux = iteration(
            params, opt_state, env_states, buf, key, step)
        if detector is not None:
            jax.block_until_ready(params)  # charge the iteration, not the
            # NEXT iteration's implicit sync, to this step's wall time
            if detector.update(time.time() - t_it):
                straggler_flags += 1
                if log_fn:
                    log_fn({"iteration": it, "straggler": True,
                            "step_s": round(time.time() - t_it, 3),
                            "mean_s": round(detector.mean, 3)})
        if it % tc.log_every == 0 or it == tc.iterations - 1:
            m = jax.tree.map(float, aux)
            m["iteration"] = it
            m["transitions"] = int((it + 1) * tc.n_envs * tc.collect_steps)
            m["elapsed_s"] = round(time.time() - t0, 1)
            if detector is not None:
                m["straggler_flags"] = straggler_flags
            history.append(m)
            if log_fn:
                log_fn(m)
    return params, history


def evaluate(env_cfg: env_lib.EnvConfig, pool, policy, n_steps: int = 5000,
             seed: int = 1234, n_envs: int = 4) -> dict:
    """Run a policy greedily; returns paper metrics (avg QoS, latency/token).
    Observations are built in the policy's declared format
    (``routers.Policy.obs_fmt``) so routers trained on segment obs evaluate
    on segment obs."""
    key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, n_envs)
    obs_fmt = getattr(policy, "obs_fmt", "padded")

    def run_one(k):
        state = env_lib.reset(env_cfg, pool, k)
        pstate = policy.init_state(k)

        def body(carry, i):
            state, pstate, k = carry
            k, k_act = jax.random.split(k)
            obs = features.build_obs(env_cfg, pool, state, fmt=obs_fmt)
            a, pstate = policy.act(pstate, state, obs, k_act)
            state, r, info = env_lib.step(env_cfg, pool, state, a)
            return (state, pstate, k), r

        (state, _, _), rews = jax.lax.scan(
            body, (state, pstate, k), jnp.arange(n_steps))
        return env_lib.episode_metrics(state), jnp.mean(rews)

    metrics, mean_rew = jax.jit(jax.vmap(run_one))(keys)
    out = {k: float(jnp.mean(v)) for k, v in metrics.items()}
    out["mean_reward"] = float(jnp.mean(mean_rew))
    return out
