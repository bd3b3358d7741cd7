"""Discrete Soft Actor-Critic (paper §V-A uses SAC [42]; discrete-action
variant à la Christodoulou 2019) with the HAN state abstraction in front.

Actor / twin critics are 2-layer MLPs on the arrived-request embedding
(paper Table II: HAN 19K params, actor-critic 10K).  Entropy temperature α
is auto-tuned toward a target entropy of `entropy_target_frac * log(A)`.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import features, han as han_lib, spans


@dataclasses.dataclass(frozen=True)
class SACConfig:
    n_actions: int = 7            # N experts + drop
    hidden: int = 64
    gamma: float = 0.97
    tau: float = 0.005            # polyak for target critics
    lr: float = 3e-4
    alpha_lr: float = 3e-4
    entropy_target_frac: float = 0.35
    init_alpha: float = 0.2
    use_han: bool = True          # False -> Baseline RL (flat expert feats)
    flat_dim: int = 18            # N * 3 expert-level features
    han: han_lib.HANConfig = han_lib.HANConfig()
    # run-edge rows at the head of segment-layout obs["req"]
    # (features.seg_run_rows(env_cfg)); only needed when training on
    # obs_fmt="segments"
    n_run_edges: Optional[int] = None
    # ragged-fleet segment layout: the concrete per-expert capacities
    # (mirrors EnvConfig.run_caps/wait_caps) so the rebuilt segment ids
    # match the ragged row layout; None = uniform split
    run_caps: Optional[Tuple[int, ...]] = None
    wait_caps: Optional[Tuple[int, ...]] = None


def _mlp_init(key, dims):
    params = []
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        k1, key = jax.random.split(key)
        params.append({
            "w": jax.random.normal(k1, (a, b), jnp.float32) * jnp.sqrt(2.0 / a),
            "b": jnp.zeros((b,), jnp.float32),
        })
    return params


@jax.custom_vjp
def _add_bias(x, b):
    return x + b


def _add_bias_fwd(x, b):
    return x + b, None


def _add_bias_bwd(_, g):
    # the bias gradient sums over the batch: fixed order, so the sharded
    # and the single-device training iterations round it identically
    return g, features.fixed_order_sum(g.reshape(-1, g.shape[-1]), axis=0)


_add_bias.defvjp(_add_bias_fwd, _add_bias_bwd)


def _mlp(params, x):
    for i, lyr in enumerate(params):
        x = _add_bias(x @ lyr["w"], lyr["b"])
        if i + 1 < len(params):
            x = jax.nn.relu(x)
    return x


def init_params(key, cfg: SACConfig) -> dict:
    ks = jax.random.split(key, 6)
    d_in = cfg.han.hidden if cfg.use_han else cfg.flat_dim
    p = {
        "actor": _mlp_init(ks[0], (d_in, cfg.hidden, cfg.n_actions)),
        "q1": _mlp_init(ks[1], (d_in, cfg.hidden, cfg.n_actions)),
        "q2": _mlp_init(ks[2], (d_in, cfg.hidden, cfg.n_actions)),
        "log_alpha": jnp.log(jnp.asarray(cfg.init_alpha, jnp.float32)),
    }
    if cfg.use_han:
        p["han"] = han_lib.init_params(ks[3], cfg.han)
        p["han_critic"] = han_lib.init_params(ks[4], cfg.han)
    p["q1_target"] = jax.tree.map(jnp.copy, p["q1"])
    p["q2_target"] = jax.tree.map(jnp.copy, p["q2"])
    if cfg.use_han:
        p["han_critic_target"] = jax.tree.map(jnp.copy, p["han_critic"])
    return p


def embed(params: dict, cfg: SACConfig, obs: dict, *, which: str = "actor") -> jax.Array:
    """obs -> state embedding. Batched obs get vmapped automatically.
    Dispatches on the obs layout: padded (``run``/``wait``) vs segments
    (``req``; see features.to_segments), same HAN parameters either way."""
    if not cfg.use_han:
        flat = obs["expert"][..., :3].reshape(*obs["expert"].shape[:-2], -1)
        return flat
    han_params = params["han"] if which in ("actor",) else params[which]
    batched = obs["arrived"].ndim == 2

    if "req" in obs:
        if cfg.n_run_edges is None:
            raise ValueError(
                "segment-layout obs need SACConfig.n_run_edges "
                "(= features.seg_run_rows(env_cfg))")
        one = lambda o: han_lib.forward_segments(
            han_params, o, cfg.han, n_run=cfg.n_run_edges,
            run_caps=cfg.run_caps, wait_caps=cfg.wait_caps)[0]
    else:
        one = lambda o: han_lib.forward(han_params, o, cfg.han)[0]

    return jax.vmap(one)(obs) if batched else one(obs)


def actor_logits(params, cfg: SACConfig, obs) -> jax.Array:
    z = embed(params, cfg, obs, which="actor")
    return _mlp(params["actor"], z)


@jax.named_scope(spans.POLICY)
def act(params, cfg: SACConfig, obs, key, *, greedy: bool = False) -> jax.Array:
    logits = actor_logits(params, cfg, obs)
    if greedy:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(key, logits, axis=-1)


def _q_values(params, cfg, obs, *, target: bool):
    which = "han_critic_target" if (target and cfg.use_han) else "han_critic"
    z = embed(params, cfg, obs, which=which if cfg.use_han else "actor")
    q1 = _mlp(params["q1_target" if target else "q1"], z)
    q2 = _mlp(params["q2_target" if target else "q2"], z)
    return q1, q2


def losses(params, cfg: SACConfig, batch: Dict) -> Tuple[jax.Array, dict]:
    """batch: obs, action (B,), reward (B,), next_obs, discount (B,)."""
    alpha = jnp.exp(params["log_alpha"])
    target_entropy = cfg.entropy_target_frac * jnp.log(float(cfg.n_actions))

    # --- critic target ---
    next_logits = actor_logits(params, cfg, batch["next_obs"])
    next_pi = jax.nn.softmax(next_logits)
    next_logpi = jax.nn.log_softmax(next_logits)
    q1_t, q2_t = _q_values(params, cfg, batch["next_obs"], target=True)
    v_next = jnp.sum(next_pi * (jnp.minimum(q1_t, q2_t)
                                - alpha * next_logpi), axis=-1)
    y = batch["reward"] + cfg.gamma * batch["discount"] * v_next
    y = jax.lax.stop_gradient(y)

    q1, q2 = _q_values(params, cfg, batch["obs"], target=False)
    a = batch["action"]
    q1_a = jnp.take_along_axis(q1, a[:, None], axis=-1)[:, 0]
    q2_a = jnp.take_along_axis(q2, a[:, None], axis=-1)[:, 0]
    critic_loss = jnp.mean(jnp.square(q1_a - y) + jnp.square(q2_a - y))

    # --- actor ---
    logits = actor_logits(params, cfg, batch["obs"])
    pi = jax.nn.softmax(logits)
    logpi = jax.nn.log_softmax(logits)
    q_min = jax.lax.stop_gradient(jnp.minimum(q1, q2))
    actor_loss = jnp.mean(jnp.sum(
        pi * (jax.lax.stop_gradient(alpha) * logpi - q_min), axis=-1))

    # --- temperature ---
    entropy = -jnp.sum(pi * logpi, axis=-1)
    alpha_loss = params["log_alpha"] * jnp.mean(
        jax.lax.stop_gradient(entropy - target_entropy))

    total = critic_loss + actor_loss + alpha_loss
    aux = {"critic_loss": critic_loss, "actor_loss": actor_loss,
           "alpha": alpha, "entropy": jnp.mean(entropy),
           "q_mean": jnp.mean(q_min)}
    return total, aux


def polyak(params: dict, cfg: SACConfig) -> dict:
    params = dict(params)
    upd = lambda t, s: jax.tree.map(
        lambda a, b: (1 - cfg.tau) * a + cfg.tau * b, t, s)
    params["q1_target"] = upd(params["q1_target"], params["q1"])
    params["q2_target"] = upd(params["q2_target"], params["q2"])
    if "han_critic_target" in params:
        params["han_critic_target"] = upd(params["han_critic_target"],
                                          params["han_critic"])
    return params


TARGET_KEYS = ("q1_target", "q2_target", "han_critic_target")


def trainable(params: dict) -> dict:
    return {k: v for k, v in params.items() if k not in TARGET_KEYS}


def merge_trainable(params: dict, new_trainable: dict) -> dict:
    out = dict(params)
    out.update(new_trainable)
    return out
