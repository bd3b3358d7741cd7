"""Faults planted under the timed path, for the tests that see the check
come out false."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import sac
from repro.env import env


def step_returns_state(monkeypatch):
    """A routing step that returns its state unchanged."""
    real = env.step

    def step(cfg, pool, state, action):
        _, r, info = real(cfg, pool, state, action)
        return state, r, info

    monkeypatch.setattr(env, "step", step)


def update_returns_params(monkeypatch):
    """A SAC update that returns the parameters unchanged."""
    monkeypatch.setattr(sac, "merge_trainable", lambda params, new: params)


def action_altered(monkeypatch):
    """Each routing decision moved to the next expert where it is made."""
    real = sac.act

    def act(params, cfg, obs, key, *, greedy=False):
        return (real(params, cfg, obs, key, greedy=greedy) + 1) % cfg.n_actions

    monkeypatch.setattr(sac, "act", act)


def half_batch(monkeypatch):
    """The SAC losses over the first half of the sampled batch."""
    real = sac.losses

    def losses(params, cfg, batch):
        half = batch["action"].shape[0] // 2
        return real(params, cfg, jax.tree.map(lambda x: x[:half], batch))

    monkeypatch.setattr(sac, "losses", losses)


ROUTE = {"state_unchanged": step_returns_state, "token_altered": action_altered}
TRAIN = {"state_unchanged": update_returns_params,
         "token_altered": action_altered, "half_batch": half_batch}
