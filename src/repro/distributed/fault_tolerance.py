"""Fault tolerance & elasticity primitives.

* ``StragglerDetector`` — per-step wall-time EMA/EMVar z-score flagging;
  at scale this wraps per-host heartbeat timestamps, here it instruments
  the trainer loop directly.  Flagged steps trigger the configured hook
  (log / requeue / exclude-host).
* ``reshard_state`` — re-place a train state onto a different mesh using
  the sharding rules (elastic up/down-scaling after node loss: restore the
  latest checkpoint, build the largest healthy mesh, reshard, continue).
* ``best_mesh_after_failure`` — given surviving device count, pick the
  largest (data, model) mesh that keeps the model axis intact (model
  parallelism cannot shrink without resharding weights across hosts; data
  parallelism can).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import numpy as np

from repro.distributed import sharding


@dataclasses.dataclass
class StragglerDetector:
    """z-score straggler flagging on step wall times: Welford during
    warmup, then EMA mean/variance (outliers excluded from the stats)."""

    alpha: float = 0.05
    z_threshold: float = 4.0
    warmup: int = 10
    rel_floor: float = 0.05   # ignore deviations below 5% of the mean
    mean: float = 0.0
    var: float = 0.0
    _m2: float = 0.0
    count: int = 0

    def update(self, dt: float) -> bool:
        """Returns True if this step is flagged as a straggler."""
        self.count += 1
        if self.count <= self.warmup:
            delta = dt - self.mean
            self.mean += delta / self.count
            self._m2 += delta * (dt - self.mean)
            if self.count == self.warmup:
                self.var = max(self._m2 / max(self.warmup - 1, 1), 1e-12)
            return False
        std = math.sqrt(max(self.var, 1e-12))
        std = max(std, self.rel_floor * abs(self.mean), 1e-9)
        z = (dt - self.mean) / std
        flagged = z > self.z_threshold
        if not flagged:  # don't poison stats with outliers
            self.mean = (1 - self.alpha) * self.mean + self.alpha * dt
            self.var = (1 - self.alpha) * self.var + \
                self.alpha * (dt - self.mean) ** 2
        return flagged


def best_mesh_after_failure(n_devices: int, model_parallel: int,
                            want_pod_axis: bool = False):
    """Largest mesh with the model axis preserved."""
    data = n_devices // model_parallel
    if data < 1:
        raise ValueError(
            f"cannot keep model={model_parallel} with {n_devices} devices")
    auto = jax.sharding.AxisType.Auto
    if want_pod_axis and data % 2 == 0:
        return jax.make_mesh(
            (2, data // 2, model_parallel), ("pod", "data", "model"),
            axis_types=(auto,) * 3)
    return jax.make_mesh((data, model_parallel), ("data", "model"),
                         axis_types=(auto,) * 2)


def reshard_state(state, new_mesh, *, train: bool = True):
    """Re-place a {params, opt, step} train state on a new mesh using the
    parameter sharding rules (elastic restart path)."""
    p_shapes = jax.eval_shape(lambda t: t, state["params"])
    p_shard = sharding.shard_params_specs(p_shapes, new_mesh, train=train)

    def opt_shard(path, x):
        sub = [p for p in path if getattr(p, "key", None) not in
               ("m", "v", "vr", "vc")]
        spec = sharding.param_spec(sub, x.shape, new_mesh, train=train)
        if len(spec) != len(x.shape):
            spec = jax.sharding.PartitionSpec(*([None] * len(x.shape)))
        return jax.sharding.NamedSharding(new_mesh, spec)

    new_params = jax.tree.map(jax.device_put, state["params"], p_shard)
    o_shard = jax.tree_util.tree_map_with_path(opt_shard, state["opt"])
    new_opt = jax.tree.map(jax.device_put, state["opt"], o_shard)
    step = jax.device_put(state["step"], jax.sharding.NamedSharding(
        new_mesh, jax.sharding.PartitionSpec()))
    return {"params": new_params, "opt": new_opt, "step": step}
