"""Fixed-capacity replay buffer as a pure-JAX pytree.

The whole buffer (obs/next_obs pytrees at full capacity — hundreds of MB
at the default 100k capacity) lives on device and is DONATED to the jitted
training iteration via ``donate_argnums`` in
``repro.core.training.make_iteration``, with no host round-trips.  The
donation contract is asserted by
``tests/test_training_substrate.py::test_iteration_donates_replay_buffer``.

Donation alone does not make an insert copy-free.  The TPU compiler keeps
each leaf with the capacity axis minor and performs a scatter in a
row-major tiling, so an insert by ``.at[idx].set`` relaid out every
capacity-sized leaf into that tiling and back on every call: 24 copies of
the padded observations' 12 leaves at the paper's sizes, about half of a
training iteration's device time.  ``add_batch`` therefore writes its rows
with dynamic update slices along the capacity axis, which the compiler
performs in place in the leaf's own layout, and never scatters.
``tests/test_tpu_compile.py::test_replay_insert_writes_in_place_for_v5e``
compiles the donated insert for a TPU v5e and holds it to no
capacity-sized copy.

Capacity sharding
-----------------
Under a mesh with an ``expert`` axis (``launch.mesh.make_expert_mesh``)
the buffer's capacity axis is split across devices: shard ``i`` of ``S``
owns global rows ``[i*cap/S, (i+1)*cap/S)`` and the ring scalars
(``ptr``/``size``) are replicated (``distributed.sharding.replay_specs``).
``shard_add_batch`` / ``shard_sample_local`` are the per-shard bodies used
inside ``training.make_iteration``'s ``shard_map``:

  * insert — each shard scatters only the transitions whose global ring
    index lands in its row range (``mode="drop"`` for the rest), so the
    union across shards is bit-identical to ``add_batch`` on the unsharded
    buffer;
  * sample — each shard gathers its owned rows and contributes exact zeros
    elsewhere; summing the contributions (``lax.psum`` over the expert
    axis) reproduces ``sample`` bit-for-bit because every global row is
    owned by exactly one shard.

Both are pure functions of the local shard plus ``(shard_idx, n_shards)``,
so ``tests/test_replay_sharded.py`` checks the bit-identity claim without
needing multiple devices, and ``tests/test_multidevice.py`` re-asserts it
on a real 8-device mesh.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import spans


def init(capacity: int, obs_example: Dict) -> dict:
    zeros_like_batched = lambda x: jnp.zeros((capacity,) + x.shape, x.dtype)
    return {
        "obs": jax.tree.map(zeros_like_batched, obs_example),
        "next_obs": jax.tree.map(zeros_like_batched, obs_example),
        "action": jnp.zeros((capacity,), jnp.int32),
        "reward": jnp.zeros((capacity,), jnp.float32),
        "discount": jnp.zeros((capacity,), jnp.float32),
        "ptr": jnp.zeros((), jnp.int32),
        "size": jnp.zeros((), jnp.int32),
        "capacity": capacity,
    }


@jax.named_scope(spans.INSERT)
def add_batch(buf: dict, obs, action, reward, discount, next_obs) -> dict:
    """Insert a batch of ``n <= capacity`` transitions (ring buffer).

    Every leaf is written with dynamic update slices along the capacity
    axis, never a scatter (module docstring).  One ``lax.cond`` over the
    whole buffer picks the path: a block ``[ptr, ptr + n)`` that fits
    below the capacity is one slice per leaf; a block that wraps the ring
    (scope ``replay.wrap``) is two, ``[cap - n, cap)`` and ``[0, n)``,
    each rewriting the rows it does not own as they were."""
    cap = buf["capacity"]
    ptr = buf["ptr"]
    n = action.shape[0]
    if n > buf["action"].shape[0]:
        raise ValueError(f"a batch of {n} transitions overfills a ring of "
                         f"{buf['action'].shape[0]} rows")
    rows = {"obs": obs, "next_obs": next_obs,
            "action": action.astype(jnp.int32),
            "reward": reward.astype(jnp.float32),
            "discount": discount.astype(jnp.float32)}
    data = {k: buf[k] for k in rows}

    def in_place(data):
        put = lambda dst, src: lax.dynamic_update_slice_in_dim(
            dst, src.astype(dst.dtype), ptr, 0)
        return jax.tree.map(put, data, rows)

    def wrap(data):
        with jax.named_scope(spans.WRAP):
            # the block's last m rows land in [0, m), the rest in
            # [ptr, cap); row j of either window takes batch row
            # (j + cap - ptr) % n, a rotation sliced from the batch twice
            # over (a gather there would relayout the whole buffer)
            m = ptr + n - cap
            j = jnp.arange(n)
            turn = (cap - ptr) % n

            def put(dst, src):
                src = lax.dynamic_slice_in_dim(
                    jnp.concatenate([src, src]), turn, n, 0).astype(dst.dtype)
                for start, new in ((cap - n, j >= m), (0, j < m)):
                    old = lax.dynamic_slice_in_dim(dst, start, n, 0)
                    new = new.reshape((n,) + (1,) * (src.ndim - 1))
                    dst = lax.dynamic_update_slice_in_dim(
                        dst, jnp.where(new, src, old), start, 0)
                return dst

            return jax.tree.map(put, data, rows)

    data = lax.cond(ptr + n <= cap, in_place, wrap, data)
    return {**data,
            "ptr": (ptr + n) % cap,
            "size": jnp.minimum(buf["size"] + n, cap),
            "capacity": cap}


@jax.named_scope(spans.SAMPLE)
def sample(buf: dict, key, batch_size: int) -> Dict:
    idx = jax.random.randint(key, (batch_size,), 0,
                             jnp.maximum(buf["size"], 1))
    take = lambda x: x[idx]
    return {
        "obs": jax.tree.map(take, buf["obs"]),
        "next_obs": jax.tree.map(take, buf["next_obs"]),
        "action": buf["action"][idx],
        "reward": buf["reward"][idx],
        "discount": buf["discount"][idx],
    }


# ---------------------------------------------------------------------------
# Capacity-sharded bodies (see module docstring).
# ---------------------------------------------------------------------------


def _owned_rows(buf: dict, idx: jax.Array, shard_idx,
                n_shards: int) -> Tuple[jax.Array, jax.Array, int]:
    """Map global ring indices to this shard's local rows.

    Returns (hit, local, cap_local): ``hit[k]`` marks indices this shard
    owns, ``local[k]`` is the in-shard row (meaningless where ~hit)."""
    cap_local = buf["action"].shape[0]
    lo = shard_idx * cap_local
    local = idx - lo
    hit = (local >= 0) & (local < cap_local)
    return hit, local, cap_local


@jax.named_scope(spans.INSERT)
def shard_add_batch(buf: dict, obs, action, reward, discount, next_obs, *,
                    shard_idx, n_shards: int) -> dict:
    """Per-shard ring-buffer insert: scatter the transitions whose global
    index lands in this shard's rows, drop the rest.  The global capacity
    is ``n_shards * local rows`` — never read from ``buf["capacity"]``,
    which stays the replicated global value."""
    n = action.shape[0]
    cap_local = buf["action"].shape[0]
    cap = cap_local * n_shards
    idx = (buf["ptr"] + jnp.arange(n)) % cap
    hit, local, _ = _owned_rows(buf, idx, shard_idx, n_shards)
    # out-of-shard rows are pointed past the local end and dropped
    tgt = jnp.where(hit, local, cap_local)
    set_at = lambda dst, src: dst.at[tgt].set(src, mode="drop")
    return {
        "obs": jax.tree.map(set_at, buf["obs"], obs),
        "next_obs": jax.tree.map(set_at, buf["next_obs"], next_obs),
        "action": set_at(buf["action"], action.astype(jnp.int32)),
        "reward": set_at(buf["reward"], reward.astype(jnp.float32)),
        "discount": set_at(buf["discount"], discount.astype(jnp.float32)),
        "ptr": (buf["ptr"] + n) % cap,
        "size": jnp.minimum(buf["size"] + n, cap),
        "capacity": buf["capacity"],
    }


@jax.named_scope(spans.SAMPLE)
def shard_sample_local(buf: dict, key, batch_size: int, *,
                       shard_idx, n_shards: int) -> Dict:
    """This shard's additive contribution to a global ``sample``: owned
    rows are gathered, all other rows contribute exact zeros.  Summing the
    contributions across shards (``lax.psum`` inside ``shard_map``, plain
    ``sum`` in tests) is bit-identical to ``sample`` on the unsharded
    buffer — ``key`` and ``size`` are replicated so every shard draws the
    same global indices."""
    idx = jax.random.randint(key, (batch_size,), 0,
                             jnp.maximum(buf["size"], 1))
    hit, local, _ = _owned_rows(buf, idx, shard_idx, n_shards)
    safe = jnp.where(hit, local, 0)

    def take(x):
        v = x[safe]
        m = hit.reshape(hit.shape + (1,) * (v.ndim - 1))
        return jnp.where(m, v, jnp.zeros((), v.dtype))

    return {
        "obs": jax.tree.map(take, buf["obs"]),
        "next_obs": jax.tree.map(take, buf["next_obs"]),
        "action": take(buf["action"]),
        "reward": take(buf["reward"]),
        "discount": take(buf["discount"]),
    }
