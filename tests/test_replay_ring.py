"""``replay.add_batch`` against a plain NumPy ring.

The insert writes a block that fits below the capacity with one dynamic
update slice per leaf, and a block that wraps the ring with two; these
cases hold both paths to the rows, ``ptr`` and ``size`` that a ring of
Python index arithmetic gives, lap after lap, for float, int32 and bool
leaves, eagerly and under a donated ``jax.jit`` with a traced capacity.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import replay


def _batch(rng, n):
    obs = {"f": rng.normal(size=(n, 3, 2)).astype(np.float32),
           "i": rng.integers(-9, 9, size=(n, 4)).astype(np.int32),
           "b": rng.random(size=(n, 2, 3)) < 0.5}
    nxt = {"f": obs["f"] + 1, "i": obs["i"] - 1, "b": ~obs["b"]}
    return (obs, rng.integers(0, 5, size=n).astype(np.int32),
            rng.normal(size=n).astype(np.float32),
            rng.random(size=n).astype(np.float32), nxt)


class NumpyRing:
    """Row ``(ptr + k) % capacity`` takes the batch's row ``k``."""

    def __init__(self, capacity, example):
        z = lambda x: np.zeros((capacity,) + x.shape[1:], x.dtype)
        self.cap, self.ptr, self.size = capacity, 0, 0
        self.rows = {"obs": {k: z(v) for k, v in example.items()},
                     "next_obs": {k: z(v) for k, v in example.items()},
                     "action": np.zeros(capacity, np.int32),
                     "reward": np.zeros(capacity, np.float32),
                     "discount": np.zeros(capacity, np.float32)}

    def add(self, obs, action, reward, discount, next_obs):
        new = {"obs": obs, "next_obs": next_obs, "action": action,
               "reward": reward, "discount": discount}
        for k in range(action.shape[0]):
            at = (self.ptr + k) % self.cap
            for part in ("obs", "next_obs"):
                for name, leaf in self.rows[part].items():
                    leaf[at] = new[part][name][k]
            for name in ("action", "reward", "discount"):
                self.rows[name][at] = new[name][k]
        self.ptr = (self.ptr + action.shape[0]) % self.cap
        self.size = min(self.size + action.shape[0], self.cap)


def _insert(mode):
    if mode == "eager":
        return replay.add_batch

    @functools.partial(jax.jit, donate_argnums=0)
    def insert(buf, *batch):
        return replay.add_batch(buf, *batch)

    return insert


@pytest.mark.parametrize("mode", ["eager", "jit"])
@pytest.mark.parametrize("capacity,batch", [
    (16, 4),   # the batch divides the capacity: no block ever wraps
    (10, 4),   # it does not: some blocks wrap the ring
    (7, 3),
    (11, 4),
    (8, 8),    # a block as long as the ring
    (9, 1),
])
def test_add_batch_matches_numpy_ring(mode, capacity, batch):
    rng = np.random.default_rng(capacity * 100 + batch)
    first = _batch(rng, batch)
    buf = replay.init(capacity, {k: v[0] for k, v in first[0].items()})
    if mode == "jit":
        buf = dict(buf, capacity=jnp.asarray(capacity, jnp.int32))
    ring = NumpyRing(capacity, first[0])
    insert = _insert(mode)
    rounds = -(-3 * capacity // batch) + 2  # at least three laps
    for r in range(rounds):
        tr = first if r == 0 else _batch(rng, batch)
        buf = insert(buf, *tr)
        ring.add(*tr)
        assert int(buf["ptr"]) == ring.ptr
        assert int(buf["size"]) == ring.size
        got = jax.tree.map(np.asarray, {k: buf[k] for k in ring.rows})
        for path, want in jax.tree_util.tree_leaves_with_path(ring.rows):
            have = got
            for k in path:
                have = have[k.key]
            assert have.dtype == want.dtype, path
            np.testing.assert_array_equal(have, want, err_msg=str(path))


def test_add_batch_refuses_a_batch_longer_than_the_ring():
    rng = np.random.default_rng(0)
    tr = _batch(rng, 5)
    buf = replay.init(4, {k: v[0] for k, v in tr[0].items()})
    with pytest.raises(ValueError, match="overfills"):
        replay.add_batch(buf, *tr)
