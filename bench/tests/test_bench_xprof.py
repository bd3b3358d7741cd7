"""The trace reduction, on hand-made intervals and on a small trace
recorded on the CPU inside the test."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import xprof


def test_merge_and_clip():
    assert xprof.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert xprof.clip([(0, 3), (5, 8), (9, 12)], 2, 10) == [(2, 3), (5, 8),
                                                           (9, 10)]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    with jax.profiler.trace(tmp):
        with jax.profiler.TraceAnnotation("bench.trace"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.test.compute"):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.test.sleep"):
                    time.sleep(0.05)
    return xprof.load(tmp)


def test_reduce_recorded_trace(recorded):
    r = xprof.reduce(recorded, "bench.trace")
    assert r["devices"] == 1
    assert r["window_s"] >= 0.15
    assert 0.0 < r["busy_s"] < r["window_s"]
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / r["window_s"])
    gaps = dict(r["idle_gaps"])
    # the three sleeps are idle time spent in their own span
    assert gaps["bench.test.sleep"] >= 0.14
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"],
                                               rel=1e-6)
    names = [n for n, _ in r["device_ops"]]
    assert any("dot" in n or "fusion" in n for n in names), names
    assert all(t > 0 for _, t in r["device_ops"])
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10


def test_reduce_needs_its_span(recorded):
    with pytest.raises(ValueError, match="no host span"):
        xprof.reduce(recorded, "bench.not_there")
