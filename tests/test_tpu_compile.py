"""The lockstep kernel compiles for a TPU v5e chip.

Interpret mode runs the kernel as plain XLA and cannot see what the TPU
compiler refuses (reshapes across the lane dim, i1 loop carries, blocks
that overflow VMEM).  These cases compile the real kernel at the engine's
packed widths for a described ``v5e:2x2`` topology: nothing runs, no chip
is needed, only the TPU compiler that ships with libtpu.

The topology is described inside a fixture, never at import: only one
process at a time may hold libtpu, and every test worker imports this
file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.env.engine_layout import (RUN_F_CH, RUN_I_CH, WAIT_F_CH,
                                     WAIT_I_CH)
from repro.kernels.lockstep_advance import ops

R = W = 5  # the paper's run/wait queue widths (EnvConfig defaults)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu in this installation
            jax.config.update("jax_enable_compilation_cache", was)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("n,admit_order", [
    (6, "fifo"), (1024, "fifo"), (4096, "fifo"), (1024, "qos_aged")])
def test_lockstep_kernel_compiles_for_v5e(one_chip, n, admit_order):
    """Ragged caps, an availability mask and an admit_min floor ride in
    the parameter pack; block_n is the auto-tuned default, so this also
    checks that the default fits the chip's scoped VMEM."""
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    f32 = jnp.float32
    params = {"k1": sds((n,), f32), "k2": sds((n,), f32),
              "mem_capacity": sds((n,), f32),
              "mem_per_token": sds((n,), f32),
              "run_cap": sds((n,), jnp.int32),
              "wait_cap": sds((n,), jnp.int32),
              "up": sds((n,), jnp.bool_),
              "admit_min": sds((n,), f32)}
    queues = {"run_i": sds((n, R, RUN_I_CH), jnp.int32),
              "run_f": sds((n, R, RUN_F_CH), f32),
              "wait_i": sds((n, W, WAIT_I_CH), jnp.int32),
              "wait_f": sds((n, W, WAIT_F_CH), f32)}

    def step(params, queues, clocks, t_next):
        return ops.lockstep_advance(params, queues, clocks, t_next,
                                    latency_L=0.030,
                                    admit_order=admit_order,
                                    interpret=False)

    compiled = jax.jit(step).lower(params, queues, sds((n,), f32),
                                   sds((), f32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_lockstep_kernel_carries_its_name(one_chip):
    """The kernel is named ``lockstep_advance`` for the chip, whatever its
    Python function is called, so that trace reductions find it."""
    n = 6
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    f32 = jnp.float32
    params = {k: sds((n,), f32) for k in ("k1", "k2", "mem_capacity",
                                          "mem_per_token")}
    queues = {"run_i": sds((n, R, RUN_I_CH), jnp.int32),
              "run_f": sds((n, R, RUN_F_CH), f32),
              "wait_i": sds((n, W, WAIT_I_CH), jnp.int32),
              "wait_f": sds((n, W, WAIT_F_CH), f32)}

    def step(params, queues, clocks, t_next):
        return ops.lockstep_advance(params, queues, clocks, t_next,
                                    latency_L=0.030, admit_order="fifo",
                                    interpret=False)

    lowered = jax.jit(step).lower(params, queues, sds((n,), f32),
                                  sds((), f32))
    assert 'kernel_name = "lockstep_advance"' in lowered.as_text()


@pytest.mark.parametrize("capacity_arg", ["traced", "static"])
def test_replay_insert_writes_in_place_for_v5e(one_chip, capacity_arg):
    """The donated insert of one collect step of the paper's training (16
    envs, padded observations of 6 experts) into the 100,000-row buffer
    keeps the buffer in its parameter layout: no instruction, in either
    branch of the insert, copies a capacity-sized leaf.  A scatter there
    relaid out each of the 12 leaves twice (24 such copies)."""
    from repro.core import replay

    cap, n = 100_000, 16
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    f32 = jnp.float32
    obs = {"expert": ((6, 9), f32), "run": ((6, R, 7), f32),
           "wait": ((6, W, 7), f32), "run_mask": ((6, R), jnp.bool_),
           "wait_mask": ((6, W), jnp.bool_), "arrived": ((7,), f32)}
    rows = lambda m: {k: sds((m,) + s, dt) for k, (s, dt) in obs.items()}
    buf = {"obs": rows(cap), "next_obs": rows(cap),
           "action": sds((cap,), jnp.int32), "reward": sds((cap,), f32),
           "discount": sds((cap,), f32), "ptr": sds((), jnp.int32),
           "size": sds((), jnp.int32), "capacity": sds((), jnp.int32)}
    if capacity_arg == "static":
        del buf["capacity"]

    def insert(buf, *batch):
        if capacity_arg == "static":
            buf = dict(buf, capacity=cap)
        return replay.add_batch(buf, *batch)

    text = jax.jit(insert, donate_argnums=0).lower(
        buf, rows(n), sds((n,), jnp.int32), sds((n,), f32), sds((n,), f32),
        rows(n)).compile().as_text()
    copies = re.findall(rf"= \w+\[{cap}[,\]]\S* copy\(", text)
    assert not copies, copies
    assert "conditional" in text


def test_served_decode_writes_the_latent_cache_in_place_for_v5e(one_chip):
    """The decode step of DeepSeek-V2-Lite as ``dsv2lite.serve`` serves it
    (published widths, 16 of 64 routed experts held, 48 slots of 2,048
    positions) fits one chip beside its weights and writes the donated
    latent cache in place: the cache's bytes alias, and no instruction
    copies a cache-sized array."""
    import dataclasses

    from repro.configs import get_config
    from repro.env import serve_engine
    from repro.models import model

    cfg = dataclasses.replace(get_config("deepseek-v2-lite"),
                              held_experts=tuple(range(16)))
    place = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    params = place(jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), cfg)))
    cache = place(jax.eval_shape(lambda: model.init_cache(cfg, 48, 2048)))
    tokens = jax.ShapeDtypeStruct((48,), jnp.int32, sharding=one_chip)
    _, decode = serve_engine.make_steps(cfg, 2048)
    # at JAX's default precision, as the benchmark runs it (this suite
    # sets "highest", which the grouped products' kernel refuses for
    # bfloat16 operands)
    with jax.default_matmul_precision("default"):
        compiled = decode.lower(params, cache, tokens).compile()
    mem = compiled.memory_analysis()
    latent = sum(cache[k].size * cache[k].dtype.itemsize
                 for k in ("c_kv", "k_pe"))
    assert latent <= mem.alias_size_in_bytes < latent + 2**20
    assert mem.temp_size_in_bytes < 0.5e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9
    # what produces a whole cache leaf is each layer's scatter of its new
    # entries into the donated buffer (a kCustom fusion), never a copy or
    # an elementwise pass over it
    whole = [line for line in compiled.as_text().splitlines()
             if re.search(r"= bf16\[27,48,2048,\d+\]\S* \w", line)]
    assert whole
    bad = [w for w in whole if " copy(" in w or "kind=kLoop" in w]
    assert not bad, bad
