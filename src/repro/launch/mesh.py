"""Production mesh construction.

Defined as functions (not module constants) so importing this module never
touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to obtain placeholder devices.
"""
from __future__ import annotations

import functools

import jax
import numpy as np
from jax.sharding import AxisType


def device_order(n_devices: int = None):
    """The first ``n_devices`` visible devices in PROCESS-MAJOR order:
    sorted by (process_index, id), i.e. each host's devices form one
    contiguous block.  Under ``jax.distributed`` multi-host runs this is
    the enumeration every expert/train mesh uses, so an expert shard
    never straddles hosts and the queue tensors a host owns stay on its
    own HBM; single-process it reduces to ``jax.devices()`` order."""
    devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    return devs[:n_devices] if n_devices else devs


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


@functools.lru_cache(maxsize=None)
def _expert_mesh_cached(n: int):
    if jax.process_count() > 1:
        # Multi-host (jax.distributed initialized): build the mesh from an
        # explicit process-major device array so each host's expert shards
        # live on its own devices.  jax.make_mesh's device assignment is
        # free to interleave hosts, so we bypass it here.
        return jax.sharding.Mesh(np.asarray(device_order(n)), ("expert",))
    return jax.make_mesh((n,), ("expert",), axis_types=(AxisType.Auto,))


def make_expert_mesh(n_devices: int = None):
    """1-D mesh over the ``expert`` logical axis (scheduling-engine expert
    sharding, `engine.advance_all(backend="shard_map")`).  Defaults to all
    visible devices — ALL hosts' devices in process-major ``device_order``
    under ``jax.distributed`` multi-host runs; cached so jitted engine
    steps can call it freely."""
    return _expert_mesh_cached(n_devices or len(jax.devices()))


@functools.lru_cache(maxsize=None)
def _train_mesh_cached(n: int, data):
    if data is None:
        return _expert_mesh_cached(n)
    if data < 1 or n % data:
        raise ValueError(
            f"n_devices={n} not divisible into a data axis of {data}")
    devs = np.asarray(device_order(n)).reshape(data, n // data)
    return jax.sharding.Mesh(devs, ("data", "expert"))


def make_train_mesh(n_devices: int = None, data: int = None):
    """Mesh for the router-training substrate.  ``data=None`` keeps the
    1-D ``expert`` axis the scheduling engine shards over —
    ``training.make_iteration(mesh=...)`` splits the replay buffer's
    capacity axis across it while params / envs stay replicated (see
    ``repro.core.training``).  ``data=k`` builds a 2-D ``("data",
    "expert")`` mesh (process-major ``device_order``, so it composes with
    multi-host): the collect batch (env axis) shards over ``data`` while
    the buffer still shards over ``expert`` — bit-identical to the 1-D
    path (``distributed.sharding.DATA``).  ``data=1`` is a degenerate but
    valid 2-D mesh, letting a single device exercise the gather path."""
    return _train_mesh_cached(n_devices or len(jax.devices()), data)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU runs)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // max(data, 1)))
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


# TPU v5e hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12       # per chip (MXU, bf16)
VPU_FLOPS_F32 = 3.9e12         # per chip (vector unit, f32 elementwise)
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link
