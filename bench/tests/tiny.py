"""A copy of the benchmark at a size a CPU test can hold: the same files,
with the traffic mixes and the router cut down."""
from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

TRAFFIC = {
    "train-poisson": {"n_envs": 2, "collect_steps": 2, "updates_per_iter": 2,
                      "batch_size": 8, "buffer_capacity": 64,
                      "warmup_transitions": 12, "checked_iterations": 3},
    "route-realworld": {"n_envs": 2, "chunk_steps": 4, "warmup_steps": 8,
                        "reset_chunks": 2, "advance_windows": 8},
}
# a smaller HAN compiles in a fraction of the time on the CPU
HAN = {"han": {"hidden": 16, "heads": 2, "layers": 1, "leaky_slope": 0.2}}
CONFIGS = {"paper6": HAN}


def edit(path: str, changes: dict) -> None:
    with open(path) as f:
        data = json.load(f)
    data.update(changes)
    with open(path, "w") as f:
        json.dump(data, f)


def make_root(tmp: str) -> str:
    """A checkout-like directory holding BENCHMARK.json and a cut-down
    ``bench/``; returns its path."""
    root = os.path.join(tmp, "root")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for name, changes in TRAFFIC.items():
        edit(os.path.join(root, "bench", "traffic", name + ".json"), changes)
    for name, changes in CONFIGS.items():
        edit(os.path.join(root, "bench", "configs", name + ".json"), changes)
    # the CPU's peaks, so that the readers that divide by a peak run
    edit(os.path.join(root, "bench", "peaks.json"),
         {"cpu": {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 1e12,
                  "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}})
    return root
