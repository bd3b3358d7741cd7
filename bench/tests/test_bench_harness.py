"""Discovery by name, the refusal to run without a TPU, and a cell added
by adding files and entries only."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run as bench_run
from bench.tests import tiny

ROOT = tiny.ROOT


def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [w["name"] for w in spec()["workloads"]])
def test_every_cell_resolves_by_name(cell):
    need = bench_run.resolve(ROOT, cell)
    assert os.path.exists(need["entry"])
    assert need["limits"], "every cell has limits for its check"
    assert "TPU v5 lite" in need["peaks"]
    e2e = [m["name"] for m in bench_run.metrics_of(need["spec"], cell, False)]
    layer = [m["name"] for m in bench_run.metrics_of(need["spec"], cell, True)]
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    for name in e2e + layer:
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           name + ".py")), name


def test_moves_names_an_end_to_end_metric_of_the_same_cells():
    s = spec()
    e2e = {m["name"]: m for m in s["end_to_end"]}
    cells = [w["name"] for w in s["workloads"]]
    for m in s["per_layer"]:
        target = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in target.get("workloads", cells), (m["name"], cell)


def _bench_cmd(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper6.route",
         "--seed", "2200000003", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_to_run_without_a_tpu():
    p = _bench_cmd(ROOT, {"PYTHONPATH": os.path.join(ROOT, "src")})
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    # a directory with only BENCHMARK.json and the benchmark's own files
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _bench_cmd(str(tmp_path))
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


DEMO_ENTRY = '''
class Entry:
    """A stand-in entry: fixed work, fixed check numbers."""
    def __init__(self, cfg, traffic):
        self.traffic = traffic
    def setup(self, seed):
        self.seed = seed
    def window(self, seconds):
        n = self.traffic["ops"]
        return {"wall_s": 0.5, "attempted": n, "failed": 0, "ops": n}
    def episode_counts(self):
        return {}
    def counts(self):
        return {}
    def trace_prepare(self):
        pass
    def traced_stretch(self, seconds):
        pass
    def trace_extras(self):
        return {}
    def release(self):
        pass
    def check(self, control=False):
        return {"exact": 0.0}
'''


def demo_root(tmp_path) -> str:
    """A checkout-like directory with a cell added by files alone."""
    root = tiny.make_root(str(tmp_path))
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "entries", "demo.py"), "w") as f:
        f.write(DEMO_ENTRY)
    with open(os.path.join(b, "traffic", "demo-mix.json"), "w") as f:
        json.dump({"entry": "demo", "ops": 40}, f)
    with open(os.path.join(b, "configs", "demo.json"), "w") as f:
        json.dump({"n_experts": 1}, f)
    with open(os.path.join(b, "limits", "demo.cell.json"), "w") as f:
        json.dump({"exact": 0}, f)
    with open(os.path.join(b, "metrics", "ops_per_s.py"), "w") as f:
        f.write("def read(record):\n"
                "    w = record['window']\n"
                "    return w['ops'] / w['wall_s'] if 'ops' in w else None\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        s = json.load(f)
    s["configs"].append({"name": "demo", "source": "https://example.org",
                         "file": "bench/configs/demo.json", "reduced": [],
                         "why": "demo"})
    s["workloads"].append({"name": "demo.cell", "config": "demo",
                           "traffic": "demo-mix", "chips": 1, "why": "demo"})
    s["end_to_end"].append({"name": "ops_per_s", "unit": "ops/s",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": ["demo.cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(s, f)
    return root


def run_demo(root):
    return bench_run.run(["--workload", "demo.cell", "--seed", "2200000004",
                          "--seconds", "0.1", "--trace", "0"],
                         root=root, require_tpu=False)


def test_a_cell_is_added_by_adding_files(tmp_path):
    res = run_demo(demo_root(tmp_path))
    assert res["correct"] is True
    assert res["metrics"]["ops_per_s"] == {"value": 80.0, "unit": "ops/s"}
    assert "setup_s" in res["metrics"]
    assert list(res)[-1] == "checks"


def test_a_run_caches_every_program(tmp_path):
    # programs that compile in under a second are cached too, so that a
    # warm run compiles nothing
    import jax
    assert run_demo(demo_root(tmp_path))["correct"] is True
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
