"""The route cells' check on the CPU at a small size: the program passes,
the control (the reference in bfloat16) fails, and each fault a routing
cell can have, planted under the timed path, fails.  The window repeats
one range of simulated time."""
import jax
import numpy as np
import pytest

from bench import check
from bench import run as bench_run
from bench.tests import faults, tiny

CELLS = ("paper6.route",)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("route")))


def one_run(root, cell, control=False, trace=0):
    return bench_run.run(["--workload", cell, "--seed", "2200000005",
                          "--seconds", "0.3", "--trace", str(trace)],
                         root=root, require_tpu=False, control=control)


@pytest.mark.parametrize("cell", CELLS)
def test_program_passes_and_control_fails(root, cell):
    res = one_run(root, cell, control=True, trace=1)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    # the engine's program time is read from the TPU's trace alone
    assert {"device_idle.route", "mfu.route"} <= set(res["metrics"])
    control = {k[len("control."):]: v for k, v in res["control"].items()}
    ok, lines = check.verdict(control, bench_run.resolve(root, cell)["limits"])
    assert not ok, lines


@pytest.mark.parametrize("fault", sorted(faults.ROUTE))
def test_fault_fails(root, fault, monkeypatch):
    faults.ROUTE[fault](monkeypatch)
    res = one_run(root, "paper6.route")
    assert res["correct"] is False, res["checks"]


def test_window_repeats_one_simulated_range(root, monkeypatch):
    need = bench_run.resolve(root, "paper6.route")
    entry = bench_run.load_module(need["entry"], "bench_entry_route").Entry(
        need["cfg"], need["traffic"])
    entry.setup(2200000006)
    clocks, real = [], entry._chunk

    def chunk(params, states, keys):
        clocks.append(np.asarray(jax.device_get(states["clock"])))
        return real(params, states, keys)

    monkeypatch.setattr(entry, "_chunk", chunk)
    every = need["traffic"]["reset_chunks"]
    while len(clocks) < 3 * every:
        entry.window(0.05)
    warm = np.asarray(jax.device_get(entry.warm_states["clock"]))
    # every pass starts from set-up's state and runs the same chunks
    for i in range(0, len(clocks), every):
        np.testing.assert_array_equal(clocks[i], warm)
    assert np.all(clocks[1] > warm)
    np.testing.assert_array_equal(clocks[every + 1], clocks[1])
