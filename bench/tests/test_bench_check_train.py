"""The training cell's check on the CPU at a small size: the program
passes, the control (the reference in bfloat16) fails, and each fault a
training cell can have, planted under the timed path, fails."""
import pytest

from bench import check
from bench import run as bench_run
from bench.tests import tiny

CELL = "paper6.train"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("train")))


def one_run(root, control=False, trace=0):
    return bench_run.run(["--workload", CELL, "--seed", "2200000006",
                          "--seconds", "0.3", "--trace", str(trace)],
                         root=root, require_tpu=False, control=control)


def test_program_passes_and_control_fails(root):
    res = one_run(root, control=True, trace=1)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    for name in ("collect_ms.train", "update_ms.train", "mfu.train",
                 "device_idle.train"):
        assert name in res["metrics"], name
    control = {k[len("control."):]: v for k, v in res["control"].items()}
    ok, lines = check.verdict(control, bench_run.resolve(root, CELL)["limits"])
    assert not ok, lines

