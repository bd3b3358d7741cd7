"""The training cell's check on the CPU at a small size sees each fault
that a training cell can have, planted under the timed path."""
import pytest

from bench import run as bench_run
from bench.tests import faults, tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("train_faults")))


@pytest.mark.parametrize("fault", sorted(faults.TRAIN))
def test_fault_fails(root, fault, monkeypatch):
    faults.TRAIN[fault](monkeypatch)
    res = bench_run.run(["--workload", "paper6.train", "--seed", "2200000007",
                         "--seconds", "0.3", "--trace", "0"],
                        root=root, require_tpu=False)
    assert res["correct"] is False, res["checks"]
