"""The serve cell's window finds the requests its check compares, whatever
the seed: the cell's own traffic (48 slots, the fixed request stream) is
served at a small width through ``ExpertServer`` on a clock that advances
as a TPU v5e's engine iterations take, and the window is cut to half the
benchmark's, as on a chip twice as slow.  The weights and activations are
float32 here, so the program and the reference differ by the order of
their sums alone, at every checked slot and position, prompts of up to
1,536 tokens included."""
import types

import pytest

from bench import check
from bench import run as bench_run
from bench.tests.test_bench_check_serve import CELL, CONFIG

# a TPU v5e at the published widths: a decode step of the 48 slots, and a
# prefill's fixed and per-token seconds (bench/run.py's calibration line)
DECODE_S = 0.052
PREFILL_S, PREFILL_TOKEN_S = 0.024, 1.02e-4
WINDOW_S = 5.0
# float32 readings are about 1e-5 (bfloat16 ones at this width: 0.02-0.6)
FLOAT32_ERR = 1e-3


def entry_on_clock():
    need = bench_run.resolve(bench_run.ROOT, CELL)
    mod = bench_run.load_module(need["entry"], "bench_entry_serve_on_clock")
    clock = [0.0]
    mod.time = types.SimpleNamespace(perf_counter=lambda: clock[0])
    cfg = dict(need["cfg"], **CONFIG, param_dtype="float32")
    return mod.Entry(cfg, need["traffic"]), clock, need


def on_clock(server, clock) -> None:
    real = server.step

    def step():
        finished = real()
        e = server.iteration_log[-1]
        clock[0] += (DECODE_S if e["kind"] == "decode"
                     else PREFILL_S + PREFILL_TOKEN_S * e["x"])
        return finished

    server.step = step


@pytest.mark.parametrize("seed", [2200001691, 3987654321])
def test_every_seed_finds_its_checked_requests(seed):
    entry, clock, need = entry_on_clock()
    assert entry.traffic["slots"] == 48
    entry.setup(seed)
    on_clock(entry.server, clock)
    window = entry.window(WINDOW_S)
    assert window["decisions"] > 0
    entry.release()
    numbers = entry.check()
    assert numbers["unchecked"] == 0, numbers
    assert numbers["finished_slots"] >= entry.traffic["checked_requests"]
    ok, lines = check.verdict(numbers, need["limits"])
    assert ok, lines
    assert numbers["logit_err"] < FLOAT32_ERR, numbers
