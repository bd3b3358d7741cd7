#!/usr/bin/env python3
"""Readings that the correctness limits are set from: the numbers that
the check compares, for the program and for the control (the plain
reference in bfloat16 in the program's place), over many seeds in one
process.

    python3 bench/readings.py --workload <cell> --first-seed <n> --seeds 12 --seconds 4

Each seed runs the cell's set-up, a window of ``--seconds`` at the cell's
own load and the check with the control; one JSON line per seed, then the
largest program reading and the smallest control reading of each number.
The benchmark's own runs do not run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--fault", default=None,
                    help="plant a fault of bench/tests/faults.py under the "
                         "timed path and read the program alone")
    args = ap.parse_args(argv)

    from bench import run
    need = run.resolve(ROOT, args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("readings are taken on the TPU", file=sys.stderr)
        return 2
    from repro.launch import compile_cache
    compile_cache.configure()
    if args.fault:
        import pytest

        from bench.tests import faults
        plant = {**faults.ROUTE, **faults.TRAIN}[args.fault]
        plant(pytest.MonkeyPatch())
    entry = run.load_module(need["entry"], "bench_entry").Entry(
        need["cfg"], need["traffic"])
    low, high = {}, {}
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        t0 = time.perf_counter()
        entry.setup(seed)
        t1 = time.perf_counter()
        entry.window(args.seconds)
        entry.release()
        t2 = time.perf_counter()
        nums = entry.check(control=not args.fault)
        t3 = time.perf_counter()
        print(json.dumps({"seed": seed, "setup_s": t1 - t0,
                          "check_s": t3 - t2, **nums}), flush=True)
        for k, v in nums.items():
            if k.startswith("control."):
                k = k[len("control."):]
                high[k] = min(high.get(k, v), v)
            else:
                low[k] = max(low.get(k, v), v)
    print(json.dumps({"workload": args.workload, "program_max": low,
                      "control_min": high}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
