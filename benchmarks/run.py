"""Benchmark harness — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig7,roofline]
                                            [--json] [--check [--tol 1.3]]

Emits ``name,us_per_call,derived`` CSV on stdout; with ``--json`` each
section additionally writes machine-readable ``BENCH_<suite>.json`` (name,
us_per_call, parsed derived metrics) for perf-trajectory tracking, and with
``--check`` fresh ``us_per_call`` values are diffed against the committed
baselines (exit 1 beyond ``--tol``; wired into scripts/ci.sh for the engine
suite).  Sections:
  fig7/fig9    routing comparison (Poisson / real-world)      bench_routing
  fig10/table2 e2e latency decomposition + component profile  bench_latency
  fig11        number-of-experts sweep                        bench_scaling
  fig12        arrival-rate sweep                             bench_rates
  fig13        latency-req sweep + admission orders           bench_deadlines
  scenarios    scripted dynamic workload/fleet sweep          bench_scenarios
  faults       MTBF x failover-mode robustness sweep          bench_faults
  fig14/15     long-run QoS + GPU utilization                 bench_longrun
  fig16/17/18  training curves + ablations                    bench_ablation
  engine       advance_all microbenchmark (lockstep vs seed)  bench_engine
  predictors   score/length bucket predictor accuracy         bench_predictors
  roofline     dry-run roofline terms (reads experiments/)    roofline
               + engine-mode HLO roofline of advance_all
                 (timed rows; gated via BENCH_roofline.json)

CI & benchmarks
---------------
Two lanes run in ``.github/workflows/ci.yml``:

  * tier-1 (push/PR, jax matrix: pinned minimum 0.4.35 + latest):
    ``scripts/ci.sh`` = fast tests (``-m "not slow"``) + the engine,
    routing, latency, scaling, rates, deadlines, scenarios, faults and
    roofline perf gates, i.e. ``--quick
    --only <suite> --check --require-baseline --tol 1.8`` with
    ``REPRO_BENCH_RL=0`` (heuristic rows only — no router quick-training
    on shared runners; ``--quick`` also keeps the scaling suite
    CI-shaped, see ``bench_scaling``);
  * nightly (scheduled): the ``slow`` suites (multi-device subprocess
    tests, system tests) plus this harness end-to-end with ``--check``
    over every committed baseline.

Tolerance rationale (the one place it is documented): ``--tol`` compares
fresh ``us_per_call`` against the committed ``BENCH_<suite>.json``.  The
default 1.3x is right for hand runs on an idle box; CI runners share
cores with the harness and other jobs, so both lanes pass 1.8x — large
enough to absorb scheduler noise, small enough to catch a real 2x
regression.  ``--require-baseline`` makes a *missing* baseline file a
failure rather than a skip, so renames can't silently disable the gate.

Regenerating baselines (after an intentional perf change, on an idle
box)::

    PYTHONPATH=src python -m benchmarks.run --quick --only engine --json
    for s in routing latency scaling rates deadlines scenarios faults \
             roofline; do
        REPRO_BENCH_RL=0 PYTHONPATH=src python -m benchmarks.run --quick \
            --only $s --json
    done

and commit the rewritten ``BENCH_<suite>.json`` (CI-sized: ``--quick`` +
``REPRO_BENCH_RL=0`` keep step counts and row sets identical to what
ci.sh measures).
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true",
                   help="shorter eval episodes (CI-sized)")
    p.add_argument("--only", default="",
                   help="comma-separated section filter")
    p.add_argument("--json", action="store_true",
                   help="write BENCH_<suite>.json per section")
    p.add_argument("--check", action="store_true",
                   help="diff fresh us_per_call against the committed "
                        "BENCH_<suite>.json baselines; exit 1 on regression")
    p.add_argument("--tol", type=float, default=1.3,
                   help="--check regression tolerance (x baseline); see the "
                        "'CI & benchmarks' module docstring for the rationale")
    p.add_argument("--require-baseline", action="store_true",
                   help="with --check, fail (readably) when a suite's "
                        "BENCH_<suite>.json baseline is missing instead of "
                        "skipping the gate")
    args = p.parse_args()
    from repro.launch import compile_cache
    compile_cache.configure()
    only = set(args.only.split(",")) if args.only else None
    steps = 1200 if args.quick else 4000
    steps_s = 800 if args.quick else 3000

    def want(*names):
        return only is None or any(n in only for n in names)

    from benchmarks import common

    failures = []

    def section(suite, fn):
        common.drain_results()  # a fresh collection window per suite
        fn()
        rows = common.drain_results()
        if args.check:  # diff BEFORE --json overwrites the baseline file
            failures.extend(
                common.check_against_baseline(suite, rows, tol=args.tol,
                                              require=args.require_baseline))
        if args.json:
            common.write_json(suite, rows=rows)

    print("name,us_per_call,derived")
    t0 = time.time()
    if want("fig7", "fig9", "routing"):
        from benchmarks import bench_routing
        section("routing", lambda: bench_routing.run(n_steps=steps))
    if want("fig10", "table2", "latency"):
        from benchmarks import bench_latency
        section("latency", lambda: bench_latency.run(n_steps=steps_s))
    if want("fig11", "scaling"):
        from benchmarks import bench_scaling
        section("scaling", lambda: bench_scaling.run(n_steps=steps_s,
                                                     quick=args.quick))
    if want("fig12", "rates"):
        from benchmarks import bench_rates
        section("rates", lambda: bench_rates.run(n_steps=steps_s))
    if want("fig13", "deadlines"):
        from benchmarks import bench_deadlines
        section("deadlines", lambda: bench_deadlines.run(n_steps=steps_s))
    if want("scenarios"):
        from benchmarks import bench_scenarios
        section("scenarios", lambda: bench_scenarios.run(n_steps=steps_s))
    if want("faults"):
        from benchmarks import bench_faults
        section("faults", lambda: bench_faults.run(n_steps=steps_s))
    if want("fig14", "fig15", "longrun"):
        from benchmarks import bench_longrun
        section("longrun",
                lambda: bench_longrun.run(n_windows=6 if args.quick else 10))
    if want("fig16", "fig17", "fig18", "ablation"):
        from benchmarks import bench_ablation
        section("ablation", lambda: bench_ablation.run(n_steps=steps_s))
    if want("engine", "bench_engine"):
        from benchmarks import bench_engine
        section("engine",
                lambda: bench_engine.run(n_steps=1000 if args.quick else 2000))
    if want("predictors"):
        from benchmarks import bench_predictors
        section("predictors",
                lambda: bench_predictors.run(steps=300 if args.quick else 600))
    if want("roofline"):
        from benchmarks import roofline

        def roofline_section():
            # dry-run rows (derived-only; prints, needs experiments/dryrun)
            roofline.run(write_md="experiments/roofline_table.md")
            # engine-mode rows (timed; the gated BENCH_roofline.json set)
            roofline.engine_run(quick=args.quick)

        section("roofline", roofline_section)
    print(f"# total wall time: {time.time() - t0:.1f}s", file=sys.stderr)
    if args.check:
        if failures:
            print("# PERF REGRESSIONS:", file=sys.stderr)
            for f in failures:
                print(f"#   {f}", file=sys.stderr)
            sys.exit(1)
        print("# perf check passed", file=sys.stderr)


if __name__ == "__main__":
    main()
