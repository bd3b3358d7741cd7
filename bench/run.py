#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json`` at the
root of the checkout:

* ``bench/configs/<config>.json``   the configuration (fleet, router);
* ``bench/traffic/<traffic>.json``  the traffic mix, which names its entry;
* ``bench/entries/<entry>.py``      what the window drives (``Entry``);
* ``bench/metrics/<metric>.py``     one reader per metric (``read``);
* ``bench/limits/<cell>.json``      the limits of the correctness check;
* ``bench/peaks.json``              the chip's peaks, by ``device_kind``.

A run loads, builds the inputs and weights on the device from the seed,
compiles and warms up (``setup_s``, timed from process start), measures
for ``--seconds``, reads the device's peak memory, with ``--trace 1``
runs a further stretch under the profiler and reduces the trace, frees
the program's state and checks what the window produced against the
plain reference.  The last line of standard output is one JSON object;
the compared numbers with their limits are the last lines of standard
error and the result's last key.  Without a TPU, or with fewer chips than
the cell asks for, the run exits with 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# long enough for several chunks or iterations, short enough that the
# profiler keeps every device event of the smallest cell's many tiny ops
TRACE_SECONDS = 0.5


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def by_name(items: list, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def resolve(root: str, workload: str) -> dict:
    """Everything the named cell needs, found by name."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = by_name(spec["workloads"], workload, "workload")
    config = by_name(spec["configs"], cell["config"], "config")
    bench = os.path.join(root, "bench")
    traffic = load_json(os.path.join(bench, "traffic", cell["traffic"] + ".json"))
    return {"spec": spec, "cell": cell,
            "cfg": load_json(os.path.join(root, config["file"])),
            "traffic": traffic,
            "entry": os.path.join(bench, "entries", traffic["entry"] + ".py"),
            "limits": load_json(os.path.join(bench, "limits",
                                             workload + ".json")),
            "peaks": load_json(os.path.join(bench, "peaks.json"))}


def metrics_of(spec: dict, cell: str, trace: bool) -> list:
    """The cell's metrics of one kind: end-to-end, or per-layer when
    traced.  A metric without ``workloads`` belongs to every cell."""
    kind = spec["per_layer" if trace else "end_to_end"]
    return [m for m in kind if cell in m.get("workloads", [cell])]


def read_metrics(root: str, metrics: list, record: dict) -> dict:
    out = {}
    for m in metrics:
        reader = load_module(os.path.join(root, "bench", "metrics",
                                          m["name"] + ".py"),
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def device_info(devices, chips: int) -> dict:
    used = devices[:chips]
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": peak}


def run(argv=None, *, root: str = ROOT, require_tpu: bool = True,
        control: bool = False) -> dict:
    """One run; returns the result object (``None`` where the run may not
    measure).  ``require_tpu=False`` lets the tests drive the rest of a
    run on the CPU; ``control`` adds the control's numbers to the check."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    need = resolve(root, args.workload)
    chips = need["cell"]["chips"]
    for p in (root, os.path.join(root, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    t_jax = time.perf_counter()
    devices = jax.devices()
    t_devices = time.perf_counter()
    if require_tpu:
        if devices[0].platform != "tpu" or len(devices) < chips:
            log(f"needs {chips} TPU chip(s); JAX has {len(devices)} "
                f"{devices[0].platform} device(s)")
            return None
        if devices[0].device_kind not in need["peaks"]:
            log(f"device kind {devices[0].device_kind!r} is not in peaks.json")
            return None
    from repro.launch import compile_cache
    log(f"compile cache: {compile_cache.configure()}")
    # keep every program in the cache, however quick it was to compile,
    # so that a warm run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # seconds of JAX's tracing, lowering and compiling; a compile that the
    # persistent cache serves still counts as one backend compile
    jax_s = {}
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: jax_s.setdefault(
            event.rsplit("/", 1)[1], []).append(secs)
        if event.startswith("/jax/core/compile/") else None)
    compiles = lambda: len(jax_s.get("backend_compile_duration", []))

    entry = load_module(need["entry"], "bench_entry_" + need["traffic"]["entry"]
                        ).Entry(need["cfg"], need["traffic"])
    entry.setup(args.seed)
    setup_s = time.perf_counter() - T_START
    compiled_in_setup = compiles()
    log(f"setup {setup_s!r} s: importing JAX {t_jax - T_START!r} s, finding "
        f"the devices {t_devices - t_jax!r} s, the entry "
        f"{setup_s - (t_devices - T_START)!r} s, of which JAX "
        f"{json.dumps({k: sum(v) for k, v in jax_s.items()})} s; "
        f"{compiled_in_setup} programs compiled or loaded from the cache")
    window = entry.window(args.seconds)
    compiled_in_window = compiles() - compiled_in_setup
    device = device_info(devices, chips)
    log(f"window {window['wall_s']!r} s, {compiled_in_window} programs "
        f"compiled or loaded inside it")
    log(f"window: {json.dumps(window)}")
    log(f"program counts: {json.dumps(entry.episode_counts())}")

    record = {"cell": args.workload, "setup_s": setup_s, "window": window,
              "counts": entry.counts(),
              "peak": need["peaks"].get(device["kind"]), "device": device}
    breakdown = None
    if args.trace:
        entry.trace_prepare()
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            with jax.profiler.trace(tmp):
                with jax.profiler.TraceAnnotation("bench.trace"):
                    entry.traced_stretch(TRACE_SECONDS)
                record["extras"] = entry.trace_extras()
            from bench import xprof
            pd = xprof.load(tmp)
            record["profile"] = pd
            record["trace"] = xprof.reduce(pd, "bench.trace")
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
        breakdown = {k: record["trace"][k] for k in ("device_ops",
                                                     "idle_gaps")}
        log(f"trace: {json.dumps(record['trace'])}")
        log(f"trace extras: {json.dumps(record['extras'])}")

    entry.release()
    from bench import check
    t_check = time.perf_counter()
    numbers = entry.check(control=control)
    log(f"check against the reference took "
        f"{time.perf_counter() - t_check!r} s")
    log(f"numbers read against the reference: {json.dumps(numbers)}")
    correct, lines = check.verdict(numbers, need["limits"])
    if compiled_in_window:
        correct = False
        lines.append(f"compiles_in_window {compiled_in_window} limit 0 FAIL")
    metrics = read_metrics(root, metrics_of(need["spec"], args.workload,
                                            bool(args.trace)), record)
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": window["failed"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control:
        result["control"] = {k: v for k, v in numbers.items()
                             if k.startswith("control.")}
    result["checks"] = {k: {"value": numbers.get(k), "limit": v}
                        for k, v in need["limits"].items()}
    for line in lines:
        log(f"check: {line}")
    return result


def main(argv=None) -> int:
    result = run(argv)
    if result is None:
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
