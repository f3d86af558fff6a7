#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload cu16k_nve --seed 7 --seconds 10 --trace 0

The workload names a cell of ``BENCHMARK.json``; its configuration file
(``bench/configs/``), its cell file (``bench/cells/``), its lattice
(``bench/lattices/<lattice>.py``) and the readers of its metrics
(``bench/metrics/<metric>.py``) are found by name, so a new cell or metric
is new files and entries, not an edit here. With ``--trace 0`` the
line carries the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from a profiler trace of the window. The last lines on
standard error, and the ``checks`` key that ends the result line, give each
number compared beside its limit.

Exits non-zero, and prints no result, where JAX finds no TPU or fewer chips
than the cell asks for, where the program's sources are not in the
checkout, or where a phase fails.
"""

import time

T_START = time.perf_counter()

import argparse
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def metric_entries(bench, workload: str, trace: bool):
    """The cell's metrics of one kind: every entry that lists the workload,
    or lists none."""
    key = "per_layer" if trace else "end_to_end"
    return [m for m in bench[key]
            if workload in m.get("workloads", [workload])]


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro.md.api  # noqa: F401  (the system under test)
    except ImportError as e:
        log(f"bench: the program's sources are not in this checkout ({e})")
        return 2
    import jax
    from bench import harness, systems, trace as trace_lib

    bench = systems.load_benchmark(ROOT)
    work, config, cell = systems.load_cell(args.workload, bench, ROOT)
    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": int(work["chips"])}
    if info["platform"] != "tpu":
        log(f"bench: JAX found no TPU (platform {info['platform']})")
        return 3
    if len(devices) < work["chips"]:
        log(f"bench: the cell needs {work['chips']} chips, JAX has "
            f"{len(devices)}")
        return 3
    if work["chips"] != 1:
        log(f"bench: cells on {work['chips']} chips are not built yet")
        return 3
    harness.compile_cache()
    peaks = systems.load_json(ROOT, "bench", "peaks.json")
    if info["kind"] not in peaks:
        log(f"bench: no peaks for device kind {info['kind']!r}")
        return 3

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    try:
        record = harness.run(config, cell, args.seed, args.seconds, trace_dir,
                             T_START, log=log)
        record["peaks"] = peaks[info["kind"]]
        record["trace"] = (trace_lib.load(trace_lib.find_xplane(trace_dir))
                           if trace_dir else None)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    for m in metric_entries(bench, args.workload, bool(args.trace)):
        value = systems.by_name("metrics", m["name"]).read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    info["memory_peak_bytes"] = max(record["peak_bytes_per_device"])
    line = {"correct": harness.passed(record["checks"]),
            "attempted": record["steps"],
            "failed": 0 if harness.passed(record["checks"])
            else record["steps"],
            "metrics": metrics, "device": info}
    if record["trace"] is not None:
        tr = record["trace"]
        info["busy_s"] = trace_lib.busy_ns(tr) * 1e-9
        info["window_s"] = trace_lib.window_ns(tr) * 1e-9
        line["breakdown"] = {"device_ops": trace_lib.top_ops(tr),
                             "idle_gaps": trace_lib.idle_gaps(tr)}
    line["checks"] = record["checks"]
    for name, c in record["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
