#!/usr/bin/env python3
"""Where a cell's device time goes, layer by layer, from one traced window.

    python3 bench/layers.py --workload cu16k_nve --seed 7 \
        --out layers.json [--xplane window.xplane.pb.gz]

Runs the cell's set-up and a warm-up call of one chunk as ``bench/run.py``
does, then one call of one chunk under the profiler, inside a
``bench.window`` span. Writes, as JSON, under ``metrics`` the ten per-layer
metrics of ``scopes.layer_metrics`` (read from the program's scopes and
``MDResult``'s neighbor counters), and besides them each layer key's device
time per MD step, their sum over the busy time, the device time under the
``md.first_build`` span, the counters, the top operations of each layer
and the longest idle gaps. ``--xplane`` keeps the raw trace, gzipped.
"""

import time

T_START = time.perf_counter()

import argparse
import gzip
import json
import os
import shutil
import sys
import tempfile
from typing import Any, Dict, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

COUNTERS = ("nbr_builds", "nbr_live_slots", "nbr_slots", "host_syncs",
            "escalations")


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--xplane", default=None)
    return ap.parse_args(argv)


def summarize(tr: Dict[str, Any], counters: Dict[str, Optional[int]],
              steps: int) -> Dict[str, Any]:
    """The report of a scoped trace ``tr`` (``trace.load``) of a window of
    ``steps`` MD steps and the program's ``counters`` for that call."""
    from bench import scopes, trace
    ms = lambda ns: ns * 1e-6 / steps            # noqa: E731
    layers = scopes.layer_ns(tr) or {}
    busy = trace.busy_ns(tr)
    first = scopes.span_device_ns(tr, "md.first_build")
    t0, t1 = trace.window(tr)
    devs = [k for k, evs in tr["devices"].items() if evs]
    top: Dict[str, Dict[str, float]] = {}
    for k in devs:
        for (name, s, d), op in zip(tr["devices"][k], tr["scopes"].get(k, [])):
            a, b = max(s, t0), min(s + d, t1)
            if b > a and not trace.CONTAINERS.match(name):
                pool = top.setdefault(scopes.layer_key(op), {})
                stem = trace.op_stem(name)
                pool[stem] = pool.get(stem, 0.0) + (b - a) * 1e-9 / len(devs)
    return {
        "steps": steps,
        "window_ms_per_step": ms(trace.window_ns(tr)),
        "busy_ms_per_step": ms(busy),
        "metrics": scopes.layer_metrics(tr, steps, counters),
        "layers_ms_per_step": {k: ms(v) for k, v in sorted(layers.items())},
        "layers_over_busy": sum(layers.values()) / busy if busy else None,
        "first_build_ms": first * 1e-6 if first is not None else None,
        "counters": counters,
        "top_ops": {k: sorted(v.items(), key=lambda kv: -kv[1])[:6]
                    for k, v in sorted(top.items())},
        "idle_gaps": trace.idle_gaps(tr),
    }


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from bench import harness, scopes, systems, trace

    _, config, cell = systems.load_cell(args.workload,
                                        systems.load_benchmark(ROOT), ROOT)
    harness.compile_cache()
    setup = harness.prepare(config, cell, args.seed)
    steps = harness.chunk_steps(cell)

    def call():
        res = harness.simulation(setup, steps, args.seed).run(
            setup.params_run, setup.pos, setup.typ, setup.box)
        jax.block_until_ready(res.final_pos)
        return res

    trace_dir = tempfile.mkdtemp(prefix="bench_layers_")
    try:
        with jax.default_matmul_precision(config["precision"]):
            call()
            log(f"set-up and warm-up {time.perf_counter() - T_START:.3f} s")
            with jax.profiler.trace(trace_dir):
                with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                    res = call()
        path = trace.find_xplane(trace_dir)
        tr = trace.load(path)
        if args.xplane:
            with open(path, "rb") as f, gzip.open(args.xplane, "wb") as g:
                shutil.copyfileobj(f, g)
        report = summarize(tr, {k: getattr(res, k, None) for k in COUNTERS},
                           steps)
        report.update(workload=args.workload, seed=args.seed,
                      atoms=len(setup.pos), rung=cell["rung"],
                      device=jax.devices()[0].device_kind)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    log(json.dumps({k: report[k] for k in (
        "busy_ms_per_step", "metrics", "layers_ms_per_step",
        "layers_over_busy", "first_build_ms", "counters")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
