#!/usr/bin/env python3
"""The two readings each limit of ``correct`` is set from, on the chip.

    python3 bench/readings.py --workload cu16k_nve --seeds 1-12 \
        --control-seeds 101-103 --out readings_cu16k_nve.jsonl

For every seed of ``--seeds`` the program runs the cell as configured (a
one-chunk window at the cell's size) and its numbers are read against the
reference's run from the same initial frame: the lower reading of each
number is the largest of these. For the same seed two faults are read
with the reference put in the program's place and the fault planted in
it: a step that returns its state unchanged, and (on the first
``--kick-seeds`` seeds) kicks of the wrong sign. For every seed of
``--control-seeds`` the program itself runs at the TPU's default matmul
precision (one bf16 pass per f32 product), its own lower-precision path:
the upper reading is the smallest control reading. One process serves
every seed, so only the first run of each precision compiles.

Each program row also gives ``v_change_rms`` (A/fs): rms |v_ref - v_0|,
the change the reference's forces made over the window, which is the
denominator of ``velocity``. The last line summarises each side: the
least and the largest reading of each number, each with its seed.
"""

import time

T_START = time.perf_counter()

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

NUMBERS = ("velocity", "position", "etot_drift", "energy", "virial")


def seed_list(text):
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def summarize(rows):
    """{side: {number: {"min": [value, seed], "max": [value, seed]}}}."""
    summary = {}
    for side in dict.fromkeys(r["side"] for r in rows):
        got = [r for r in rows if r["side"] == side]
        summary[side] = {
            k: {"min": list(min((r["numbers"][k], r["seed"]) for r in got)),
                "max": list(max((r["numbers"][k], r["seed"]) for r in got))}
            for k in NUMBERS}
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seed_list, required=True)
    ap.add_argument("--control-seeds", type=seed_list, default=[])
    ap.add_argument("--chunks", type=int, default=1)
    ap.add_argument("--kick-seeds", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    from bench import harness, systems
    if jax.devices()[0].platform != "tpu":
        print("readings: JAX found no TPU", file=sys.stderr)
        return 3
    harness.compile_cache()
    _, config, cell = systems.load_cell(args.workload,
                                        systems.load_benchmark(ROOT), ROOT)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")

    runs = [(s, None) for s in args.seeds] + \
        [(s, "default") for s in args.control_seeds]
    warmed = set()
    for i, (seed, precision) in enumerate(runs):
        t0 = time.perf_counter()
        rec = harness.run(config, cell, seed, 0.0, None, t0,
                          precision=precision, chunks=args.chunks,
                          warm=precision not in warmed, checked=False,
                          log=lambda m: print(m, file=sys.stderr))
        warmed.add(precision)
        setup, out = rec["setup"], rec["out"]
        t1 = time.perf_counter()
        traj = harness.reference_trajectory(setup, rec["steps"])
        t_ref = time.perf_counter() - t1
        checks = harness.check(setup, out, cell["limits"], traj)
        moved = traj["pos"] - np.asarray(setup.pos, np.float64)
        moved -= setup.box * np.round(moved / setup.box)
        kicked = traj["vel"] - np.asarray(setup.vel0, np.float64)
        emit({"seed": seed, "side": "program" if precision is None
              else "program@default",
              "numbers": {k: checks[k]["value"] for k in NUMBERS},
              "passed": harness.passed(checks),
              "counters": {k: v["value"] for k, v in checks.items()
                           if k not in NUMBERS},
              "window_s": rec["window_s"], "steps": rec["steps"],
              "reference_s": t_ref, "run_s": time.perf_counter() - t0,
              "max_displacement_a": float(np.sqrt(
                  np.max(np.sum(moved * moved, axis=1)))),
              "v_change_rms": float(np.sqrt(
                  np.mean(np.sum(kicked * kicked, axis=1))))})
        if precision is not None:
            continue
        faults = [("unchanged", harness.unchanged_trajectory(
            setup, rec["steps"]))]
        if i < args.kick_seeds:
            faults.append(("kick_sign", harness.reference_trajectory(
                setup, rec["steps"], kick_sign=-1.0)))
        for name, bad in faults:
            c = harness.check(setup, harness.in_program_place(setup, out, bad),
                              cell["limits"], traj)
            emit({"seed": seed, "side": f"fault:{name}",
                  "numbers": {k: c[k]["value"] for k in NUMBERS},
                  "passed": harness.passed(c)})
    print(json.dumps({"summary": summarize(rows),
                      "total_s": time.perf_counter() - T_START}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
