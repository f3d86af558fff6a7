#!/usr/bin/env python3
"""How the seeded model's force scale spreads over seeds, and what share of
runs that puts over the ``velocity`` limit.

    python3 bench/survey.py --workload cu16k_nve \
        --seeds 1-200,8589934593-8589934792 \
        --out bench/readings/survey/cu16k_nve.jsonl
    python3 bench/survey.py --workload cu16k_nve \
        --tail bench/readings/survey/cu16k_nve.jsonl

The first form writes one row per seed: the cell's atoms and seeded weights
(as a run makes them) and the plain reference's rms force on the first
frame, ``f_rms`` (eV/A). It runs on any backend, at the cell's size or, with
``--cells``, at a smaller box of the same lattice.

The second form takes no device. ``velocity`` is rms |v - v_ref| over
rms |v_ref - v_0|. Its denominator follows the seed's force scale, and its
numerator (the velocity update's f32 rounding plus the program's absolute
force error) stays within a few 1e-9 A/fs whatever that scale is (the
committed readings). So it pairs every surveyed seed with every sound
reading of ``bench/readings/<workload>.jsonl``: the pair's ``velocity`` is
the reading's numerator over ``c * f_rms`` of the surveyed seed, where
``c`` is the median ratio v_change_rms / f_rms over the seeds that both
files hold. It prints the share of pairs over the cell's limit, and over
half of it.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def survey(workload: str, seeds, cells, out: str) -> None:
    import numpy as np
    from bench import reference, systems, weights
    _, config, cell = systems.load_cell(workload, systems.load_benchmark(ROOT),
                                        ROOT)
    if cells:
        cell = dict(cell, system=dict(cell["system"], cells=[cells] * 3))
    model = config["model"]
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    for seed in seeds:
        pos, typ, box = systems.build_system(cell, seed)
        n_of_type = [int((typ == t).sum()) for t in range(int(model["ntypes"]))]
        ks = reference.neighbor_capacity(model, n_of_type, float(np.prod(box)))
        lists, _ = reference.neighbor_lists(pos, typ, box, model["rcut"], ks)
        dstd = reference.env_stats(model, pos, typ, box, lists)
        params = weights.make_params(seed, model,
                                     config["weights"]["head_scale"], dstd)
        _, f, _ = reference.energy_forces_virial(params, model, pos, typ, box,
                                                 lists)
        row = {"seed": seed, "atoms": len(pos), "cells": cell["system"]["cells"],
               "f_rms": float(np.sqrt(np.mean(np.sum(f * f, axis=1))))}
        print(json.dumps(row), flush=True)
        with open(out, "a") as fh:
            fh.write(json.dumps(row) + "\n")


def tail(workload: str, survey_path: str) -> dict:
    """Share of (surveyed seed, sound reading) pairs whose ``velocity``
    would read over the cell's limit, and over half of it."""
    import statistics
    from bench import systems
    limit = systems.load_cell(workload, systems.load_benchmark(ROOT),
                              ROOT)[2]["limits"]["velocity"]
    with open(survey_path) as fh:
        scale = {r["seed"]: r["f_rms"] for r in map(json.loads, fh)}
    with open(os.path.join(ROOT, "bench", "readings",
                           f"{workload}.jsonl")) as fh:
        sound = [r for r in map(json.loads, fh) if r["side"] == "program"]
    both = [r for r in sound if r["seed"] in scale]
    c = statistics.median(r["v_change_rms"] / scale[r["seed"]] for r in both)
    nums = [r["numbers"]["velocity"] * r["v_change_rms"] for r in sound]
    reads = [n / (c * f) for n in nums for f in scale.values()]
    return {"seeds": len(scale), "readings": len(nums), "paired": len(both),
            "c": c, "limit": limit,
            "over_limit": sum(v > limit for v in reads) / len(reads),
            "over_half_limit": sum(v > limit / 2 for v in reads) / len(reads),
            "largest": max(reads)}


def main(argv=None) -> int:
    from bench.readings import seed_list
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seed_list)
    ap.add_argument("--cells", type=int, default=0)
    ap.add_argument("--out")
    ap.add_argument("--tail")
    args = ap.parse_args(argv)
    if args.tail:
        print(json.dumps(tail(args.workload, args.tail)))
        return 0
    if not (args.seeds and args.out):
        ap.error("--seeds and --out, or --tail")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import harness
    harness.compile_cache()
    survey(args.workload, args.seeds, args.cells, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
