"""The limits of ``correct`` stand on the chip readings committed beside
them (``bench/readings/<workload>.jsonl``, written by ``bench/readings.py``).

For every workload with a readings file: each number's limit is at least
twice the largest reading of the sound program; every fault row fails the
committed limits; every control row (the program at the TPU's default
precision) fails at least one of them. A limit edit that the readings do
not support fails here. Where a force-scale survey is committed beside them
(``bench/readings/survey/<workload>.jsonl``, from ``bench/survey.py``),
fewer than a thousandth of runs are predicted to read ``velocity`` over
its limit.
"""

import glob
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import readings, survey, systems  # noqa: E402

WORKLOADS = sorted(os.path.basename(p)[:-len(".jsonl")] for p in glob.glob(
    os.path.join(ROOT, "bench", "readings", "*.jsonl")))


def load(workload):
    """(limits, rows by side: program, control, faults) of a workload."""
    limits = systems.load_cell(workload, systems.load_benchmark(ROOT),
                               ROOT)[2]["limits"]
    with open(os.path.join(ROOT, "bench", "readings",
                           f"{workload}.jsonl")) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return limits, {
        "program": [r for r in rows if r["side"] == "program"],
        "control": [r for r in rows if r["side"] == "program@default"],
        "faults": [r for r in rows if r["side"].startswith("fault:")]}


def fails(row, limits) -> bool:
    return any(row["numbers"][k] > limits[k] for k in readings.NUMBERS)


def test_some_workload_has_readings():
    assert "cu16k_nve" in WORKLOADS


@pytest.mark.parametrize("workload", WORKLOADS)
@pytest.mark.parametrize("number", readings.NUMBERS)
def test_limit_is_twice_the_sound_maximum(workload, number):
    limits, rows = load(workload)
    sound_max = max(r["numbers"][number] for r in rows["program"])
    assert limits[number] >= 2 * sound_max


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_fault_fails(workload):
    limits, rows = load(workload)
    assert rows["faults"]
    assert all(fails(r, limits) for r in rows["faults"]), \
        [(r["seed"], r["side"]) for r in rows["faults"]
         if not fails(r, limits)]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_control_fails(workload):
    limits, rows = load(workload)
    assert len({r["seed"] for r in rows["control"]}) >= 3
    assert all(fails(r, limits) for r in rows["control"]), \
        [r["seed"] for r in rows["control"] if not fails(r, limits)]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_readings_cover_the_seeds_and_faults(workload):
    _, rows = load(workload)
    seeds = {r["seed"] for r in rows["program"]}
    assert len(seeds) == len(rows["program"]) >= 24
    assert sum(s > 2**32 for s in seeds) >= 8
    unchanged = {r["seed"] for r in rows["faults"]
                 if r["side"] == "fault:unchanged"}
    assert unchanged == seeds
    assert len({r["seed"] for r in rows["faults"]
                if r["side"] == "fault:kick_sign"}) >= 3


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_runs_read_no_counter(workload):
    _, rows = load(workload)
    for r in rows["program"] + rows["control"]:
        assert all(v == 0 for v in r["counters"].values()), r["seed"]
        assert r["v_change_rms"] > 0


SURVEYED = [w for w in WORKLOADS if os.path.exists(
    os.path.join(ROOT, "bench", "readings", "survey", f"{w}.jsonl"))]


@pytest.mark.parametrize("workload", SURVEYED)
def test_velocity_tail_is_under_a_thousandth_of_runs(workload):
    got = survey.tail(workload, os.path.join(
        ROOT, "bench", "readings", "survey", f"{workload}.jsonl"))
    assert got["seeds"] >= 400 and got["paired"] >= 24
    assert got["over_limit"] < 1e-3, got
