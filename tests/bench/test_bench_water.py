"""A configuration with new species and a new kind of box needs only new
files and entries. ``fixtures/water_root`` is a benchmark root of its own
(``BENCHMARK.json``, a configuration and a cell file) that names the
paper's two-species water model at toy widths on the ``water`` lattice.
It loads through ``systems.load_cell`` and ``harness.prepare``, runs
through ``harness.run`` on the CPU as ``test_bench_check.py`` runs copper
(two type sections, two tables, two fitting nets, per-type masses), passes
``correct``, and fails it with its timed path broken."""

import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, systems  # noqa: E402
from test_bench_check import (clear_program_caches,  # noqa: E402
                              plant_backwards_kick, plant_unchanged_state)

FIXTURE_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "fixtures", "water_root")
SEED = 2**33 + 12345


def water_cell():
    bench = systems.load_benchmark(FIXTURE_ROOT)
    return systems.load_cell("h2o_toy", bench, FIXTURE_ROOT)


@pytest.fixture(autouse=True)
def fresh_programs():
    yield
    clear_program_caches()


def run_water():
    _, config, cell = water_cell()
    clear_program_caches()
    return harness.run(config, cell, SEED, 0.0, None, time.perf_counter(),
                       chunks=1, log=lambda m: None)


def test_water_cell_prepares_from_its_files_alone():
    work, config, cell = water_cell()
    assert (work["config"], cell["system"]["lattice"]) == ("water_toy",
                                                           "water")
    setup = harness.prepare(config, cell, SEED)
    n = 192 * 8
    assert len(setup.pos) == n
    assert (np.bincount(setup.typ) == [n // 3, 2 * n // 3]).all()
    np.testing.assert_array_equal(
        setup.masses, np.where(setup.typ == 0, 15.999, 1.008))
    assert sorted(setup.params["fit"]) == sorted(setup.params["embed"]) == \
        ["0", "1"]
    assert len(setup.ks) == 2 and setup.n_neighbors > 0


def test_water_sound_run_is_correct():
    rec = run_water()
    assert harness.passed(rec["checks"]), rec["checks"]
    c = rec["counters"]
    assert c["nbr_builds"] >= 1 and 0 < c["nbr_live_slots"] < c["nbr_slots"]


def test_water_state_returned_unchanged_fails(monkeypatch):
    plant_unchanged_state(monkeypatch)
    checks = run_water()["checks"]
    assert not harness.passed(checks), checks


def test_water_kick_with_the_wrong_sign_fails(monkeypatch):
    plant_backwards_kick(monkeypatch)
    checks = run_water()["checks"]
    assert not harness.passed(checks), checks
