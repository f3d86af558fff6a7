"""The comparison that decides ``correct`` fails its control and the
faults a one-chip MD cell can have, and passes a sound run.

Everything runs at toy width on the CPU through the harness's own run
(``harness.run``), which is what ``bench/run.py`` calls after it has found
the chip: the sound program passes; the reference computed with bf16
operands, put in the program's place, fails; and so does the program with
its timed path broken in each of four ways. The limits, the time step and
the rebuild cadence are those of the committed copper cell.
"""

import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, systems  # noqa: E402

CONFIG = {"model": {"ntypes": 1, "type_map": ["Cu"], "rcut": 4.0,
                    "rcut_smth": 2.0, "sel": [64], "embed_widths": [8, 16, 32],
                    "axis_neuron": 4, "type_one_side": True,
                    "fit_widths": [24, 24, 24], "table_lower": -2.0,
                    "table_upper": 10.0, "cheb_order": 32, "dtype": "float32"},
          "masses": systems.load_json(ROOT, "bench", "configs",
                                      "copper.json")["masses"],
          "precision": "highest", "weights": {"head_scale": 1.0}}
COPPER = systems.load_json(ROOT, "bench", "cells", "cu16k_nve.json")
LIMITS = COPPER["limits"]
CELL = {"rung": "cheb", "system": {"lattice": "fcc", "cells": [4, 4, 4],
                                   "jitter_a": 0.1},
        "protocol": {**COPPER["protocol"], "skin_a": 1.0},
        "limits": LIMITS}
SEED = 2**33 + 12345


def clear_program_caches():
    """Compiled programs and cached engines may hold a broken path."""
    from repro.md import stepper
    stepper.md_outer_engine.cache_clear()
    stepper._dyn_cell_list_fn.cache_clear()
    jax.clear_caches()


@pytest.fixture(autouse=True)
def fresh_programs():
    yield
    clear_program_caches()


def run_toy():
    clear_program_caches()
    return harness.run(CONFIG, CELL, SEED, 0.0, None, time.perf_counter(),
                       chunks=1, log=lambda m: None)


@pytest.fixture(scope="module")
def sound():
    return run_toy()


def test_sound_run_is_correct(sound):
    assert harness.passed(sound["checks"]), sound["checks"]
    assert sound["steps"] == COPPER["protocol"]["rebuild_every"]


def test_control_fails(sound):
    out = harness.with_reference(sound["setup"], sound["out"], "bf16")
    checks = harness.check(sound["setup"], out, LIMITS)
    assert not harness.passed(checks), checks


def plant_unchanged_state(monkeypatch):
    """Break the program's MD step so that it returns its state unchanged."""
    from repro.md import stepper
    real = stepper.make_md_step

    def broken(potential, ensemble, barostat=None):
        step = real(potential, ensemble, barostat)

        def md_step(carry, *aux):
            _, thermo = step(carry, *aux)
            return carry, thermo
        return md_step

    monkeypatch.setattr(stepper, "make_md_step", broken)


def plant_backwards_kick(monkeypatch):
    """Break the program's half kick so that it pushes against the force."""
    from repro.md import integrator
    real = integrator.verlet_half_kick

    def backwards(vel, force, masses, dt):
        return real(vel, -force, masses, dt)

    monkeypatch.setattr(integrator, "verlet_half_kick", backwards)


def test_state_returned_unchanged_fails(monkeypatch):
    plant_unchanged_state(monkeypatch)
    checks = run_toy()["checks"]
    assert not harness.passed(checks), checks


def test_kick_with_the_wrong_sign_fails(monkeypatch):
    plant_backwards_kick(monkeypatch)
    checks = run_toy()["checks"]
    assert not harness.passed(checks), checks


def test_faults_planted_in_the_reference_fail(sound):
    setup, out = sound["setup"], sound["out"]
    steps = sound["steps"]
    traj = harness.reference_trajectory(setup, steps)
    for bad in (harness.unchanged_trajectory(setup, steps),
                harness.reference_trajectory(setup, steps, kick_sign=-1.0)):
        checks = harness.check(setup, harness.in_program_place(
            setup, out, bad), LIMITS, traj)
        assert not harness.passed(checks), checks
    unchanged = harness.check(setup, harness.in_program_place(
        setup, out, harness.unchanged_trajectory(setup, steps)), LIMITS, traj)
    assert unchanged["velocity"]["value"] == pytest.approx(1.0)


def test_half_the_atoms_left_out_fails(monkeypatch):
    from repro.core import dp_model
    real = dp_model.dp_energy

    def half(params, cfg, rij, nmask, atype, amask, impl=None,
             nsel_norm=None):
        keep = (jnp.arange(amask.shape[0]) % 2 == 0).astype(amask.dtype)
        return 2.0 * real(params, cfg, rij, nmask, atype, amask * keep, impl,
                          nsel_norm=nsel_norm)

    monkeypatch.setattr(dp_model, "dp_energy", half)
    checks = run_toy()["checks"]
    assert not harness.passed(checks), checks


def test_pair_dropped_where_the_list_is_made_fails(monkeypatch):
    from repro.md import neighbors
    real = neighbors.make_cell_list_fn

    def dropping(*args, **kw):
        fn = real(*args, **kw)

        def build(*a, **k):
            nlist, ovf = fn(*a, **k)
            return nlist.at[0, 0].set(-1), ovf
        return build

    monkeypatch.setattr(neighbors, "make_cell_list_fn", dropping)
    checks = run_toy()["checks"]
    assert not harness.passed(checks), checks
