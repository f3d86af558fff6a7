"""The plain reference agrees with the program's ``mlp`` rung at
``highest`` precision at toy size, for one type and for two."""

import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, reference, systems, weights  # noqa: E402

COPPER = {"ntypes": 1, "type_map": ["Cu"], "rcut": 4.0, "rcut_smth": 2.0,
          "sel": [64], "embed_widths": [8, 16, 32], "axis_neuron": 4,
          "fit_widths": [24, 24, 24]}
WATER = {"ntypes": 2, "type_map": ["O", "H"], "rcut": 4.0, "rcut_smth": 0.5,
         "sel": [16, 32], "embed_widths": [8, 16, 32], "axis_neuron": 4,
         "fit_widths": [24, 24, 24]}
FCC = {"lattice": "fcc", "cells": [3, 3, 3], "jitter_a": 0.1}
CU_MASS = systems.load_json(ROOT, "bench", "configs", "copper.json")["masses"]
SYSTEMS = {"copper": COPPER, "water": WATER}


def frame(name, seed=3):
    """A jittered FCC frame; for two types, every third atom of type 0."""
    model = SYSTEMS[name]
    pos, typ, box = systems.build_system({"system": FCC}, seed)
    if model["ntypes"] == 2:
        typ = (np.arange(len(pos)) % 3 != 0).astype(np.int32)
    n_of = [int((typ == t).sum()) for t in range(model["ntypes"])]
    ks = reference.neighbor_capacity(model, n_of, float(np.prod(box)))
    lists, counts = reference.neighbor_lists(pos, typ, box, model["rcut"], ks)
    dstd = reference.env_stats(model, pos, typ, box, lists)
    params = weights.make_params(seed, model, 1.0, dstd)
    return model, pos, typ, box, lists, counts, params


def sectioned(lists, sel):
    """The program's type-sectioned (N, sum(sel)) list from the per-type
    brute-force lists."""
    out = []
    for lst, s in zip(lists, sel):
        lst = np.asarray(lst)
        assert (np.sum(lst >= 0, axis=1) <= s).all()
        pad = np.full((lst.shape[0], s), -1, np.int32)
        w = min(s, lst.shape[1])
        pad[:, :w] = lst[:, :w]
        out.append(pad)
    return np.concatenate(out, axis=1)


@pytest.mark.parametrize("name", ["copper", "water"])
def test_reference_matches_mlp_at_highest(name):
    from repro.core import dp_model
    model, pos, typ, box, lists, counts, params = frame(name)
    cfg = harness.dp_config(model, "mlp")
    nlist = jax.numpy.asarray(sectioned(lists, model["sel"]))
    with jax.default_matmul_precision("highest"):
        e, f, w = dp_model.dp_energy_forces(
            params, cfg, jax.numpy.asarray(pos), nlist,
            jax.numpy.asarray(typ), jax.numpy.asarray(box, np.float32),
            impl="mlp", nsel_norm=cfg.nsel)
    e_ref, f_ref, w_ref = reference.energy_forces_virial(
        params, model, pos, typ, box, lists, "highest", block=16)
    scale = harness.energy_scale(params, len(pos))
    assert abs(float(e) - e_ref) / scale < 1e-6
    f = np.asarray(f, np.float64)
    assert np.sqrt(np.mean((f - f_ref) ** 2) / np.mean(f_ref ** 2)) < 1e-5
    assert np.max(np.abs(np.asarray(w) - w_ref)) / np.max(np.abs(w_ref)) \
        < 1e-5
    # the control's precision is visibly lower
    e_lo, f_lo, _ = reference.energy_forces_virial(
        params, model, pos, typ, box, lists, "bf16", block=16)
    assert np.sqrt(np.mean((f_lo - f_ref) ** 2) / np.mean(f_ref ** 2)) > 1e-4


def test_brute_force_lists_are_complete():
    model, pos, typ, box, lists, counts, _ = frame("water")
    d = pos[:, None, :] - pos[None, :, :]
    d -= box * np.round(d / box)
    r = np.sqrt((d.astype(np.float64) ** 2).sum(-1))
    np.fill_diagonal(r, np.inf)
    for t, (lst, cnt) in enumerate(zip(lists, counts)):
        want = (r < model["rcut"]) & (typ[None, :] == t)
        assert (cnt == want.sum(1)).all()
        lst = np.asarray(lst)
        for i in range(len(pos)):
            assert set(lst[i][lst[i] >= 0]) == set(np.nonzero(want[i])[0])


def test_initial_velocities_are_the_programs_draw():
    from repro.md import integrator, lattice
    pos, typ, _ = systems.build_system({"system": FCC}, 5)
    seed = 2**40 + 77
    masses = systems.masses(CU_MASS, ["Cu"], typ)
    mine = systems.initial_velocities(seed, masses, 330.0)
    theirs = integrator.init_velocities(
        jax.random.PRNGKey(systems.sim_seed(seed)),
        jax.numpy.asarray(lattice.masses_for(("Cu",), typ)), 330.0)
    np.testing.assert_allclose(mine, np.asarray(theirs), rtol=1e-6,
                               atol=1e-9)


def test_integration_conserves_energy_across_list_rebuilds():
    model, pos, typ, box, _, _, params = frame("copper")
    masses = systems.masses(CU_MASS, ["Cu"], typ)
    vel = systems.initial_velocities(3, masses, 3000.0)
    # a 0.2 A skin makes the hot atoms outrun the list within the run
    run = reference.integrate(params, model, pos, vel, typ, box, masses,
                              1.0, 40, skin=0.2, block=16)
    etot = run["pe"] + run["ke"]
    assert np.ptp(etot) < 1e-6 * harness.energy_scale(params, len(pos))
    assert np.ptp(run["ke"]) > 10 * np.ptp(etot)
