"""The cells' atoms come from lattices found by name, and their masses from
the configuration files: copper's system is the one the benchmark built
before, and the water lattice is the paper's molecule at 1 g/cm^3."""

import hashlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import systems  # noqa: E402

FIXTURE_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "fixtures", "water_root")
SEEDS = [1, 2**33 + 1]
# sha256 of pos, typ, box and masses of cu16k_nve's system cut to 3x3x3
# cells, as the benchmark built it before lattices and masses were data
# (the FCC builder and the Cu mass in bench/systems.py)
CU_DIGESTS = {
    1: "d098cdf4a3ff394c56cff5b5e4499baf91b4c4e6ff0987bb46c50f6bc8d944e8",
    2**33 + 1:
        "ae6b41f01709ff65510f14cd4c1890e767ad78da1e85ba933cb3f8a7d46206c2",
}


def water(cells=(1, 1, 1), jitter=0.0):
    return {"system": {"lattice": "water", "cells": list(cells),
                       "jitter_a": jitter}}


def distances(pos, box):
    d = pos[:, None, :] - pos[None, :, :]
    d -= box * np.round(d / box)
    return np.sqrt(np.sum(d * d, axis=-1))


@pytest.mark.parametrize("seed", SEEDS)
def test_copper_system_is_the_one_built_before(seed):
    bench = systems.load_benchmark(ROOT)
    _, config, cell = systems.load_cell("cu16k_nve", bench, ROOT)
    small = json.loads(json.dumps(cell))
    small["system"]["cells"] = [3, 3, 3]
    pos, typ, box = systems.build_system(small, seed)
    m = systems.masses(config["masses"], config["model"]["type_map"], typ)
    assert (pos.dtype, typ.dtype, box.dtype, m.dtype) == (
        np.float32, np.int32, np.float64, np.float64)
    h = hashlib.sha256()
    for a in (pos, typ, box, m):
        h.update(a.tobytes())
    assert h.hexdigest() == CU_DIGESTS[seed]


@pytest.mark.parametrize("root", [ROOT, FIXTURE_ROOT])
def test_masses_are_the_programs(root):
    """``api.Simulation`` takes its masses from ``md/lattice.MASS``; the
    reference's must be the same."""
    from repro.md import lattice
    bench = systems.load_benchmark(root)
    for entry in bench["configs"]:
        config = systems.load_json(root, entry["file"])
        type_map = config["model"]["type_map"]
        assert set(config["masses"]) == set(type_map)
        for element in type_map:
            assert config["masses"][element] == lattice.MASS[element]


def test_unknown_lattice_raises():
    for name in ("hcp", "../systems", ""):
        with pytest.raises(ValueError):
            systems.build_system({"system": {"lattice": name, "cells": [1],
                                             "jitter_a": 0.0}}, 1)


def test_water_cell_shape():
    """192 atoms to a cell, O, H, H in molecule order, 1 : 2."""
    pos, typ, box = systems.build_system(water((2, 1, 3)), 5)
    assert pos.shape == (192 * 6, 3)
    assert (typ == np.tile([0, 1, 1], 64 * 6)).all()
    np.testing.assert_allclose(box, [24.84, 12.42, 37.26])
    assert (pos >= 0).all() and (pos < box).all()


@pytest.mark.parametrize("seed", SEEDS)
def test_water_molecules_are_rigid_at_zero_jitter(seed):
    pos, _, box = systems.build_system(water((2, 2, 2)), seed)
    r = distances(pos.astype(np.float64), box)
    mol = np.arange(len(pos)) // 3
    o, h1, h2 = (np.arange(0, len(pos), 3) + k for k in range(3))
    np.testing.assert_allclose(r[o, h1], 0.9572, atol=1e-5)
    np.testing.assert_allclose(r[o, h2], 0.9572, atol=1e-5)
    arm1, arm2 = ((pos[h] - pos[o]).astype(np.float64) for h in (h1, h2))
    arm1 -= box * np.round(arm1 / box)
    arm2 -= box * np.round(arm2 / box)
    cos = np.sum(arm1 * arm2, axis=1) / 0.9572 ** 2
    np.testing.assert_allclose(np.degrees(np.arccos(cos)), 104.52, atol=2e-3)
    assert r[mol[:, None] != mol[None, :]].min() > 1.0


def test_water_bonds_move_by_the_jitter_alone():
    """With jitter, each O-H distance differs from 0.9572 A by at most the
    two atoms' own displacements (the same seed draws the same
    orientations whatever the jitter)."""
    cell, seed = (2, 1, 1), 2**33 + 7
    still, _, box = systems.build_system(water(cell, 0.0), seed)
    moved, _, _ = systems.build_system(water(cell, 0.05), seed)
    d = moved.astype(np.float64) - still
    d -= box * np.round(d / box)
    shift = np.linalg.norm(d, axis=1)
    assert shift.max() > 0.05
    r = distances(moved.astype(np.float64), box)
    o = np.arange(0, len(moved), 3)
    for k in (1, 2):
        off = np.abs(r[o, o + k] - 0.9572)
        assert (off <= shift[o] + shift[o + k] + 1e-5).all()


def test_water_is_the_same_work_for_every_seed():
    a = systems.build_system(water((2, 2, 2), 0.02), SEEDS[0])
    b = systems.build_system(water((2, 2, 2), 0.02), SEEDS[1])
    assert a[0].shape == b[0].shape
    assert (a[1] == b[1]).all() and (a[2] == b[2]).all()
    assert not (a[0] == b[0]).all()
