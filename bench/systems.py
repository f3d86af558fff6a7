"""Inputs of a cell, made from its files and the seed alone.

A configuration (``bench/configs/<name>.json``) holds the model's published
sizes under ``model`` and the seeded-weight recipe under ``weights``; a cell
(``bench/cells/<name>.json``) holds the system and the MD protocol. This
module builds the atoms (a jittered FCC crystal), the initial velocities
and the seed's integer streams. The lattice and the velocity draw are
copies of the program's ``md/lattice.py`` and ``md/integrator.py``, so that
the benchmark's inputs do not move when those change.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# atomic masses (amu)
MASS = {"Cu": 63.546}
FCC_CU_A = 3.634          # paper Sec. 4
KB_EV = 8.617333262e-5    # eV / K
FORCE_TO_ACC = 9.64853329045e-3          # (eV/A)/amu in A/fs^2


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    return load_json(root, "BENCHMARK.json")


def workload_entry(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_cell(name: str, bench: Dict[str, Any], root: str = ROOT):
    """(workload entry, config file, cell file) of the workload ``name``."""
    w = workload_entry(bench, name)
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = load_json(root, conf["file"])
    cell = load_json(root, "bench", "cells", f"{w['traffic']}.json")
    return w, config, cell


def seed_words(seed: int) -> Tuple[int, int]:
    """The low and high 32-bit words of a seed up to 64 bits."""
    seed = int(seed)
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed {seed} is not a whole number below 2**64")
    return seed & 0xFFFFFFFF, seed >> 32


def sim_seed(seed: int) -> int:
    """A 31-bit seed for the program's velocity draw that still depends on
    every bit of ``seed`` (its PRNG key keeps only the low 32 bits)."""
    lo, hi = seed_words(seed)
    return (lo ^ (hi * 0x9E3779B1)) & 0x7FFFFFFF


def fcc(n: Tuple[int, int, int], a: float = FCC_CU_A):
    """FCC lattice: (positions (N, 3), types (N,), box (3,))."""
    base = np.array([[0.0, 0.0, 0.0], [0.5, 0.5, 0.0], [0.5, 0.0, 0.5],
                     [0.0, 0.5, 0.5]])
    grid = np.stack(np.meshgrid(*[np.arange(k) for k in n], indexing="ij"),
                    axis=-1).reshape(-1, 1, 3)
    pos = (grid + base[None]).reshape(-1, 3) * a
    return pos, np.zeros(len(pos), np.int32), np.asarray(n, float) * a


def build_system(cell: Dict[str, Any], seed: int):
    """(pos float32 (N, 3) inside the box, typ int32 (N,), box float64 (3,))
    of the cell's system for ``seed``. Every seed gives the same atoms,
    types and box; only the jitter moves."""
    system = cell["system"]
    rng = np.random.default_rng(list(seed_words(seed)))
    if system["lattice"] != "fcc":
        raise ValueError(f"unknown lattice {system['lattice']!r}")
    pos, typ, box = fcc(tuple(system["cells"]))
    pos = pos + rng.normal(0.0, system["jitter_a"], pos.shape)
    return np.mod(pos, box).astype(np.float32), typ.astype(np.int32), box


def masses(type_map, typ: np.ndarray) -> np.ndarray:
    return np.array([MASS[t] for t in type_map])[typ]


def initial_velocities(seed: int, masses: np.ndarray, temp_k: float
                       ) -> np.ndarray:
    """The velocities the program's NVE run starts from, (N, 3) float32 in
    A/fs: Maxwell-Boltzmann at ``temp_k`` drawn from ``sim_seed(seed)``,
    with the centre-of-mass drift removed -- the draw the program makes
    from the seed it is given, so that the reference can start from the
    same frame."""
    import jax
    import jax.numpy as jnp
    m = jnp.asarray(masses, jnp.float32)
    sigma = jnp.sqrt(KB_EV * temp_k / m * FORCE_TO_ACC)
    v = jax.random.normal(jax.random.PRNGKey(sim_seed(seed)),
                          (m.shape[0], 3)) * sigma[:, None]
    mom = jnp.sum(v * m[:, None], axis=0)
    return np.asarray(v - mom / jnp.sum(m))
