"""Inputs of a cell, made from its files and the seed alone.

A configuration (``bench/configs/<name>.json``) holds the model's published
sizes under ``model`` and the seeded-weight recipe under ``weights``; a cell
(``bench/cells/<name>.json``) holds the system and the MD protocol. This
module builds the atoms (the cell's lattice, ``bench/lattices/<name>.py``,
found by name, and a seeded jitter), each atom's mass (from the
configuration's ``masses``), the initial velocities and the seed's integer
streams. The velocity draw is a copy of the program's ``md/integrator.py``,
so that the benchmark's inputs do not move when that changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Dict, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]*")
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


def by_name(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py`` (a lattice builder, a metric
    reader) of the name a cell file or ``BENCHMARK.json`` gives."""
    path = os.path.join(ROOT, "bench", kind, f"{name}.py")
    if not NAME.fullmatch(name) or not os.path.isfile(path):
        raise ValueError(f"no {kind} named {name!r} under bench/{kind}/")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build_system(cell: Dict[str, Any], seed: int):
    """(pos float32 (N, 3) inside the box, typ int32 (N,), box float64 (3,))
    of the cell's system for ``seed``: the lattice ``system["lattice"]``
    (``build(system, rng)`` of ``bench/lattices/<lattice>.py``) with a
    Gaussian jitter of ``system["jitter_a"]`` on every coordinate, both
    drawn from the seed. Every seed gives the same atoms, types and box."""
    system = cell["system"]
    rng = np.random.default_rng(list(seed_words(seed)))
    pos, typ, box = by_name("lattices", system["lattice"]).build(system, rng)
    pos = pos + rng.normal(0.0, system["jitter_a"], pos.shape)
    return np.mod(pos, box).astype(np.float32), typ.astype(np.int32), box


def masses(table: Dict[str, float], type_map, typ: np.ndarray) -> np.ndarray:
    """Each atom's mass (amu): the configuration's ``masses`` ``table``
    by element, through its ``type_map``."""
    return np.array([table[t] for t in type_map])[typ]


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
