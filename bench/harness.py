"""One run of one cell: set-up, warm-up, the timed window, the check.

The system under test is the program's single-process MD entry,
``api.Simulation`` with the outer engine (neighbor rebuilds inside the
jitted chunk program, one host sync per chunk). Everything else -- the
atoms, the initial velocities, the weights, the plain reference, the
operation counts, the trace reduction and the comparison that decides
``correct`` -- is the benchmark's own.

Order of a run:
  1. set-up: the atoms and the seeded weights (on the device), the
     brute-force neighbor count of the first frame (for ``dstd`` and the
     operation counts), the program's tables, one warm-up call of one
     chunk (compiles, or loads from the persistent cache, every program
     the window runs); a second call of one chunk times a chunk only
     where the warm-up compiled something;
  2. the window: ONE ``Simulation.run`` call of a whole number of chunks,
     about ``seconds`` long, closed by the host fetch of its results; with
     ``trace`` it runs under the profiler;
  3. the device's peak memory, read before any reference work;
  4. the check: the plain reference integrates the window's steps from
     the window's initial frame, and is evaluated at its final frame.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import time
from typing import Any, Callable, Dict, Optional

import numpy as np

from bench import flops, reference, systems, weights

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
# The program's neighbor counters for the window's call (``MDResult``), which
# ``scopes.layer_metrics`` reads; None where the program has no such field.
NBR_COUNTERS = ("nbr_builds", "nbr_live_slots", "nbr_slots")


class CompileCounter:
    """While it is ``active``: the programs JAX compiles or loads from its
    persistent cache (``count``), the loads among them (``hits``) and the
    seconds both took (``seconds``). One per process: JAX keeps every
    listener."""

    _one = None

    def __new__(cls):
        if cls._one is None:
            import jax
            cls._one = super().__new__(cls)
            cls._one.active = False
            cls._one._reset()
            jax.monitoring.register_event_duration_secs_listener(
                cls._one._on_duration)
            jax.monitoring.register_event_listener(cls._one._on_event)
        return cls._one

    def _reset(self):
        self.count, self.hits, self.seconds = 0, 0, 0.0

    def _on_duration(self, event, duration, **_):
        if self.active and event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if self.active and event == CACHE_HIT_EVENT:
            self.hits += 1

    @property
    def compiled(self) -> int:
        """Programs compiled, not loaded from the cache."""
        return self.count - self.hits

    @contextlib.contextmanager
    def watch(self):
        self._reset()
        self.active = True
        try:
            yield self
        finally:
            self.active = False


def compile_cache():
    """JAX's persistent cache: where ``JAX_COMPILATION_CACHE_DIR`` says,
    else at a fixed path inside the checkout (the path is part of the
    cache's key), with every program kept."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(systems.ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def dp_config(model: Dict[str, Any], rung: str):
    from repro.core.types import DPConfig
    fields = {f.name for f in dataclasses.fields(DPConfig)}
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in model.items() if k in fields}
    return DPConfig(**kw, impl=rung)


def energy_scale(params, n_atoms: int) -> float:
    """N * sum|w_head| of the largest head: the scale f32 rounding of the
    energy follows (each atom's energy is a near-cancelling sum of head
    terms, so |E| itself is an accident of the seed)."""
    return n_atoms * max(float(np.abs(np.asarray(net["head"]["w"])).sum())
                         for net in params["fit"].values())


def kinetic_tensor(vel: np.ndarray, m: np.ndarray) -> np.ndarray:
    v = np.asarray(vel, np.float64)
    return np.einsum("i,ia,ib->ab", m, v, v) / systems.FORCE_TO_ACC


@dataclasses.dataclass
class Setup:
    model: Dict[str, Any]
    cell: Dict[str, Any]
    pos: np.ndarray
    vel0: np.ndarray                # the window's initial velocities
    typ: np.ndarray
    box: np.ndarray
    masses: np.ndarray
    params: Dict[str, Any]          # the benchmark's weights (MLP)
    params_run: Dict[str, Any]      # with the program's tables
    potential: Any                  # the program's potential for the rung
    ks: tuple                       # brute-force list widths
    n_neighbors: int                # real neighbors in the first frame


def prepare(config, cell, seed: int) -> Setup:
    """Atoms, velocities, weights and tables of a cell for ``seed``."""
    from repro.md import api
    model = config["model"]
    pos, typ, box = systems.build_system(cell, seed)
    masses = systems.masses(config["masses"], model["type_map"], typ)
    vel0 = systems.initial_velocities(seed, masses, cell["protocol"]["temp_k"])
    n_of_type = [int((typ == t).sum()) for t in range(int(model["ntypes"]))]
    ks = reference.neighbor_capacity(model, n_of_type, float(np.prod(box)))
    lists, counts = reference.neighbor_lists(pos, typ, box, model["rcut"], ks)
    dstd = reference.env_stats(model, pos, typ, box, lists)
    del lists
    params = weights.make_params(seed, model, config["weights"]["head_scale"],
                                 dstd)
    pot = api.make_potential("dp", dp_config(model, cell["rung"]),
                             impl=cell["rung"])
    # the tabulated rungs build their tables from the MLP weights
    params_run = pot.prepare_params(params) if hasattr(pot, "prepare_params") \
        else params
    return Setup(model, cell, pos, vel0, typ, box, masses, params, params_run,
                 pot, ks, flops.neighbor_total(counts))


def simulation(setup: Setup, steps: int, seed: int):
    from repro.md import api
    p = setup.cell["protocol"]
    if p["ensemble"] != "nve":
        raise ValueError(f"unknown ensemble {p['ensemble']!r}")
    return api.Simulation(api.SimulationSpec(
        potential=setup.potential, ensemble=api.NVE(), steps=steps, dt_fs=p["dt_fs"],
        temp_k=p["temp_k"], rebuild_every=p["rebuild_every"], thermo_every=1,
        skin=p["skin_a"], seed=systems.sim_seed(seed), engine="outer",
        chunk_segments=p["chunk_segments"]))


def chunk_steps(cell) -> int:
    p = cell["protocol"]
    return int(p["rebuild_every"]) * int(p["chunk_segments"])


def peak_bytes(devices) -> list:
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def reference_frame(setup: Setup, pos, box, prec: str = "highest"):
    """(E, W) of the plain reference at the frame ``pos`` in ``box``."""
    lists, _ = reference.neighbor_lists(pos, setup.typ, box,
                                        setup.model["rcut"], setup.ks)
    e, _, w = reference.energy_forces_virial(
        setup.params, setup.model, pos, setup.typ, box, lists, prec)
    return e, w


def reference_trajectory(setup: Setup, steps: int, prec: str = "highest",
                         kick_sign: float = 1.0) -> Dict[str, Any]:
    """The plain reference's NVE run of ``steps`` steps from the window's
    initial frame (``reference.integrate``)."""
    return reference.integrate(
        setup.params, setup.model, setup.pos, setup.vel0, setup.typ,
        setup.box, setup.masses, setup.cell["protocol"]["dt_fs"], steps,
        prec, kick_sign=kick_sign)


def unchanged_trajectory(setup: Setup, steps: int) -> Dict[str, Any]:
    """What a step that returns its state unchanged yields: the initial
    frame, and its energies in every thermo row."""
    e0, w0 = reference_frame(setup, setup.pos, setup.box)
    v0 = np.asarray(setup.vel0, np.float64)
    ke0 = 0.5 * float(np.trace(kinetic_tensor(v0, setup.masses)))
    return {"pos": np.asarray(setup.pos, np.float64), "vel": v0,
            "virial": w0, "pe": np.full(steps, e0), "ke": np.full(steps, ke0)}


def in_program_place(setup: Setup, out: Dict[str, Any],
                     traj: Dict[str, Any]) -> Dict[str, Any]:
    """``out`` with a trajectory of the reference's put in the program's
    place: its final frame, its thermo rows and its final stress."""
    vol = float(np.prod(np.asarray(out["final_box"], np.float64)))
    stress = (kinetic_tensor(traj["vel"], setup.masses) + traj["virial"]) / vol
    return {**out, "final_pos": traj["pos"], "final_vel": traj["vel"],
            "pe": np.asarray(traj["pe"]), "ke": np.asarray(traj["ke"]),
            "stress": stress}


def with_reference(setup: Setup, out: Dict[str, Any], prec: str,
                   kick_sign: float = 1.0) -> Dict[str, Any]:
    """``out`` with the reference's own run at ``prec`` in the program's
    place."""
    return in_program_place(setup, out, reference_trajectory(
        setup, len(out["pe"]), prec, kick_sign))


def check(setup: Setup, out: Dict[str, Any], limits: Dict[str, float],
          traj: Optional[Dict[str, Any]] = None
          ) -> Dict[str, Dict[str, float]]:
    """Numbers compared, each beside its limit, and the counters that must
    read 0. ``traj`` is the reference's run of the window's steps from the
    window's initial frame (made here where not given).

    ``velocity``: rms |v - v_ref| at the end over rms |v_ref - v_0|, the
    change the forces made. ``position``: the largest distance (A, minimum
    image) between an atom and its reference. ``etot_drift``: the spread
    (max - min) of pe + ke over the window's thermo rows, over
    N * sum|w_head|. ``energy``: the last thermo row's energy against the
    reference at the program's final frame, over N * sum|w_head|.
    ``virial``: W = sigma V - sum m v (x) v from the last stress against
    the reference at that frame, over max|W_ref|."""
    n = len(setup.pos)
    pos, vel, box = out["final_pos"], out["final_vel"], out["final_box"]
    rows = np.asarray(out["pe"]) + np.asarray(out["ke"])
    bad_rows = int(np.sum(~np.isfinite(rows)))
    names = ("velocity", "position", "etot_drift", "energy", "virial")
    if np.isfinite(pos).all() and np.isfinite(vel).all() and not bad_rows:
        if traj is None:
            traj = reference_trajectory(setup, len(rows))
        scale = energy_scale(setup.params, n)
        v0 = np.asarray(setup.vel0, np.float64)
        dv = np.asarray(vel, np.float64) - traj["vel"]
        velocity = float(np.sqrt(np.sum(dv * dv)
                                 / np.sum((traj["vel"] - v0) ** 2)))
        bx = np.asarray(box, np.float64)
        dx = np.asarray(pos, np.float64) - traj["pos"]
        dx -= bx * np.round(dx / bx)
        position = float(np.sqrt(np.max(np.sum(dx * dx, axis=1))))
        etot_drift = float(np.max(rows) - np.min(rows)) / scale
        e_ref, w_ref = reference_frame(setup, pos, box)
        vol = float(np.prod(bx))
        w_prog = out["stress"] * vol - kinetic_tensor(vel, setup.masses)
        energy = abs(out["pe"][-1] - e_ref) / scale
        virial = float(np.max(np.abs(w_prog - w_ref))
                       / np.max(np.abs(w_ref)))
        values = (velocity, position, etot_drift, energy, virial)
    else:
        values = (float("inf"),) * len(names)
        bad_rows = max(bad_rows, 1)
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in zip(names, values)}
    checks.update({
        "nonfinite_rows": {"value": bad_rows, "limit": 0},
        "atoms_lost": {"value": n - len(pos), "limit": 0},
        "compiles_in_window": {"value": out["compiles"], "limit": 0},
        "escalations_in_window": {"value": out["escalations"], "limit": 0},
        # one host sync for the call's first build, then one per chunk
        "host_syncs_off": {"value": abs(out["host_syncs"] - 1
                                        - out["chunks"]), "limit": 0},
    })
    return checks


def passed(checks) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def run(config, cell, seed: int, seconds: float, trace_dir: Optional[str],
        t_start: float, precision: Optional[str] = None, chunks: Optional[int] = None,
        warm: bool = True, log: Callable[[str], None] = print,
        checked: bool = True) -> Dict[str, Any]:
    """One run of a one-chip cell: the record the metric readers take,
    with ``checks``. ``precision`` overrides the configuration's (the
    control runs the program at ``default``); ``chunks`` fixes the window
    instead of sizing it from ``seconds``; ``warm=False`` skips the
    warm-up call (for a process that has run the cell before), and
    ``checked=False`` the check."""
    import jax
    counter = CompileCounter()
    precision = precision or config["precision"]
    setup = prepare(config, cell, seed)
    c_steps = chunk_steps(cell)

    def call(steps):
        t0 = time.perf_counter()
        res = simulation(setup, steps, seed).run(
            setup.params_run, setup.pos, setup.typ, setup.box)
        jax.block_until_ready(res.final_pos)
        return res, time.perf_counter() - t0

    with jax.default_matmul_precision(precision):
        t_call = None
        if warm:
            with counter.watch() as w:
                t_warm = call(c_steps)[1]
            log(f"warm-up call {t_warm:.3f} s: {w.compiled} programs "
                f"compiled, {w.hits} loaded, {w.seconds:.3f} s in both")
            if not w.compiled:
                t_call = t_warm - w.seconds
        if chunks is None:
            if t_call is None:
                t_call = call(c_steps)[1]
                log(f"sizing call {t_call:.3f} s")
            chunks = max(1, round(seconds / t_call))
        steps = chunks * c_steps
        sim = simulation(setup, steps, seed)
        setup_s = time.perf_counter() - t_start
        log(f"set-up {setup_s:.3f} s; window {chunks} chunks x {c_steps} "
            f"steps")
        profiler = (jax.profiler.trace(trace_dir) if trace_dir
                    else contextlib.nullcontext())
        with profiler, counter.watch() as window_compiles:
            with jax.profiler.TraceAnnotation("bench.window"):
                t0 = time.perf_counter()
                res = sim.run(setup.params_run, setup.pos, setup.typ,
                              setup.box)
                jax.block_until_ready(res.final_pos)
                window_s = time.perf_counter() - t0
    peaks = peak_bytes(jax.devices()[:1])
    out = {
        "final_pos": np.asarray(res.final_pos),
        "final_vel": np.asarray(res.final_vel),
        "final_box": np.asarray(res.final_box, np.float64),
        "stress": np.asarray(res.stress[-1], np.float64),
        "pe": np.array([r["pe"] for r in res.thermo], np.float64),
        "ke": np.array([r["ke"] for r in res.thermo], np.float64),
        "compiles": window_compiles.count,
        "escalations": int(res.escalations),
        "host_syncs": int(res.host_syncs),
        "chunks": chunks,
    }
    counters = {k: getattr(res, k, None) for k in NBR_COUNTERS}
    del res, sim
    t0 = time.perf_counter()
    checks = check(setup, out, cell["limits"]) if checked else None
    log(f"window {window_s:.3f} s; check {time.perf_counter() - t0:.3f} s")
    n = len(setup.pos)
    return {
        "atoms": n, "chips": 1, "steps": steps,
        "force_evals": steps + 1, "window_s": window_s, "setup_s": setup_s,
        "peak_bytes_per_device": peaks,
        "model_flops_per_eval": flops.model_flops_per_step(
            setup.model, n, setup.n_neighbors),
        "dp_fused_per_eval": flops.dp_fused_cost_per_step(
            setup.model, n, setup.n_neighbors),
        "counters": counters, "checks": checks, "setup": setup, "out": out,
    }
