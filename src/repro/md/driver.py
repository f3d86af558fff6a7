"""Single-process MD driver reproducing the paper's protocol (Sec. 4).

The run is described by a :class:`repro.md.api.SimulationSpec` — a
``Potential`` (DP at any implementation rung, tabulated DP, analytic LJ),
an ``Ensemble`` (NVE Verlet, Langevin, Berendsen) and the protocol scalars
— and executed by :func:`run_simulation` (what ``api.Simulation.run``
calls). The default protocol is the paper's: Velocity-Verlet NVE,
Maxwell-Boltzmann init at 330 K, neighbor list with a 2 A buffer rebuilt
every 50 steps, thermo recorded every 50 steps; 99 steps => energy and
forces evaluated 100 times.

Three stepping engines share this entry point:

  engine="outer"  the whole-trajectory two-level scan (``md/stepper.py``
                  ``OuterEngine``): neighbor rebuild folded INTO the jitted
                  program, scanned over segments — one host sync and
                  overflow check per *chunk* of segments, with a chunk
                  retry from snapshot on capacity overflow.
  engine="scan"   (default) the fused on-device segment engine: one jitted
                  ``lax.scan`` per rebuild segment, donated state buffers,
                  thermo fetched once per segment, overflow checked at
                  segment boundaries (host rebuild) with escalation retry.
  engine="python" the seed per-step Python loop, kept as the trajectory
                  reference and the benchmark baseline
                  (``benchmarks/md_step_time.py``).

The engines agree on the physics: within the skin buffer every pair inside
rcut is in both lists and pairs beyond rcut contribute exactly zero, so the
only divergence is floating-point summation order.

``run_md`` remains as a DEPRECATED thin shim over the spec API; for
NVE + DP it stays bit-exact with ``Simulation.run`` (guarded by tests).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import DPConfig
from repro.md import api, integrator, lattice, neighbors, stepper

#: Host spans on the profiler's clock (no cost without an active profiler
#: beyond the annotation object); the names are listed in the README.
_span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class MDResult:
    thermo: List[Dict[str, float]]
    final_pos: np.ndarray
    final_vel: np.ndarray
    wall_s: float
    steps: int
    n_atoms: int
    engine: str = "scan"
    escalations: int = 0          # neighbor capacity escalations taken
    host_syncs: int = 0           # device->host round-trips in the hot loop
    overflow_checks: int = 0      # neighbor-overflow flags inspected
    overflow_worst: int = 0       # worst flag seen (<= 0: slot slack left)
    final_box: Optional[np.ndarray] = None   # (3,) A — moves under a barostat
    stress: Optional[np.ndarray] = None      # (steps, 3, 3) eV/A^3 per-step
    grid_rebuilds: int = 0        # cell grids re-derived from a moved box
    nbr_builds: int = 0           # neighbor builds, escalation retries and
    #                               replayed chunks' in-program builds too
    nbr_live_slots: int = 0       # live list entries (nlist >= 0) and
    nbr_slots: int = 0            # atoms x sel, summed over the in-program
    #                               builds the run stepped with (outer only)

    @property
    def us_per_step_atom(self) -> float:
        return self.wall_s * 1e6 / (self.steps * self.n_atoms)

    def press_gpa_trace(self) -> np.ndarray:
        """Per-recorded-row instantaneous pressure (GPa) convenience."""
        return np.asarray([row.get("press_gpa", np.nan)
                           for row in self.thermo])


@functools.lru_cache(maxsize=None)
def _kick_drift_jit(ensemble: api.Ensemble):
    """Seed loop's jitted first half-step, cached per (hashable) ensemble
    so the compile is reused across ``run_simulation`` calls — steady-state
    benchmark fairness."""

    @jax.jit
    def kick_drift(pos, vel, f, masses, dt, box):
        vel = ensemble.half_kick(vel, f, masses, dt)
        pos = ensemble.drift(pos, vel, dt, box)
        return pos, vel

    return kick_drift


def run_md(cfg: DPConfig, params: Any, pos: np.ndarray, typ: np.ndarray,
           box: np.ndarray, *, steps: int = 99, dt_fs: float = 1.0,
           temp_k: float = 330.0, rebuild_every: int = 50,
           thermo_every: int = 50, skin: float = 2.0,
           impl: Optional[str] = None, seed: int = 0,
           engine: str = "scan", chunk_segments: int = 8,
           escalation: Optional[stepper.EscalationPolicy] = None,
           potential: Optional[api.Potential] = None,
           ensemble: Optional[api.Ensemble] = None,
           barostat: Optional[api.Barostat] = None) -> MDResult:
    """DEPRECATED kwarg-pile entry point; thin shim over the spec API.

    Build an :class:`api.SimulationSpec` and call ``api.Simulation.run``
    instead. The shim constructs exactly that spec (a ``DPPotential``
    pinned to ``cfg.nsel`` + NVE unless ``potential``/``ensemble``
    override), so NVE + DP trajectories are bit-identical between the two
    entry points — guarded by ``tests/test_api.py``.
    """
    spec = api.SimulationSpec(
        potential=potential or api.DPPotential(cfg, impl=impl,
                                               nsel_norm=cfg.nsel),
        ensemble=ensemble or api.NVE(),
        steps=steps, dt_fs=dt_fs, temp_k=temp_k,
        rebuild_every=rebuild_every, thermo_every=thermo_every, skin=skin,
        seed=seed, engine=engine, chunk_segments=chunk_segments,
        escalation=escalation, barostat=barostat)
    return run_simulation(spec, params, pos, typ, box)


def neighbor_spec(pot: api.Potential, skin: float, n_atoms: int,
                  box: np.ndarray) -> neighbors.NeighborSpec:
    """The neighbor layout a run starts from: ``pot``'s cutoff plus
    ``skin``, its ``sel``, and cell bins sized for ``n_atoms`` in ``box``."""
    rcut_nbr = pot.rcut + skin
    return neighbors.NeighborSpec(
        rcut_nbr=rcut_nbr, sel=pot.sel,
        cell_capacity=neighbors.cell_capacity_for(n_atoms, box, rcut_nbr))


@functools.partial(jax.profiler.annotate_function, name="md.run")
def run_simulation(spec: api.SimulationSpec, params: Any, pos: np.ndarray,
                   typ: np.ndarray, box: np.ndarray) -> MDResult:
    """Run ``spec`` on ``(params, pos, typ, box)`` — the one MD entry point.

    The potential supplies the neighbor-list layout (``sel``/``rcut``) and
    the force evaluation; the ensemble supplies the integration step and
    its extra state (which rides in the scan carry). Engine selection and
    the capacity-escalation fault tolerance are exactly as documented in
    the module docstring.
    """
    if spec.engine not in ("outer", "scan", "python"):
        raise ValueError(f"unknown engine {spec.engine!r}")
    pot, ens_obj, baro = spec.potential, spec.ensemble, spec.barostat
    n = len(pos)
    masses = jnp.asarray(lattice.masses_for(pot.type_map, np.asarray(typ)))
    box_np = stepper.box_lengths(box)
    nspec = neighbor_spec(pot, spec.skin, n, box_np)

    pos = jnp.asarray(pos, jnp.float32)
    typ = jnp.asarray(typ, jnp.int32)
    boxj = stepper.pack_box(box_np)     # the DYNAMIC box: rides in the carry
    vel = integrator.init_velocities(jax.random.PRNGKey(spec.seed), masses,
                                     spec.temp_k)

    if spec.engine == "python":
        return _run_md_python(pot, ens_obj, params, pos, vel, typ, boxj,
                              box_np, masses, nspec, steps=spec.steps,
                              dt_fs=spec.dt_fs,
                              rebuild_every=spec.rebuild_every,
                              thermo_every=spec.thermo_every, barostat=baro)

    # ------------------------------------- fused on-device paths (scan/outer)
    with _span("md.first_build"):
        build = stepper.build_neighbors_escalating(
            pot.layout_cfg(), nspec, box_np, pos, typ, spec.escalation,
            dynamic_box=True)
    escalations = build.escalations
    overflow_checks = build.escalations + 1
    overflow_worst = build.overflow
    pot_run = pot.with_layout(build.spec.sel)
    with _span("md.first_force"):
        _, f, _ = pot_run.energy_forces(params, pos, typ, build.nlist,
                                        box=boxj)

    if spec.engine == "outer":
        return _run_md_outer(pot, ens_obj, params, pos, vel, f, typ, boxj,
                             box_np, masses, build, steps=spec.steps,
                             dt_fs=spec.dt_fs,
                             rebuild_every=spec.rebuild_every,
                             thermo_every=spec.thermo_every,
                             chunk_segments=spec.chunk_segments,
                             escalation=spec.escalation,
                             escalations0=escalations, barostat=baro)

    eng = stepper.md_segment_engine(pot_run, ens_obj, barostat=baro)
    carry = stepper.MDCarry(pos, vel, f, ens_obj.init_state(), boxj,
                            baro.init_state() if baro is not None else ())

    thermo: List[Dict[str, float]] = []
    stress_segs: List[np.ndarray] = []
    host_syncs = 1                      # initial build's overflow check
    nbr_builds = build.escalations + 1
    grid_rebuilds = 0
    grid_key = stepper.grid_key_for(nspec, box_np)
    ref_box_escal = box_np      # box the last volume fold was taken against
    t0 = time.perf_counter()
    step_base = 0
    for seg_len in stepper.segment_schedule(spec.steps, spec.rebuild_every):
        if step_base > 0:
            # segment boundary: rebuild the list at current positions AND
            # the current (carried) box; the overflow check + escalation
            # retry lives inside (one host sync per segment, not per step).
            # The grid is re-derived from the box each time, so a barostat
            # shrinking the box can never silently outrun the cell stencil;
            # only an actual cell-count change compiles a new search. With
            # no barostat the box provably never moves: skip the fetch
            # entirely (zero extra round-trips on the NVE path).
            if baro is not None:
                box_now = np.asarray(carry.box, float)   # device fetch
                host_syncs += 1
                key_now = stepper.grid_key_for(build.spec, box_now)
                if key_now != grid_key:
                    grid_key = key_now
                    grid_rebuilds += 1
            else:
                box_now = box_np
            # ref_box folds the carried-box volume into the capacity jump:
            # a barostat squeeze raises every density at once. The
            # reference advances to the box each fold was taken against,
            # so later overflows only fold ADDITIONAL shrink (no
            # compounding of the same density jump).
            build = stepper.build_neighbors_escalating(
                pot.layout_cfg(), build.spec, box_now, carry.pos, typ,
                spec.escalation, dynamic_box=True,
                ref_box=ref_box_escal if baro is not None else None)
            host_syncs += 1
            nbr_builds += build.escalations + 1
            overflow_checks += build.escalations + 1
            overflow_worst = max(overflow_worst, build.overflow)
            if build.escalations:
                escalations += build.escalations
                ref_box_escal = box_now
                pot_run = pot.with_layout(build.spec.sel)
                eng = stepper.md_segment_engine(pot_run, ens_obj,
                                                barostat=baro)
        carry, th = eng.run(carry, seg_len, params, build.nlist, typ,
                            masses, spec.dt_fs)
        # ONE device->host sync per segment fetches the stacked thermo
        # (pe/ke + the pressure observables ride in the same fetch).
        thermo.extend(stepper.thermo_rows(
            np.asarray(th["pe"]), np.asarray(th["ke"]), step_base,
            spec.steps, spec.thermo_every, n, press=np.asarray(th["press"]),
            vol=np.asarray(th["vol"])))
        stress_segs.append(np.asarray(th["stress"]))
        host_syncs += 1
        step_base += seg_len
    carry.pos.block_until_ready()
    wall = time.perf_counter() - t0
    return MDResult(thermo=thermo, final_pos=np.asarray(carry.pos),
                    final_vel=np.asarray(carry.vel), wall_s=wall,
                    steps=spec.steps, n_atoms=n, engine="scan",
                    escalations=escalations, host_syncs=host_syncs,
                    overflow_checks=overflow_checks,
                    overflow_worst=overflow_worst,
                    final_box=np.asarray(carry.box),
                    stress=(np.concatenate(stress_segs)
                            if stress_segs else None),
                    grid_rebuilds=grid_rebuilds, nbr_builds=nbr_builds)


def _run_md_outer(pot: api.Potential, ens_obj: api.Ensemble, params, pos,
                  vel, f, typ, boxj, box_np, masses,
                  build: stepper.NeighborBuild, *, steps, dt_fs,
                  rebuild_every, thermo_every, chunk_segments,
                  escalation, escalations0,
                  barostat: Optional[api.Barostat] = None):
    """Whole-trajectory two-level scan: rebuild folded into the program.

    Chunks of ``chunk_segments`` rebuild segments run as ONE jitted
    ``lax.scan`` over segments (each segment: on-device neighbor rebuild at
    the current positions and the current CARRIED box, then
    ``rebuild_every`` MD steps scanned inside). The host touches the device
    once per chunk: the accumulated overflow flag (+ the chunk's stacked
    thermo ride along in the same fetch). On overflow the rebuilt list
    silently truncated inside the trace, so the whole chunk is REPLAYED
    from its entry snapshot with geometrically escalated capacities — the
    segment engine's escalation policy applied at chunk granularity
    (physics pinned by the potential's layout re-targeting). A
    ``GRID_INVALID`` flag instead means a barostat moved the box past its
    static cell grid: the replay re-derives the grid from the snapshot box
    (a recompile, no capacity growth). The ensemble and barostat state (RNG
    keys, box) ride in the carry — and in the snapshot, so a replayed chunk
    re-draws the same noise.
    """
    policy = escalation or stepper.EscalationPolicy()
    n = pos.shape[0]
    grid_key = stepper.grid_key_for(build.spec, box_np)
    ref_box_escal = box_np      # box the last volume fold was taken against
    spec_n = build.spec
    pot_run = pot.with_layout(spec_n.sel)
    donate = stepper.default_donate()
    carry = stepper.OuterCarry(pos, vel, f, jnp.zeros((), jnp.int32),
                               ens_obj.init_state(), boxj,
                               barostat.init_state()
                               if barostat is not None else ())

    thermo: List[Dict[str, float]] = []
    stress_chunks: List[np.ndarray] = []
    escalations = escalations0
    grid_rebuilds = 0
    host_syncs = 1                      # initial build's overflow check
    overflow_checks = escalations0 + 1
    overflow_worst = build.overflow
    nbr_builds = escalations0 + 1       # the host-path builds before the loop
    live_slots = slots = 0
    t0 = time.perf_counter()
    step_base = 0
    for chunk, (n_segs, seg_len) in enumerate(
            stepper.chunk_schedule(steps, rebuild_every, chunk_segments)):
        for attempt in range(policy.max_attempts + 1):
            with _span("md.chunk", chunk=chunk, attempt=attempt):
                eng = stepper.md_outer_engine(pot_run, ens_obj, spec_n,
                                              grid_key, donate, barostat)
                # Chunk-entry snapshot for the escalation replay. Without
                # donation the input carry stays valid — keeping the
                # reference is free. With donation the inputs are consumed
                # by the run, so copy to host first (the buffers are
                # already synced: the previous chunk's overflow check
                # waited on them).
                with _span("md.snapshot"):
                    snap = jax.device_get(carry) if donate else carry
                with _span("md.dispatch"):
                    out, th = eng.run(carry, n_segs, seg_len, params, typ,
                                      masses, dt_fs)
                nbr_builds += n_segs        # one in-program build a segment
                with _span("md.sync"):
                    ovf = int(out.overflow)     # THE host sync for this chunk
                host_syncs += 1
                overflow_checks += 1
                if ovf >= int(neighbors.GRID_INVALID):
                    # geometry, not capacity: the carried box outgrew the
                    # static cell grid MID-chunk — the snapshot box still
                    # maps to the old counts, so re-derive from the
                    # POST-chunk box instead (coarser counts from a smaller
                    # box keep every cell >= rcut for the chunk's larger
                    # early boxes too). A box that DIPPED below validity
                    # and recovered by chunk end reproduces the old key:
                    # coarsen one cell per dim then — larger cells buy
                    # margin, so every retry makes progress instead of
                    # replaying the identical flap to exhaustion. Growing
                    # sel would never fix this.
                    key_new = stepper.grid_key_for(
                        spec_n, np.asarray(out.box, float))
                    if key_new == grid_key:
                        key_new = tuple(max(1, k - 1) for k in grid_key)
                    grid_key = key_new
                    grid_rebuilds += 1
                else:
                    overflow_worst = max(overflow_worst, ovf)
                    if ovf <= 0:
                        carry = out
                        # thermo and the build counters arrive stacked
                        # (n_segs, seg_len) / (n_segs,)
                        with _span("md.thermo_fetch"):
                            thermo.extend(stepper.thermo_rows(
                                np.asarray(th["pe"]).reshape(-1),
                                np.asarray(th["ke"]).reshape(-1),
                                step_base, steps, thermo_every, n,
                                press=np.asarray(th["press"]).reshape(-1),
                                vol=np.asarray(th["vol"]).reshape(-1)))
                            stress_chunks.append(
                                np.asarray(th["stress"]).reshape(-1, 3, 3))
                            live = np.asarray(th["nbr_live"], np.int64)
                        live_slots += int(live.sum())
                        slots += live.size * n * spec_n.nsel
                        break
                    # fold the carried-box volume ratio into the growth: a
                    # barostat-compressed chunk raises the density
                    # everywhere, so the capacity jump matches it in ONE
                    # replay. Advance the reference box afterwards — a
                    # later retry (or later chunk) only folds ADDITIONAL
                    # shrink, never re-applies the same density jump
                    # multiplicatively.
                    box_out = np.asarray(out.box, float)
                    vol_scale = policy.volume_scale(ref_box_escal, box_out)
                    ref_box_escal = box_out
                    spec_n = dataclasses.replace(
                        spec_n,
                        sel=tuple(policy.grow(s, vol_scale)
                                  for s in spec_n.sel),
                        cell_capacity=policy.grow(spec_n.cell_capacity,
                                                  vol_scale))
                    pot_run = pot.with_layout(spec_n.sel)
                    escalations += 1
                carry = stepper.OuterCarry(
                    jnp.asarray(snap.pos), jnp.asarray(snap.vel),
                    jnp.asarray(snap.force), jnp.zeros((), jnp.int32),
                    jax.tree.map(jnp.asarray, snap.ens),
                    jnp.asarray(snap.box),
                    jax.tree.map(jnp.asarray, snap.baro))
        else:
            raise RuntimeError(
                f"neighbor capacity overflow persists after "
                f"{policy.max_attempts} chunk replays (last spec: "
                f"sel={spec_n.sel}, cell_capacity={spec_n.cell_capacity})")
        step_base += n_segs * seg_len
    with _span("md.final_fetch"):
        carry.pos.block_until_ready()
        wall = time.perf_counter() - t0
        return MDResult(thermo=thermo, final_pos=np.asarray(carry.pos),
                        final_vel=np.asarray(carry.vel), wall_s=wall,
                        steps=steps, n_atoms=n, engine="outer",
                        escalations=escalations, host_syncs=host_syncs,
                        overflow_checks=overflow_checks,
                        overflow_worst=overflow_worst,
                        final_box=np.asarray(carry.box),
                        stress=(np.concatenate(stress_chunks)
                                if stress_chunks else None),
                        grid_rebuilds=grid_rebuilds, nbr_builds=nbr_builds,
                        nbr_live_slots=live_slots, nbr_slots=slots)


def _run_md_python(pot: api.Potential, ens_obj: api.Ensemble, params, pos,
                   vel, typ, boxj, box_np, masses, nspec, *, steps, dt_fs,
                   rebuild_every, thermo_every,
                   barostat: Optional[api.Barostat] = None):
    """The seed per-step loop (reference / baseline).

    Kept semantically identical to the seed except the per-rebuild
    ``assert int(ovf)`` — a blocking device->host sync inside the hot loop —
    is deferred: flags stay on device and are checked once after the run.
    The deferred flags ARE surfaced in the result (``overflow_checks`` /
    ``overflow_worst``) and ``host_syncs`` counts the real round-trips
    (initial build + each thermo fetch + the deferred check), so the three
    engines report comparable diagnostics. Under a barostat the box is a
    live device value: the per-rebuild neighbor search takes it as a traced
    argument (static grid re-derived from the host copy only when the cell
    counts change — the reference implementation of the dynamic-box
    machinery the fused engines scan).
    """
    grid_key = stepper.grid_key_for(nspec, box_np)
    # the lru-cached dynamic fn: grid-key oscillations near a cell-count
    # boundary reuse compiled programs instead of re-jitting each flip
    nbr_fn = stepper._dyn_cell_list_fn(nspec, grid_key)
    kick_drift = _kick_drift_jit(ens_obj)

    nlist, ovf = nbr_fn(pos, typ, boxj)
    host_syncs = 1
    overflow_worst = int(ovf)
    assert overflow_worst <= 0, f"neighbor overflow {overflow_worst} at init"
    e, f, _ = pot.energy_forces(params, pos, typ, nlist, box=boxj)
    ens = ens_obj.init_state()
    baro = barostat.init_state() if barostat is not None else ()

    thermo: List[Dict[str, float]] = []
    stress_steps = []
    ovf_flags = []
    grid_rebuilds = 0
    t0 = time.perf_counter()
    for step in range(steps):
        pos, vel = kick_drift(pos, vel, f, masses, dt_fs, boxj)
        if (step + 1) % rebuild_every == 0:
            if barostat is not None:
                # grid follows the barostat-moved box; recompile only when
                # the host copy says the cell counts changed (a fixed box
                # skips the fetch entirely — no extra sync on the NVE path)
                box_host = np.asarray(boxj, float)
                host_syncs += 1
                key_now = stepper.grid_key_for(nspec, box_host)
                if key_now != grid_key:
                    grid_key = key_now
                    grid_rebuilds += 1
                    nbr_fn = stepper._dyn_cell_list_fn(nspec, key_now)
            nlist, ovf = nbr_fn(pos, typ, boxj)
            ovf_flags.append(ovf)           # device scalar; no sync here
        e, f_new, stats = pot.energy_forces(params, pos, typ, nlist,
                                            box=boxj)
        vel = ens_obj.half_kick(vel, f_new, masses, dt_fs)
        vel, ens = ens_obj.finalize(vel, masses, dt_fs, ens)
        f = f_new
        vol = integrator.volume_of(boxj)
        stress = integrator.stress_tensor(
            integrator.kinetic_tensor(vel, masses), stats["virial"], vol)
        stress_steps.append(stress)         # device value; no sync here
        # thermo snapshots PRE-barostat velocities/volume — the same point
        # in the step the fused engines record, so rows are comparable
        # across engines even when SCR rescales vel by 1/mu
        if (step + 1) % thermo_every == 0 or step == steps - 1:
            ke = float(integrator.kinetic_energy(vel, masses))
            thermo.append({
                "step": step + 1, "pe": float(e), "ke": ke,
                "etot": float(e) + ke,
                "temp": float(integrator.temperature(vel, masses)),
                "press_gpa": float(integrator.pressure_of(stress))
                * integrator.EV_A3_TO_GPA,
                "vol": float(vol),
            })
            host_syncs += 1                 # the thermo fetch
        if barostat is not None:
            boxj, pos, vel, baro = barostat.apply(boxj, pos, vel, stress,
                                                  baro, dt_fs)
    pos.block_until_ready()
    wall = time.perf_counter() - t0
    if ovf_flags:
        # ONE deferred fetch inspects every rebuild's flag after the run.
        worst = int(jnp.max(jnp.stack(ovf_flags)))
        host_syncs += 1
        overflow_worst = max(overflow_worst, worst)
        assert worst <= 0, f"neighbor overflow {worst} during run"
    return MDResult(thermo=thermo, final_pos=np.asarray(pos),
                    final_vel=np.asarray(vel), wall_s=wall, steps=steps,
                    n_atoms=pos.shape[0], engine="python",
                    host_syncs=host_syncs,
                    overflow_checks=len(ovf_flags) + 1,
                    overflow_worst=overflow_worst,
                    final_box=np.asarray(boxj),
                    stress=(np.asarray(jnp.stack(stress_steps))
                            if stress_steps else None),
                    grid_rebuilds=grid_rebuilds,
                    nbr_builds=len(ovf_flags) + 1)
