"""Fused on-device MD stepping engine: ``lax.scan`` over rebuild segments.

The seed driver dispatched every Velocity-Verlet step from Python and synced
device->host for thermo/overflow each step — per-step launch overhead and
pipeline bubbles that cap throughput far below the hardware (the paper's
headline numbers come precisely from eliminating per-step overheads, Sec. 3.4;
the follow-up work fuses whole step sequences). This module keeps the inner
loop resident on the accelerator:

  * one jitted ``lax.scan`` over the ``rebuild_every``-step segment between
    neighbor-list rebuilds, with the (pos, vel, force) carry donated so XLA
    reuses the state buffers in place;
  * thermo (PE/KE) accumulated on device into fixed-size ``(seg_len,)``
    arrays — ONE device->host sync per segment instead of per step;
  * neighbor overflow flags checked once per segment boundary, with a
    capacity-escalation retry (the fault-tolerance policy for density
    fluctuations): capacities grow geometrically and the list is rebuilt
    from the same — still valid — positions. The descriptor normalization
    is pinned to the model's native ``cfg.nsel`` via ``nsel_norm`` so
    escalated capacities change padding, never physics.

Both the single-process driver (``md/driver.py``) and the distributed slab
driver (``md/domain.py`` + ``launch/md_run.py``) run their inner loops
through :class:`SegmentEngine`, so halo-exchange/migration cadence aligns
with segment boundaries by construction.

The scanned step bodies are generic over the composable simulation API
(``md/api.py``): :func:`make_md_step` closes over a ``(potential, ensemble,
barostat)`` triple, and the engine caches key on those (hashable) adapters —
the legacy ``make_vv_step``/``vv_*_engine`` names remain as DP+NVE shims.
The simulation BOX rides in the scan carry (not the closure): a barostat
rescales it inside the scanned program, the per-step thermo streams the
stress tensor/pressure/volume next to pe/ke, and the neighbor search takes
the box as a traced argument over a static cell grid (``GRID_INVALID``
flags a box that outgrew its grid).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import DPConfig
from repro.md import api, integrator, neighbors


#: Name scope of the integration ops of a step (kicks, drift, thermostat
#: finalize, kinetic energy, stress, barostat): everything in the step but
#: the force evaluation, whose layers the potential names itself.
INTEGRATE_SCOPE = "md.integrate"


def default_donate() -> bool:
    """Donation saves the carry copy on gpu/tpu; the cpu backend only warns."""
    return jax.default_backend() != "cpu"


def segment_schedule(steps: int, rebuild_every: int) -> List[int]:
    """Split ``steps`` into scan-segment lengths at neighbor-rebuild cadence.

    Full ``rebuild_every``-length segments followed by one trailing partial
    segment; rebuild (and, distributed, migration) happens between entries.
    """
    if steps < 0 or rebuild_every <= 0:
        raise ValueError(f"bad schedule: steps={steps} rebuild={rebuild_every}")
    sched = [rebuild_every] * (steps // rebuild_every)
    if steps % rebuild_every:
        sched.append(steps % rebuild_every)
    return sched


def scan_segment(step_fn: Callable, carry: Any, n_steps: int, *aux: Any):
    """``lax.scan`` of ``step_fn(carry, *aux) -> (carry, per_step_out)``.

    The shared inner loop of both drivers — call inside a jit context; the
    per-step outputs come back stacked with a leading ``(n_steps,)`` dim.
    """

    def body(c, _):
        return step_fn(c, *aux)

    return jax.lax.scan(body, carry, None, length=n_steps)


class SegmentEngine:
    """One jitted dispatch per segment, carry buffers donated.

    ``step_fn(carry, *aux) -> (carry, per_step_out)`` is scanned for
    ``n_steps``; jits are cached per segment length (a run has at most two:
    the full segment and the trailing partial one).
    """

    def __init__(self, step_fn: Callable, donate: Optional[bool] = None):
        self._step_fn = step_fn
        self._donate = default_donate() if donate is None else donate
        self._jits: Dict[int, Any] = {}

    def run(self, carry: Any, n_steps: int, *aux: Any):
        fn = self._jits.get(n_steps)
        if fn is None:
            seg = functools.partial(scan_segment, self._step_fn)

            def run_n(carry, *aux, _seg=seg, _n=n_steps):
                return _seg(carry, _n, *aux)

            fn = jax.jit(run_n, donate_argnums=(0,) if self._donate else ())
            self._jits[n_steps] = fn
        return fn(carry, *aux)


# ------------------------------------------------- capacity escalation policy

@dataclasses.dataclass(frozen=True)
class EscalationPolicy:
    """Geometric capacity growth on neighbor overflow (checked per segment)."""
    growth: float = 1.6
    max_attempts: int = 6
    round_to: int = 8

    def grow(self, n: int, scale: float = 1.0) -> int:
        """Grow ``n`` by ``max(growth, scale)``.

        ``scale`` folds an external density factor into the capacity
        decision — the launch-volume / carried-volume ratio under a
        barostat squeeze — so a replay jumps straight to a capacity that
        holds the CURRENT density instead of creeping up by ``growth`` per
        retry (a box compressed 2x in volume doubles every per-region
        density at once).
        """
        factor = max(self.growth, float(scale))
        n_new = max(int(n * factor), n + 1)
        return -(-n_new // self.round_to) * self.round_to

    @staticmethod
    def volume_scale(box_ref, box_now) -> float:
        """Launch-volume / current-volume, clamped >= 1 (grow-only)."""
        v0 = float(np.prod(np.asarray(box_ref, float).reshape(-1)))
        v1 = float(np.prod(np.asarray(box_now, float).reshape(-1)))
        return max(v0 / max(v1, 1e-30), 1.0)


class NeighborBuild(NamedTuple):
    nlist: jax.Array
    cfg_run: DPConfig             # cfg with sel matching the nlist layout
    spec: neighbors.NeighborSpec  # possibly escalated
    escalations: int
    overflow: int = 0             # worst flag seen across build attempts
    #                               (> 0 iff escalation fired; <= 0: slack)


@functools.lru_cache(maxsize=None)
def _cell_list_fn(spec: neighbors.NeighborSpec,
                  box_key: Tuple[float, ...]):
    """Cached jitted neighbor fn per (spec, box) — rebuilds reuse the jit."""
    return neighbors.make_cell_list_fn(spec, np.asarray(box_key, float))


@functools.lru_cache(maxsize=None)
def _dyn_cell_list_fn(spec: neighbors.NeighborSpec,
                      ncell_key: Tuple[int, ...]):
    """Cached jitted DYNAMIC-box neighbor fn, keyed by the static cell GRID.

    The box rides in as a traced argument, so a barostat moving the box
    does NOT recompile the search — only a box change large enough to alter
    the cell counts (``floor(box / rcut_nbr)``) keys a new program. The
    reference box is ``(k + 0.5) * rcut_nbr``: ``k * rcut_nbr`` can floor
    back to ``k - 1`` in float, silently building a different grid than
    the key claims (and a key of 3 would flip to the brute-force path).
    """
    ref_box = (np.asarray(ncell_key, float) + 0.5) * spec.rcut_nbr
    return neighbors.make_cell_list_fn(spec, ref_box, dynamic_box=True)


def grid_key_for(spec: neighbors.NeighborSpec,
                 box: np.ndarray) -> Tuple[int, ...]:
    """The static cell-grid signature of ``box`` (see ``_dyn_cell_list_fn``)."""
    return tuple(int(n) for n in np.maximum(
        np.floor(np.asarray(box, float) / spec.rcut_nbr).astype(int), 1))


def build_neighbors_escalating(
    cfg: DPConfig, spec: neighbors.NeighborSpec, box: np.ndarray,
    pos: jax.Array, typ: jax.Array,
    policy: Optional[EscalationPolicy] = None,
    dynamic_box: bool = False,
    ref_box: Optional[np.ndarray] = None,
) -> NeighborBuild:
    """Build the neighbor list; on overflow escalate capacities and retry.

    This is the ONE host sync per segment: the overflow flag of the fresh
    list decides escalation. Escalation grows every type-section capacity
    and the cell-bin capacity, then rebuilds from the same positions — the
    positions are valid, only the static capacities were too small. The
    returned ``cfg_run`` carries the escalated ``sel`` so the model sees the
    matching slot layout; callers must evaluate it with
    ``nsel_norm=cfg.nsel`` to keep the trained descriptor normalization.

    ``dynamic_box=True`` routes through the dynamic-box search (the grid is
    re-derived from the CURRENT ``box`` on every call, so the grid is valid
    by construction and only an actual cell-count change recompiles) — the
    form the drivers use now that the box rides in the scan carry.
    ``ref_box`` (the LAUNCH box) folds the carried-box volume ratio into
    the first escalation: a barostat-compressed box raises every density
    at once, so the capacity jump matches it instead of creeping.
    """
    policy = policy or EscalationPolicy()
    box_np = np.asarray(box, float).reshape(-1)
    scale = (policy.volume_scale(ref_box, box_np)
             if ref_box is not None else 1.0)
    escalations = 0
    worst = None
    for _ in range(policy.max_attempts):
        if dynamic_box:
            fn = _dyn_cell_list_fn(spec, grid_key_for(spec, box_np))
            nlist, ovf = fn(pos, typ, jnp.asarray(box_np, jnp.float32))
        else:
            nlist, ovf = _cell_list_fn(spec, tuple(box_np))(pos, typ)
        worst = int(ovf) if worst is None else max(worst, int(ovf))
        if int(ovf) <= 0:
            cfg_run = (cfg if tuple(spec.sel) == tuple(cfg.sel)
                       else dataclasses.replace(cfg, sel=tuple(spec.sel)))
            return NeighborBuild(nlist, cfg_run, spec, escalations, worst)
        spec = dataclasses.replace(
            spec,
            sel=tuple(policy.grow(s, scale) for s in spec.sel),
            cell_capacity=policy.grow(spec.cell_capacity, scale))
        scale = 1.0     # the density jump is folded in once
        escalations += 1
    raise RuntimeError(
        f"neighbor capacity overflow persists after {policy.max_attempts} "
        f"escalations (last spec: sel={spec.sel}, "
        f"cell_capacity={spec.cell_capacity})")


# --------------------------------------- single-process MD-step segment fn

class MDCarry(NamedTuple):
    """Donated scan carry of the single-process MD segment.

    ``ens`` is the ensemble's extra state (RNG key, ...); stateless
    ensembles carry an empty pytree, which adds zero ops to the program.
    ``box`` is the DYNAMIC simulation box: it rides in the carry (not the
    closure) so a barostat can move it inside the scanned program; ``baro``
    is the barostat's extra state (RNG key for stochastic cell rescale).
    """
    pos: jax.Array     # (N, 3) A
    vel: jax.Array     # (N, 3) A/fs
    force: jax.Array   # (N, 3) eV/A
    ens: Any = ()      # ensemble state pytree
    box: Any = None    # (3,) A dynamic box (None: legacy fixed-box callers)
    baro: Any = ()     # barostat state pytree


#: Legacy name (pre composable-API); ``ens`` defaults keep 3-arg calls valid.
VVCarry = MDCarry


def make_md_step(potential: api.Potential, ensemble: api.Ensemble,
                 barostat: Optional[api.Barostat] = None) -> Callable:
    """One kick-drift-(force)-kick step of ``ensemble`` under ``potential``.

    ``(MDCarry, params, nlist, typ, masses, dt) -> (MDCarry, thermo)`` —
    the scanned body shared by :func:`md_segment_engine` (inner loop only)
    and :func:`md_outer_engine` (whole-trajectory two-level scan). The box
    comes from the CARRY: after the thermostat finalize the ``barostat``
    (if any) turns the instantaneous stress into an affine box + position
    rescale that the next step sees. Per-step thermo streams pe/ke plus the
    pressure observables (stress tensor (3, 3) eV/A^3, scalar pressure,
    volume) — the virial every potential already computes, promoted from
    computed-and-dropped to a stacked on-device observable. For NVE the
    thermostat finalize is the identity and ``barostat=None`` adds no box
    update ops, so trajectories stay bit-exact with the fixed-box step."""

    def md_step(carry: MDCarry, params, nlist, typ, masses, dt):
        pos, vel, f, ens, box, baro = carry
        with jax.named_scope(INTEGRATE_SCOPE):
            vel = ensemble.half_kick(vel, f, masses, dt)
            pos = ensemble.drift(pos, vel, dt, box)
        e, f_new, stats = potential.energy_forces(params, pos, typ, nlist,
                                                  box=box)
        with jax.named_scope(INTEGRATE_SCOPE):
            vel = ensemble.half_kick(vel, f_new, masses, dt)
            vel, ens = ensemble.finalize(vel, masses, dt, ens)
            ke = integrator.kinetic_energy(vel, masses)
            vol = integrator.volume_of(box)
            stress = integrator.stress_tensor(
                integrator.kinetic_tensor(vel, masses), stats["virial"], vol)
            if barostat is not None:
                box, pos, vel, baro = barostat.apply(box, pos, vel, stress,
                                                     baro, dt)
            thermo = {"pe": e, "ke": ke, "stress": stress,
                      "press": integrator.pressure_of(stress), "vol": vol}
        return MDCarry(pos, vel, f_new, ens, box, baro), thermo

    return md_step


def make_vv_step(cfg_run: DPConfig, impl: Optional[str],
                 nsel_norm: Optional[int]) -> Callable:
    """Legacy DP + NVE step body (shim over :func:`make_md_step`)."""
    return make_md_step(api.DPPotential(cfg_run, impl, nsel_norm), api.NVE())


@functools.lru_cache(maxsize=None)
def md_segment_engine(potential: api.Potential, ensemble: api.Ensemble,
                      donate: Optional[bool] = None,
                      barostat: Optional[api.Barostat] = None
                      ) -> SegmentEngine:
    """Engine whose step is one full kick-drift-(force)-kick MD step.

    Cached per (potential, ensemble, barostat) — hashable frozen adapters —
    so repeated runs and capacity-escalation retries reuse compiled
    segments. Everything array-valued (params, nlist, masses, dt) is a
    traced aux arg; the box rides in the carry.
    """
    return SegmentEngine(make_md_step(potential, ensemble, barostat),
                         donate=donate)


def vv_segment_engine(cfg_run: DPConfig, impl: Optional[str],
                      nsel_norm: Optional[int],
                      donate: Optional[bool] = None) -> SegmentEngine:
    """Legacy DP + NVE engine (shim over :func:`md_segment_engine`)."""
    return md_segment_engine(api.DPPotential(cfg_run, impl, nsel_norm),
                             api.NVE(), donate)


# ------------------------------------------- two-level scan (outer engine)

class OuterCarry(NamedTuple):
    """Carry of the outer scan over segments.

    ``overflow`` accumulates the worst neighbor-capacity excess seen by any
    on-device rebuild in the chunk; it is the ONLY value the host inspects —
    once per chunk of segments, not per segment. ``ens`` threads the
    ensemble's extra state through the two-level scan; ``box``/``baro``
    thread the dynamic box and the barostat state, so the on-device rebuild
    searches the box the barostat actually produced (a grid-validity
    violation surfaces through ``overflow`` as ``neighbors.GRID_INVALID``).
    """
    pos: jax.Array       # (N, 3) A
    vel: jax.Array       # (N, 3) A/fs
    force: jax.Array     # (N, 3) eV/A
    overflow: jax.Array  # () int32
    ens: Any = ()        # ensemble state pytree
    box: Any = None      # (3,) A dynamic box
    baro: Any = ()       # barostat state pytree


class OuterEngine:
    """Whole-trajectory on-device MD: ``lax.scan`` over rebuild segments.

    ``seg_fn(carry, seg_len, *aux) -> (carry, seg_out)`` runs ONE segment
    (neighbor rebuild at current positions + ``seg_len`` integration steps,
    all traced). :meth:`run` scans it over ``n_segments`` segments in a
    single jitted dispatch — host round-trips drop from one per segment to
    one per *chunk* of segments. Jits are cached per
    ``(n_segments, seg_len)``.
    """

    def __init__(self, seg_fn: Callable, donate: Optional[bool] = None):
        self._seg_fn = seg_fn
        self._donate = default_donate() if donate is None else donate
        self._jits: Dict[Tuple[int, int], Any] = {}

    def jitted(self, n_segments: int, seg_len: int):
        """The jitted ``(carry, *aux) -> (carry, seg_out)`` chunk program
        (what :meth:`run` calls; ``.lower`` it to compile ahead of time)."""
        key = (n_segments, seg_len)
        fn = self._jits.get(key)
        if fn is None:
            def run_chunk(carry, *aux, _n=n_segments, _len=seg_len):
                def body(c, _):
                    return self._seg_fn(c, _len, *aux)
                return jax.lax.scan(body, carry, None, length=_n)

            fn = jax.jit(run_chunk,
                         donate_argnums=(0,) if self._donate else ())
            self._jits[key] = fn
        return fn

    def run(self, carry: Any, n_segments: int, seg_len: int, *aux: Any):
        """Returns (carry, seg_out stacked with leading (n_segments,))."""
        return self.jitted(n_segments, seg_len)(carry, *aux)


@functools.lru_cache(maxsize=None)
def md_outer_engine(potential: api.Potential, ensemble: api.Ensemble,
                    spec: neighbors.NeighborSpec,
                    grid_key: Tuple[int, ...],
                    donate: Optional[bool] = None,
                    barostat: Optional[api.Barostat] = None) -> OuterEngine:
    """Outer engine for the single-process driver.

    Each scanned segment rebuilds the neighbor list ON DEVICE at the
    segment-start positions AND the segment-start box from the carry
    (static-shape sort-based binning with a static grid of ``grid_key``
    cell counts — keying the cache on COUNTS, not raw box floats, so a
    barostat-moved box reuses the compiled engine until the counts actually
    change — traced cell sizes: the same cell-list code the host path
    jits, embedded in the trace) and then runs ``seg_len`` MD steps against
    it. Capacity overflow cannot branch inside the trace; it accumulates in
    the carry and the driver checks it once per chunk, retrying the whole
    chunk from a snapshot with geometrically escalated capacities
    (``potential.sel`` == ``spec.sel`` and the potential's pinned
    normalization keep the physics fixed, so escalation changes padding
    only). A barostat-shrunk box that invalidates the static grid raises
    the ``GRID_INVALID`` sentinel through the same flag; the driver then
    re-derives the grid from the snapshot box instead of growing
    capacities. The ensemble and barostat state thread through both scan
    levels in the carry. Each segment's output adds ``nbr_live``, the live
    entries of its list (``nlist >= 0``), which the driver sums into
    ``MDResult.nbr_live_slots``.
    """
    # (k + 0.5) * rcut floors back to exactly k cells (k * rcut can lose a
    # cell to float rounding — see _dyn_cell_list_fn)
    ref_box = (np.asarray(grid_key, float) + 0.5) * spec.rcut_nbr
    nbr_fn = neighbors.make_cell_list_fn(spec, ref_box, jit=False,
                                         dynamic_box=True)
    md_step = make_md_step(potential, ensemble, barostat)

    def outer_seg(carry: OuterCarry, seg_len: int, params, typ, masses, dt):
        nlist, ovf = nbr_fn(carry.pos, typ, carry.box)
        with jax.named_scope(neighbors.SCOPE):
            overflow = jnp.maximum(carry.overflow, ovf)
            live = jnp.sum(nlist >= 0, dtype=jnp.int32)
        inner = MDCarry(carry.pos, carry.vel, carry.force, carry.ens,
                        carry.box, carry.baro)
        inner, th = scan_segment(md_step, inner, seg_len,
                                 params, nlist, typ, masses, dt)
        return OuterCarry(inner.pos, inner.vel, inner.force, overflow,
                          inner.ens, inner.box, inner.baro), \
            {**th, "nbr_live": live}

    return OuterEngine(outer_seg, donate=donate)


def vv_outer_engine(cfg_run: DPConfig, impl: Optional[str],
                    nsel_norm: Optional[int],
                    spec: neighbors.NeighborSpec,
                    box_key: Tuple[float, ...],
                    donate: Optional[bool] = None) -> OuterEngine:
    """Legacy DP + NVE outer engine (shim over :func:`md_outer_engine`)."""
    return md_outer_engine(api.DPPotential(cfg_run, impl, nsel_norm),
                           api.NVE(), spec,
                           grid_key_for(spec, np.asarray(box_key, float)),
                           donate)


def box_lengths(box) -> np.ndarray:
    """Host-side (3,) orthorhombic edge lengths from a box spelling.

    Accepts a length-3 vector or a DIAGONAL (3, 3) matrix; anything else
    (triclinic cells, wrong sizes) raises instead of silently truncating —
    a zero edge would turn into inf pressure and NaN min-images downstream.
    """
    a = np.asarray(box, np.float64).reshape(-1)
    if a.size == 9:
        m = a.reshape(3, 3)
        if np.any(m != np.diag(np.diag(m))):
            raise ValueError(f"non-orthorhombic box not supported: {m}")
        a = np.diag(m)
    if a.size != 3:
        raise ValueError(f"box must be (3,) edge lengths or a diagonal "
                         f"(3, 3) matrix, got shape {np.shape(box)}")
    return a


def pack_box(box) -> jnp.ndarray:
    """The (3,) float32 dynamic-box carry entry from a host box spelling."""
    return jnp.asarray(box_lengths(box).astype(np.float32))


def chunk_schedule(steps: int, rebuild_every: int,
                   chunk_segments: int) -> List[Tuple[int, int]]:
    """Group the segment schedule into outer-scan dispatches.

    Returns ``[(n_segments, seg_len), ...]``: full ``rebuild_every``-length
    segments grouped ``chunk_segments`` at a time, then the trailing partial
    segment (if any) as its own ``(1, remainder)`` dispatch. One host sync
    per entry.
    """
    if chunk_segments <= 0:
        raise ValueError(f"chunk_segments={chunk_segments}")
    if steps < 0 or rebuild_every <= 0:
        raise ValueError(f"bad schedule: steps={steps} rebuild={rebuild_every}")
    full, rem = divmod(steps, rebuild_every)
    out: List[Tuple[int, int]] = []
    while full > 0:
        take = min(chunk_segments, full)
        out.append((take, rebuild_every))
        full -= take
    if rem:
        out.append((1, rem))
    return out


def thermo_rows(pe: np.ndarray, ke: np.ndarray, step_base: int, steps: int,
                thermo_every: int, n_atoms: int,
                press: Optional[np.ndarray] = None,
                vol: Optional[np.ndarray] = None) -> List[Dict[str, float]]:
    """Host-side selection of thermo rows from a segment's stacked PE/KE.

    Matches the seed cadence: every ``thermo_every`` global steps plus the
    final step. Temperature follows from KE and 3N degrees of freedom; when
    the stacked pressure/volume observables are given, each row gains
    ``press_gpa`` (instantaneous pressure, GPa) and ``vol`` (A^3) columns.
    """
    rows = []
    ndof = 3.0 * max(n_atoms, 1)
    for i in range(len(pe)):
        gstep = step_base + i + 1
        if gstep % thermo_every == 0 or gstep == steps:
            row = {
                "step": gstep, "pe": float(pe[i]), "ke": float(ke[i]),
                "etot": float(pe[i]) + float(ke[i]),
                "temp": 2.0 * float(ke[i]) / (ndof * integrator.KB_EV),
            }
            if press is not None:
                row["press_gpa"] = float(press[i]) * integrator.EV_A3_TO_GPA
            if vol is not None:
                row["vol"] = float(vol[i])
            rows.append(row)
    return rows
