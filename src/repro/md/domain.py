"""Distributed MD: N-D brick domain decomposition + halo exchange + migration.

This is the paper's parallelization (Sec. 3.3, 3.5.4) in JAX-native form:

  * N-D Cartesian brick decomposition over the ``spatial`` mesh axis behind
    the :class:`repro.md.topology.Topology` abstraction: a shape like
    ``(4,)``, ``(2, 4)`` or ``(2, 2, 2)`` maps the flat spatial rank to a
    brick coordinate (the paper's 3-D sub-region layout; its 100M-atom
    predecessor details the same ghost-region scheme). A ``(k,)`` topology
    degenerates to the legacy 1-D x-slab layout — same ring, same packs,
    same op order — so the slab protocol pins the general machinery.
    Each brick holds a fixed-capacity, mask-padded atom array — static
    shapes shard and jit.
  * Halo (ghost) exchange as STAGED PER-AXIS SWEEPS (x, then y, then z):
    each sweep packs boundary layers from owned atoms PLUS the ghosts of
    earlier sweeps and exchanges them with the +/- neighbor along that axis
    via per-axis ``lax.ppermute`` rings. Edge and corner ghosts ride
    through two/three axis-aligned exchanges instead of 26 explicit
    neighbor sends — the standard staged-sweep trick. Capacity-bounded with
    overflow flags.
  * Force evaluation computes contributions on ghosts too; ghost forces are
    sent BACK owner-ward by running the sweeps IN REVERSE (z, then y, then
    x) — each reverse sweep returns that axis's ghost forces to the rank
    that packed them, scatter-adding into owned slots AND earlier-axis
    ghost slots, so a corner ghost's force hops home through the same two/
    three exchanges its coordinates came from (the LAMMPS "reverse
    communication" pattern, hand-written rather than autodiffed through
    collectives).
  * The ``model`` mesh axis decomposes the NEIGHBOR dimension of the DP
    descriptor: each model shard evaluates the embedding of a slice of every
    atom's neighbor list; the 4 x M T-matrices are ``psum``-reduced. This is
    the MD analogue of tensor parallelism — the embedding net (95% of FLOPs)
    splits 16-way without touching the spatial layout.
  * Atom migration between bricks runs at neighbor-rebuild cadence as the
    same staged per-axis sweeps (split along x -> exchange -> merge, then
    y, then z): a corner-crossing migrant is routed to its destination
    brick by two/three axis-aligned hops. Capacity-bounded ppermute sends;
    overflow is reported PER AXIS, never silently dropped.

"One MPI per NUMA domain, one TF graph per rank" becomes "one SPMD program
per chip": granularity taken to its limit (DESIGN.md Sec. 3).
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.types import DPConfig
from repro.md import api, integrator, neighbors
from repro.md.topology import Topology


@dataclasses.dataclass(frozen=True)
class DomainSpec:
    box: Tuple[float, float, float]      # global orthorhombic box (A)
    n_slabs: int                          # spatial axis size (= prod(topology))
    atom_capacity: int                    # max owned atoms per brick
    halo_capacity: int                    # max ghost atoms per side per sweep
    rcut_halo: float                      # rcut + skin
    #: brick counts per decomposed axis; ``None`` -> the legacy 1-D
    #: ``(n_slabs,)`` x-slab layout (bit-compatible degenerate case)
    topology: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        shape = tuple(int(s) for s in (self.topology
                                       if self.topology is not None
                                       else (self.n_slabs,)))
        object.__setattr__(self, "topology", shape)
        Topology(shape)                        # validates the shape itself
        assert math.prod(shape) == self.n_slabs, (
            f"topology {shape} has {math.prod(shape)} bricks but "
            f"n_slabs={self.n_slabs}")

    @classmethod
    def for_topology(cls, box, topology, atom_capacity, halo_capacity,
                     rcut_halo) -> "DomainSpec":
        """Topology-first constructor: ``n_slabs`` derived from the shape."""
        topo = Topology.parse(topology)
        return cls(box=tuple(box), n_slabs=topo.n_ranks,
                   atom_capacity=atom_capacity, halo_capacity=halo_capacity,
                   rcut_halo=rcut_halo, topology=topo.shape)

    @property
    def topo(self) -> Topology:
        return Topology(self.topology)

    @property
    def slab_width(self) -> float:
        """Legacy spelling: the brick width along x."""
        return self.box[0] / self.topology[0]

    @property
    def brick_widths(self) -> Tuple[float, ...]:
        """Launch-time brick width per DECOMPOSED axis."""
        return tuple(self.box[a] / s for a, s in enumerate(self.topology))

    def validate(self) -> None:
        for a, (w, s) in enumerate(zip(self.brick_widths, self.topology)):
            assert w >= self.rcut_halo, (
                f"brick width box[{a}]/{s} = {w:.2f} < halo cutoff "
                f"{self.rcut_halo:.2f}: the decomposition needs "
                f"box[a]/shape[a] >= rcut_halo on every decomposed axis "
                f"(use fewer bricks along axis {a})")
        assert self.n_slabs >= 2, (
            "brick decomposition assumes >= 2 bricks (ghost images must not "
            "alias their owners); use md/driver.py for single-domain runs")


class SlabState(NamedTuple):
    """Per-brick padded state; leading dim = n_slabs when global."""
    pos: jax.Array        # (cap, 3)
    vel: jax.Array        # (cap, 3)
    typ: jax.Array        # (cap,) int32
    mask: jax.Array       # (cap,) bool — owned-atom validity


def partition_atoms(pos: np.ndarray, vel: np.ndarray, typ: np.ndarray,
                    spec: DomainSpec,
                    box: Optional[np.ndarray] = None
                    ) -> Tuple[SlabState, int]:
    """Host-side initial partition -> stacked (n_slabs, cap, ...) arrays.

    ``box`` overrides the launch-time geometry (a barostat-moved carried
    box changes every brick width) — repartitioning after a capacity
    escalation must bin by the box the atoms actually live in.
    """
    topo = spec.topo
    box_np = np.asarray(box if box is not None else spec.box, float)
    rank = np.zeros(len(pos), np.int64)
    for a in topo.axes:
        w = box_np[a] / topo.shape[a]
        # clamp BOTH ends: a slightly-negative coordinate (an atom that
        # drifted past a face since the last migration) must bin to brick
        # 0, never to a nonexistent negative rank (silent atom loss)
        c = np.clip((pos[:, a] / w).astype(np.int64), 0, topo.shape[a] - 1)
        rank += c * topo.strides[a]
    cap = spec.atom_capacity
    out_pos = np.zeros((spec.n_slabs, cap, 3), np.float32)
    out_vel = np.zeros((spec.n_slabs, cap, 3), np.float32)
    out_typ = np.zeros((spec.n_slabs, cap), np.int32)
    out_mask = np.zeros((spec.n_slabs, cap), bool)
    overflow = 0
    for s in range(spec.n_slabs):
        idx = np.nonzero(rank == s)[0]
        n = len(idx)
        overflow = max(overflow, n - cap)
        idx = idx[:cap]
        out_pos[s, :len(idx)] = pos[idx]
        out_vel[s, :len(idx)] = vel[idx]
        out_typ[s, :len(idx)] = typ[idx]
        out_mask[s, :len(idx)] = True
    return SlabState(pos=jnp.asarray(out_pos), vel=jnp.asarray(out_vel),
                     typ=jnp.asarray(out_typ), mask=jnp.asarray(out_mask)), overflow


def gather_atoms(state: SlabState) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side inverse of :func:`partition_atoms`: live atoms, flat."""
    pos = np.asarray(state.pos).reshape(-1, 3)
    vel = np.asarray(state.vel).reshape(-1, 3)
    typ = np.asarray(state.typ).reshape(-1)
    mask = np.asarray(state.mask).reshape(-1)
    return pos[mask], vel[mask], typ[mask]


def capacity_scale_for_box(spec: DomainSpec, box_now) -> float:
    """Launch-volume / current-volume, clamped >= 1.

    The density rise a barostat-compressed box implies: every per-brick
    capacity (owned atoms, halo shell, migration packets) must scale with
    it — growing ``sel`` alone leaves the brick arrays too small. Thin
    spec-level spelling of :meth:`EscalationPolicy.volume_scale` (one
    implementation of the clamp semantics).
    """
    from repro.md import stepper
    return stepper.EscalationPolicy.volume_scale(spec.box, box_now)


def escalate_capacities(spec: DomainSpec, policy, box_now=None,
                        n_model: int = 1) -> DomainSpec:
    """Grow DomainSpec capacities on overflow, folding the carried box in.

    ``policy`` is a :class:`repro.md.stepper.EscalationPolicy`; the growth
    factor is ``max(policy.growth, V_launch / V_now)`` so a replay after a
    barostat squeeze jumps straight to a capacity that holds the CURRENT
    density instead of creeping up by ``policy.growth`` per retry.
    ``atom_capacity`` stays divisible by ``n_model`` (the atoms-decomp
    layout constraint). The returned spec is REBASED onto ``box_now``: the
    launch box is also the reference the static cell grids derive from, so
    a replay against a squeezed carried box must re-derive them (and the
    next volume-scale comparison) from the box the atoms actually live in.
    """
    scale = 1.0 if box_now is None else capacity_scale_for_box(spec, box_now)
    atom = policy.grow(spec.atom_capacity, scale)
    atom = -(-atom // n_model) * n_model
    halo = policy.grow(spec.halo_capacity, scale)
    new_box = (spec.box if box_now is None
               else tuple(float(b) for b in np.asarray(box_now).reshape(-1)))
    return dataclasses.replace(spec, box=new_box, atom_capacity=atom,
                               halo_capacity=halo)


def repartition_state(state: SlabState, spec_new: DomainSpec,
                      box_now=None) -> Tuple[SlabState, int]:
    """Host-side re-partition into (escalated) ``spec_new`` capacities.

    Bins by ``box_now`` when the carried box moved — the replay path after
    a capacity overflow under a barostat squeeze.
    """
    pos, vel, typ = gather_atoms(state)
    return partition_atoms(pos, vel, typ, spec_new, box=box_now)


def pad_sel_for(cfg: DPConfig, n_shards: int) -> DPConfig:
    """Pad each neighbor-type section to a model-axis-divisible size."""
    sel = tuple(-(-s // n_shards) * n_shards for s in cfg.sel)
    return dataclasses.replace(cfg, sel=sel)


def _flat_rank(spatial_axis):
    """Flat spatial rank inside shard_map; handles a tuple of mesh axes
    (multi-pod meshes flatten (pod, data) in C order)."""
    if isinstance(spatial_axis, str):
        return jax.lax.axis_index(spatial_axis)
    idx = jax.lax.axis_index(spatial_axis[0])
    for a in spatial_axis[1:]:
        idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    return idx


# --------------------------------------------------------------- halo pieces

def _pack_boundary(pos, typ, mask, lo_side: bool, spec: DomainSpec,
                   face_lo: jax.Array, width=None, dim: int = 0):
    """Select atoms within rcut of a brick face (along axis ``dim``) into a
    fixed buffer.

    ``width`` may be a TRACED value derived from the carried box (the
    barostat moves the box, the brick faces move with it); ``None`` keeps
    the launch-time geometry. The caller may pass ghosts of earlier sweeps
    in ``pos``/``mask`` too — that is what routes edge/corner ghosts
    through the staged axis sweeps."""
    if width is None:
        width = spec.brick_widths[dim]
    x_rel = pos[:, dim] - face_lo
    if lo_side:
        sel = mask & (x_rel < spec.rcut_halo)
    else:
        sel = mask & (x_rel > width - spec.rcut_halo)
    # stable-compact selected atoms to the buffer front
    order = jnp.argsort(jnp.where(sel, 0, 1), stable=True)
    hc = spec.halo_capacity
    idx = order[:hc]
    valid = sel[idx]
    overflow = jnp.sum(sel) - jnp.sum(valid)
    buf_pos = jnp.where(valid[:, None], pos[idx], 0.0)
    buf_typ = jnp.where(valid, typ[idx], 0)
    return buf_pos, buf_typ, valid, idx, overflow


def _halo_sweep(pos, typ, mask, spec: DomainSpec, dim: int, coord_d,
                n_d: int, box_d, width_d, face_lo, axis,
                plus_pairs, minus_pairs):
    """ONE staged halo sweep: ghost atoms from both axis-``dim`` neighbors.

    ``pos``/``typ``/``mask`` are owned atoms plus the ghosts of EARLIER
    sweeps (that inclusion is what delivers edge/corner ghosts in two/three
    hops). Returns (ghost_pos (2*hc, 3) shifted into this brick's frame,
    ghost_typ, ghost_mask, reverse-comm bookkeeping, overflow).
    ``box_d``/``width_d`` carry the DYNAMIC geometry when the box rides in
    the scan carry.
    """
    lo_pos, lo_typ, lo_valid, lo_idx, ovf_l = _pack_boundary(
        pos, typ, mask, True, spec, face_lo, width_d, dim)
    hi_pos, hi_typ, hi_valid, hi_idx, ovf_r = _pack_boundary(
        pos, typ, mask, False, spec, face_lo, width_d, dim)

    # my low boundary -> minus neighbor's ghosts; high -> plus neighbor
    from_plus = jax.tree.map(
        lambda t: jax.lax.ppermute(t, axis, minus_pairs),
        (lo_pos, lo_typ, lo_valid))
    from_minus = jax.tree.map(
        lambda t: jax.lax.ppermute(t, axis, plus_pairs),
        (hi_pos, hi_typ, hi_valid))

    # shift ghosts into this brick's coordinate frame (periodic along dim)
    fl_pos, fl_typ, fl_valid = from_minus
    fr_pos, fr_typ, fr_valid = from_plus
    fl_shift = jnp.where(coord_d == 0, -box_d, 0.0)      # wrap from brick n-1
    fr_shift = jnp.where(coord_d == n_d - 1, box_d, 0.0)  # wrap from brick 0
    fl_pos = fl_pos.at[:, dim].add(fl_shift)
    fr_pos = fr_pos.at[:, dim].add(fr_shift)

    ghost_pos = jnp.concatenate([fl_pos, fr_pos], axis=0)
    ghost_typ = jnp.concatenate([fl_typ, fr_typ], axis=0)
    ghost_mask = jnp.concatenate([fl_valid, fr_valid], axis=0)
    book = {"lo_idx": lo_idx, "lo_valid": lo_valid,
            "hi_idx": hi_idx, "hi_valid": hi_valid}
    return ghost_pos, ghost_typ, ghost_mask, book, jnp.maximum(ovf_l, ovf_r)


def _reverse_sweep(f_prefix, ghost_force, book, axis, plus_pairs,
                   minus_pairs):
    """Return ONE axis's ghost-force segment to the ranks that packed it.

    Slot order is preserved end-to-end: my hi-boundary pack became the plus
    neighbor's from_minus ghost buffer, so the returned buffer indexes
    straight back through hi_idx (and symmetrically for lo). The scatter
    targets land in owned slots AND earlier-axis ghost slots — running the
    sweeps in reverse is what hops a corner ghost's force home.
    """
    hc = ghost_force.shape[0] // 2
    f_from_minus = ghost_force[:hc]     # ghosts owned minus-ward of me
    f_from_plus = ghost_force[hc:]      # ghosts owned plus-ward of me
    # ppermute(x, [(i, j)]) delivers x_i to j: send owner-ward.
    recv_hi = jax.lax.ppermute(f_from_minus, axis, minus_pairs)
    recv_lo = jax.lax.ppermute(f_from_plus, axis, plus_pairs)
    contrib = jnp.zeros_like(f_prefix)
    contrib = contrib.at[book["hi_idx"]].add(
        recv_hi * book["hi_valid"][:, None])
    contrib = contrib.at[book["lo_idx"]].add(
        recv_lo * book["lo_valid"][:, None])
    return f_prefix + contrib


# ------------------------------------------------------ neighbor list (brick)

def _slab_neighbors(pos_all, typ_all, mask_all, cfg: DPConfig, rc2: float,
                    n_local: int, box):
    """Brute-force type-sectioned neighbor list for local atoms vs all atoms.

    O(cap * (cap + ghosts)) — the brick-local cost; cell lists drop in here
    for production sizes (the dry-run path uses this exact function with
    ShapeDtypeStructs, so the compile proof covers it). Undecomposed axes
    are periodic via min-image (decomposed axes are ghost-resolved; the
    caller passes 1e30 there so min-image no-ops)."""
    rij = pos_all[None, :, :] - pos_all[:n_local, None, :]
    rij = rij - box * jnp.round(rij / box)
    d2 = jnp.sum(rij * rij, axis=-1)
    n_all = pos_all.shape[0]
    cand = jnp.broadcast_to(jnp.arange(n_all, dtype=jnp.int32)[None, :],
                            (n_local, n_all))
    self_mask = cand == jnp.arange(n_local, dtype=jnp.int32)[:, None]
    valid = (~self_mask) & mask_all[None, :] & mask_all[:n_local, None] \
        & (d2 < rc2)
    return neighbors.pack_type_sections(cand, valid, typ_all[cand.clip(0)],
                                        cfg.sel)


# ---------------------------------------------------------------- the MD step

def make_local_md_step(cfg: DPConfig, spec: DomainSpec, mesh: Mesh,
                       masses: Tuple[float, ...], dt_fs: float,
                       impl: Optional[str] = None,
                       spatial_axis="data",
                       model_axis: str = "model",
                       decomp: str = "slots",
                       neighbor: str = "brute",
                       potential: Optional[api.Potential] = None,
                       ensemble: Optional[api.Ensemble] = None,
                       barostat: Optional[api.Barostat] = None):
    """Per-shard MD step body — the code that runs INSIDE shard_map.

    Returns ``step_local(params, pos, vel, typ, mask, ens, box, baro) ->
    ((pos, vel, typ, mask, ens, box, baro), thermo)`` on squeezed per-brick
    arrays. Fully traceable (halo sweeps, rebuild, force, integration — no
    host branches), so it embeds equally in the per-segment engine
    (:func:`make_distributed_md_step`) and in the whole-trajectory two-level
    scan (:func:`make_outer_md_program`).

    The step is closed over a ``(potential, ensemble, barostat)`` triple
    from the composable API (``md/api.py``); ``cfg``/``impl`` remain as the
    legacy spelling for DP + NVE (``potential=None`` wraps them in a
    :class:`api.DPPotential`). The ensemble's extra state ``ens`` (RNG key,
    ...) rides in the scan carry next to the brick arrays.

    The BOX ``box`` (3,) is the dynamic, globally-replicated simulation
    box: every brick extent (per-axis width, faces, min-image wrap) is
    derived from it each step via ``spec.topo``, and a traced check that
    every rescaled brick still covers ``rcut_halo`` on every decomposed
    axis reports through ``thermo["geom_overflow"]``. Each step also
    computes the brick virial via the strain derivative ``W = -dE/d(eps)``
    of its own energy terms (one joint backward pass with the forces),
    psums it into the global stress, and — when a ``barostat`` is closed
    over — applies the affine box/position rescale identically on every
    brick (the barostat state ``baro`` is REPLICATED, so every brick draws
    the same SCR noise and the global box stays consistent).

    decomp:
      "slots" — model shards take complementary NEIGHBOR-SLOT slices of every
                atom; partial per-atom energy terms psum-reduce (for DP, the
                partial T matrices — validated vs the single-process
                reference to 1e-10).
      "atoms" — model shards take complementary ATOM slices of the brick
                (search + energy + grad end-to-end); per-shard forces
                psum-reduce. Better balanced at production sizes and keeps
                the neighbor search per-chip — the multi-pod MD dry-run path.
    neighbor: "brute" O(N^2) (tests) | "cells" O(N) brick cell list.
    """
    spec.validate()
    topo = spec.topo
    potential = potential or api.DPPotential(cfg, impl=impl)
    ensemble = ensemble or api.NVE()
    n_model = mesh.shape[model_axis]
    if isinstance(spatial_axis, str):
        n_spatial = mesh.shape[spatial_axis]
    else:
        n_spatial = 1
        for a in spatial_axis:
            n_spatial *= mesh.shape[a]
    assert n_spatial == spec.n_slabs, (n_spatial, spec.n_slabs)
    # the neighbor search only reaches rcut_halo: a potential with a larger
    # cutoff would silently lose every pair beyond it (no flag fires)
    assert potential.rcut <= spec.rcut_halo + 1e-6, (
        f"potential rcut {potential.rcut} exceeds DomainSpec.rcut_halo "
        f"{spec.rcut_halo}: pairs past the halo cutoff would be silently "
        f"dropped")
    # model-axis-divisible padded layout; normalization pinned to it (the
    # pre-API behavior: distributed DP normalizes by the PADDED capacity)
    sel_p = tuple(pad_sel_for(potential.layout_cfg(), n_model).sel)
    nsel_p = int(sum(sel_p))
    pot_p = potential.with_layout(sel_p, nsel_norm=nsel_p)
    # per-shard slice layout: each model shard sees 1/n_model of each section
    pot_local = pot_p.with_layout(tuple(s // n_model for s in sel_p),
                                  nsel_norm=nsel_p)
    cfg_layout = pot_p.layout_cfg()
    rc2 = float(spec.rcut_halo) ** 2
    mass_table = jnp.asarray(masses, jnp.float32)
    assert spec.atom_capacity % n_model == 0 or decomp == "slots"
    atom_slice = spec.atom_capacity // n_model
    n_centers = atom_slice if decomp == "atoms" else spec.atom_capacity
    # host-side per-axis ring pairs over the flat spatial rank
    plus_pairs = [topo.plus_ring(a) for a in topo.axes]
    minus_pairs = [topo.minus_ring(a) for a in topo.axes]
    nbr_fn = None
    if neighbor == "cells":
        from repro.md import slab_cells
        # densest a brick gets: its atom capacity over its launch volume
        brick_volume = float(np.prod(spec.box)) / spec.n_slabs
        nbr_fn = slab_cells.make_slab_neighbor_fn(
            cfg_layout, spec.box, spec.slab_width, spec.rcut_halo, n_centers,
            topology=spec.topology,
            max_density=spec.atom_capacity / brick_volume)

    def slot_energy(pos_all, eps, nlist_slice, typ_all, mask_local, params,
                    boxm):
        """Sum of local-atom energies from a neighbor-slot SLICE; psum over
        the model axis completes the per-atom terms (neighbor
        decomposition). ``eps`` applies an affine strain to every pair
        vector: its gradient at zero is minus this shard's virial."""
        n_local = mask_local.shape[0]
        nmask = nlist_slice >= 0
        j = jnp.maximum(nlist_slice, 0)
        rij = pos_all[j] - pos_all[:n_local, None, :]
        rij = rij - boxm * jnp.round(rij / boxm)
        rij = jnp.where(nmask[..., None], rij, 0.0)
        rij = rij + rij @ eps
        e_i = pot_local.atomic_energy(params, rij, nmask, typ_all[:n_local],
                                      axis_name=model_axis)
        return jnp.sum(e_i * mask_local)

    def atoms_energy(pos_all, eps, nlist, typ_centers, mask_centers, start,
                     params, boxm):
        """Sum of energies for an ATOM slice (full neighbor lists)."""
        nmask = nlist >= 0
        j = jnp.maximum(nlist, 0)
        centers = jax.lax.dynamic_slice_in_dim(pos_all, start, n_centers, 0)
        rij = pos_all[j] - centers[:, None, :]
        rij = rij - boxm * jnp.round(rij / boxm)
        rij = jnp.where(nmask[..., None], rij, 0.0)
        rij = rij + rij @ eps
        e_i = pot_p.atomic_energy(params, rij, nmask, typ_centers)
        return jnp.sum(e_i * mask_centers)

    def step_local(params, pos, vel, typ, mask, ens, box, baro):
        cap = pos.shape[0]
        idx_s = _flat_rank(spatial_axis)
        # per-axis brick geometry from the CARRIED box
        widths = [box[a] / float(topo.shape[a]) for a in topo.axes]
        coords = [topo.coord_along(idx_s, a) for a in topo.axes]
        faces = [coords[a].astype(jnp.float32) * widths[a]
                 for a in topo.axes]
        # min-image applies to UNDECOMPOSED axes only: decomposed-axis
        # periodicity is ghost-resolved, and a full-box wrap there would
        # alias ghost images back onto local atoms when
        # box/2 < rcut + width (1-2 brick configurations).
        boxm = jnp.stack([jnp.float32(1e30) if a < topo.ndim else box[a]
                          for a in range(3)])
        # the cutoff-vs-halo assert, traced against the CARRIED box: a
        # barostat-shrunk brick narrower than rcut_halo on ANY decomposed
        # axis silently loses pairs (ghosts only cover one neighbor brick),
        # so it must surface through the overflow-flag channel, not a
        # launch-time assert.
        geom_ovf = jnp.zeros((), jnp.int32)
        for a in topo.axes:
            geom_ovf = jnp.maximum(
                geom_ovf, (widths[a] < spec.rcut_halo).astype(jnp.int32))
        eps0 = jnp.zeros((3, 3), pos.dtype)

        # -- staged halo sweeps (x, then y, then z) -----------------------
        # each sweep packs from owned atoms + earlier sweeps' ghosts, so
        # edge/corner ghosts arrive via two/three axis-aligned exchanges
        pos_all, typ_all, mask_all = pos, typ, mask
        books = []
        h_ovf = jnp.zeros((), jnp.int32)
        for a in topo.axes:
            g_pos, g_typ, g_mask, book, ovf = _halo_sweep(
                pos_all, typ_all, mask_all, spec, a, coords[a],
                topo.shape[a], box[a], widths[a], faces[a], spatial_axis,
                plus_pairs[a], minus_pairs[a])
            books.append((pos_all.shape[0], book, a))
            pos_all = jnp.concatenate([pos_all, g_pos], axis=0)
            typ_all = jnp.concatenate([typ_all, g_typ], axis=0)
            mask_all = jnp.concatenate([mask_all, g_mask], axis=0)
            h_ovf = jnp.maximum(h_ovf, ovf)

        def reverse_comm(force_all):
            # the transpose: run the sweeps IN REVERSE (z, y, x) — each
            # hop returns that axis's ghost forces; scatter targets include
            # earlier-axis ghost slots, so corner forces hop home.
            for prefix, book, a in reversed(books):
                force_all = _reverse_sweep(
                    force_all[:prefix], force_all[prefix:], book,
                    spatial_axis, plus_pairs[a], minus_pairs[a])
            return force_all

        brick_lo3 = jnp.stack(
            [faces[a] if a < topo.ndim else jnp.float32(0.0)
             for a in range(3)])
        widths_t = tuple(widths)

        if decomp == "atoms":
            # -- model axis slices ATOMS: search + energy + grad per slice --
            start = jax.lax.axis_index(model_axis).astype(jnp.int32) * atom_slice
            if nbr_fn is not None:
                nlist, n_ovf = nbr_fn(pos_all, typ_all, mask_all, brick_lo3,
                                      start, box=box, widths=widths_t)
            else:
                nlist_full, n_ovf = _slab_neighbors(
                    pos_all, typ_all, mask_all, cfg_layout, rc2, cap, boxm)
                nlist = jax.lax.dynamic_slice_in_dim(
                    nlist_full, start, n_centers, 0)
            typ_c = jax.lax.dynamic_slice_in_dim(typ, start, n_centers, 0)
            mask_c = jax.lax.dynamic_slice_in_dim(mask, start, n_centers, 0)

            def e_fn(p_all, eps):
                return atoms_energy(p_all, eps, nlist, typ_c, mask_c, start,
                                    params, boxm)

            e_slice, (de_dpos, de_deps) = jax.value_and_grad(
                e_fn, argnums=(0, 1))(pos_all, eps0)
            # disjoint atom slices: plain psums assemble globals
            e_local = jax.lax.psum(e_slice, model_axis)
            force_all = -jax.lax.psum(de_dpos, model_axis)
            virial = -jax.lax.psum(de_deps, model_axis)
            force = reverse_comm(force_all)
        else:
            # -- model axis slices neighbor SLOTS (psum'd T matrices) -------
            if nbr_fn is not None:
                nlist, n_ovf = nbr_fn(pos_all, typ_all, mask_all, brick_lo3,
                                      0, box=box, widths=widths_t)
            else:
                nlist, n_ovf = _slab_neighbors(pos_all, typ_all, mask_all,
                                               cfg_layout, rc2, cap, boxm)
            parts = []
            for (a, b) in cfg_layout.sel_sections():
                w = (b - a) // n_model
                parts.append(jax.lax.dynamic_slice_in_dim(
                    nlist, a + jax.lax.axis_index(model_axis) * w, w, axis=1))
            nlist_slice = jnp.concatenate(parts, axis=1)

            # Grad target is e / n_model: the psum-of-T transpose sums the
            # identical cotangents of all model shards (measured n_model x
            # overcount otherwise); dividing restores per-slice exactness.
            def e_fn(p_all, eps):
                return slot_energy(p_all, eps, nlist_slice, typ_all, mask,
                                   params, boxm) / n_model

            e_frac, (de_dpos, de_deps) = jax.value_and_grad(
                e_fn, argnums=(0, 1))(pos_all, eps0)
            e_local = e_frac * n_model
            force_all = -de_dpos          # includes ghost contributions
            force = reverse_comm(force_all)
            # model axis holds complementary neighbor slices: reduce forces
            # (and this shard's slot contribution to the virial).
            force = jax.lax.psum(force, model_axis)
            virial = -jax.lax.psum(de_deps, model_axis)

        # -- ensemble step (kick-drift-kick + thermostat finalize) ----------
        m_vec = mass_table[typ]
        vel = ensemble.half_kick(vel, force, m_vec, dt_fs)
        pos = ensemble.drift(pos, vel, dt_fs, None)
        vel = ensemble.half_kick(vel, force, m_vec, dt_fs)
        vel, ens = ensemble.finalize(vel, m_vec, dt_fs, ens, amask=mask)
        # decomposed-axis bounds restore via migration; undecomposed axes
        # wrap via min-image in rij
        pos = jnp.where(mask[:, None], pos, 0.0)

        ke = 0.5 * jnp.sum(mass_table[typ] * mask * jnp.sum(vel * vel, -1)) \
            / integrator.FORCE_TO_ACC
        # -- global stress + barostat --------------------------------------
        # per-brick virial/kinetic tensors psum to the GLOBAL stress; every
        # brick computes the identical tensor, so the (replicated) barostat
        # rescale keeps box/positions consistent across the mesh.
        kin = integrator.kinetic_tensor(vel, m_vec, mask)
        vol = integrator.volume_of(box)
        stress = integrator.stress_tensor(
            jax.lax.psum(kin, spatial_axis),
            jax.lax.psum(virial, spatial_axis), vol)
        if barostat is not None:
            box, pos, vel, baro = barostat.apply(box, pos, vel, stress,
                                                 baro, dt_fs)
            pos = jnp.where(mask[:, None], pos, 0.0)

        thermo = {
            "pe": jax.lax.psum(e_local, spatial_axis),
            "ke": jax.lax.psum(ke, spatial_axis),
            "n_atoms": jax.lax.psum(jnp.sum(mask), spatial_axis),
            "halo_overflow": jax.lax.pmax(h_ovf, spatial_axis),
            "nbr_overflow": jax.lax.pmax(n_ovf, spatial_axis),
            "geom_overflow": jax.lax.pmax(geom_ovf, spatial_axis),
            "stress": stress,
            "press": integrator.pressure_of(stress),
            "vol": vol,
        }
        return (pos, vel, typ, mask, ens, box, baro), thermo

    return step_local


def _state_pspec(spatial_axis) -> SlabState:
    return SlabState(pos=P(spatial_axis), vel=P(spatial_axis),
                     typ=P(spatial_axis), mask=P(spatial_axis))


THERMO_KEYS = ("pe", "ke", "n_atoms", "halo_overflow", "nbr_overflow",
               "geom_overflow", "stress", "press", "vol")


def init_ensemble_state(ensemble: api.Ensemble, n_slabs: int, mesh: Mesh,
                        spatial_axis="data"):
    """Stacked per-brick ensemble state, device_put sharded over the bricks.

    Stateless ensembles return an empty pytree (zero overhead); stateful
    ones (Langevin) get one state per brick with the brick index folded into
    the RNG seed, so bricks draw independent noise streams.
    """
    ens = ensemble.init_state(n_slabs)
    sh = NamedSharding(mesh, P(spatial_axis))
    return jax.tree.map(lambda x: jax.device_put(x, sh), ens)


def make_distributed_md_step(cfg: DPConfig, spec: DomainSpec, mesh: Mesh,
                             masses: Tuple[float, ...], dt_fs: float,
                             impl: Optional[str] = None,
                             spatial_axis="data",
                             model_axis: str = "model",
                             decomp: str = "slots",
                             neighbor: str = "brute",
                             potential: Optional[api.Potential] = None,
                             ensemble: Optional[api.Ensemble] = None,
                             barostat: Optional[api.Barostat] = None):
    """Build the shard_map'd ``(params, SlabState, ens, box, baro) ->
    ((SlabState, ens, box, baro), thermo)`` step.

    The returned function expects SlabState (and ensemble-state) leaves
    stacked over bricks and sharded P(spatial_axis) on dim 0; params, the
    dynamic ``box`` (3,) and the barostat state ``baro`` replicated (the
    box is global — every brick sees and rescales the same one). ``ens``
    comes from :func:`init_ensemble_state` (an empty pytree for stateless
    ensembles); ``baro`` from ``barostat.init_state()`` (``()`` without a
    barostat). See :func:`make_local_md_step` for the potential/ensemble/
    barostat/decomp/neighbor options.
    """
    step_local = make_local_md_step(
        cfg, spec, mesh, masses, dt_fs, impl=impl, spatial_axis=spatial_axis,
        model_axis=model_axis, decomp=decomp, neighbor=neighbor,
        potential=potential, ensemble=ensemble, barostat=barostat)

    def step(params, state: SlabState, ens, box, baro):
        # shard_map keeps the sharded brick dim at local size 1 — squeeze it.
        pos, vel, typ, mask = (x[0] for x in state)
        ens_l = jax.tree.map(lambda x: x[0], ens)
        (pos, vel, typ, mask, ens_l, box, baro), thermo = step_local(
            params, pos, vel, typ, mask, ens_l, box, baro)
        new_state = SlabState(pos=pos[None], vel=vel[None], typ=typ[None],
                              mask=mask[None])
        return (new_state, jax.tree.map(lambda x: x[None], ens_l),
                box, baro), thermo

    state_spec = _state_pspec(spatial_axis)
    thermo_spec = {k: P() for k in THERMO_KEYS}
    # jitted: an eager shard_map runs op by op
    return jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), state_spec, P(spatial_axis), P(), P()),
        out_specs=((state_spec, P(spatial_axis), P(), P()), thermo_spec),
        check_vma=False))


# ------------------------------------------------------- segment integration

def make_segment_runner(step_fn, donate: Optional[bool] = None):
    """Run the shard_map'd MD step through the shared segment engine.

    ``step_fn`` is the ``(params, SlabState, ens, box, baro) ->
    ((SlabState, ens, box, baro), thermo)`` step from
    :func:`make_distributed_md_step`. The returned callable
    ``run(state, params, n_steps, ens=(), box=None, baro=())`` executes
    ``n_steps`` steps as ONE jitted ``lax.scan`` dispatch over the
    ``(state, ens, box, baro)`` carry (thermo comes back stacked
    ``(n_steps,)``) and returns ``((state, ens, box, baro), thermo)`` — the
    host touches the device once per rebuild/migration segment, the same
    engine the single-process driver uses, keeping halo-exchange cadence
    (per step, inside the scan) and migration cadence (per segment,
    outside) aligned by construction. ``box`` is required: the dynamic box
    rides in the carry now (pass the DomainSpec launch box for fixed-box
    runs).
    """
    from repro.md import stepper

    engine = stepper.SegmentEngine(
        lambda carry, params: step_fn(params, *carry), donate=donate)

    def run(state: SlabState, params, n_steps: int, ens=(), box=None,
            baro=()):
        if box is None:
            raise ValueError("make_segment_runner: pass the (3,) box — the "
                             "dynamic box rides in the scan carry")
        return engine.run((state, ens, stepper.pack_box(box), baro),
                          n_steps, params)

    return run


def check_segment_thermo(thermo) -> None:
    """Per-segment overflow check over a segment's stacked thermo flags.

    Replaces the seed's per-step ``int(...)`` host syncs: flags for the whole
    segment arrive in one fetch. Capacity overflow in a capacity-bounded
    collective drops atoms silently, so a hard error is the only safe exit —
    escalation here means re-partitioning with larger capacities (see
    :func:`escalate_capacities`, which folds the carried box volume into
    the growth so a barostat squeeze escalates in one hop). The
    ``geom_overflow`` flag is the traced cutoff-vs-halo check: the carried
    box shrank until a brick no longer covers ``rcut_halo`` on some
    decomposed axis (pairs would be silently lost) — re-partition with
    fewer bricks along that axis or a smaller cutoff.
    """
    if "geom_overflow" in thermo and \
            int(np.max(np.asarray(thermo["geom_overflow"]))) > 0:
        raise RuntimeError(
            "geom_overflow: the carried box shrank below the brick "
            "decomposition's cutoff+halo geometry (a brick width < "
            "rcut_halo); pairs beyond the single-neighbor halo would be "
            "silently lost — re-partition with fewer bricks on that axis "
            "(DomainSpec topology)")
    keys = ("halo_overflow", "nbr_overflow") + \
        (("mig_overflow",) if "mig_overflow" in thermo else ())
    for key in keys:
        flags = np.asarray(thermo[key])
        worst = int(np.max(flags))
        if worst > 0:
            detail = ""
            if key == "mig_overflow" and flags.ndim and flags.shape[-1] > 1:
                # per-axis migration flags: name the worst sweep axis
                axis_worst = np.max(flags.reshape(-1, flags.shape[-1]), 0)
                detail = f" (per-axis worst: {axis_worst.tolist()})"
            msg = (f"{key} by {worst} atoms during segment{detail}; rerun "
                   f"with larger halo/atom capacities (DomainSpec) — "
                   f"capacity-bounded exchanges drop atoms past capacity")
            if worst >= int(neighbors.GRID_INVALID):
                msg = (f"{key}: the carried box moved past the static brick "
                       f"cell grid's validity (a cell dimension < "
                       f"rcut_halo) — the stencil would miss pairs; "
                       f"re-partition from the current box")
            raise RuntimeError(msg)


# ------------------------------------------------------------------ migration
#
# Split into PURE pieces (split / merge — no collectives, fixed send/recv
# slot capacities, fully static shapes) composed around one ppermute pair
# PER DECOMPOSED AXIS in _migrate_local: the staged sweeps (x, then y, then
# z) route a corner-crossing migrant through two/three axis-aligned hops.
# The pure pieces are what the invariant suite drives across emulated slab
# rings AND tori, and the scan-safety of the whole path is what lets
# make_outer_md_program fold migration into the two-level scanned
# trajectory.

def split_migrants(pos, vel, typ, mask, spec: DomainSpec, face_lo,
                   width=None, dim: int = 0):
    """Partition a brick into compacted stayers + fixed-capacity send
    packets along ONE axis.

    Returns ``(stayers, left_pkt, right_pkt, pack_ovf)`` where ``stayers``
    is ``(pos_c, vel_c, typ_c, mask_c, n_stay)`` (stay-compacted, stale
    slots ZEROED — a stale copy of a departed atom would otherwise coincide
    exactly with its live ghost: NaN force gradients at r = 0) and each
    packet is ``(pos (hc, 3), vel, typ, valid)`` bound for the -/+
    neighbor along axis ``dim``. Send capacity is ``spec.halo_capacity``
    slots per side; excess migrants are reported in ``pack_ovf``, never
    silently dropped into the exchange. ``width`` may be traced
    (carried-box geometry); ``None`` keeps the launch-time value.
    """
    if width is None:
        width = spec.brick_widths[dim]
    hc = spec.halo_capacity
    x = pos[:, dim] - face_lo
    go_left = mask & (x < 0)
    go_right = mask & (x >= width)
    stay = mask & ~go_left & ~go_right

    def pack(sel):
        order = jnp.argsort(jnp.where(sel, 0, 1), stable=True)
        idx = order[:hc]
        valid = sel[idx]
        ovf = jnp.sum(sel) - jnp.sum(valid)
        return (jnp.where(valid[:, None], pos[idx], 0.0),
                jnp.where(valid[:, None], vel[idx], 0.0),
                jnp.where(valid, typ[idx], 0), valid), ovf

    left_pkt, l_ovf = pack(go_left)
    right_pkt, r_ovf = pack(go_right)
    order = jnp.argsort(jnp.where(stay, 0, 1), stable=True)
    mask_c = stay[order]
    pos_c = jnp.where(mask_c[:, None], pos[order], 0.0)
    vel_c = jnp.where(mask_c[:, None], vel[order], 0.0)
    typ_c = jnp.where(mask_c, typ[order], 0)
    stayers = (pos_c, vel_c, typ_c, mask_c, jnp.sum(stay))
    return stayers, left_pkt, right_pkt, jnp.maximum(l_ovf, r_ovf)


def merge_arrivals(stayers, in_l, in_r, idx_s, spec: DomainSpec, box=None,
                   dim: int = 0):
    """Append arrival packets to the compacted stayers of one brick.

    ``in_l`` / ``in_r`` are the packets received from the -/+ neighbor
    along axis ``dim`` (each ``(pos, vel, typ, valid)``); ``idx_s`` is this
    brick's COORDINATE along that axis (traced inside shard_map, a plain
    int in the invariant harness). Periodic wrap along ``dim`` is applied
    to migrants that crossed the box ends. Returns ``((pos, vel, typ,
    mask), overflow)`` with arrivals placed at the first free slots;
    atom-capacity overflow is reported and the excess arrivals dropped by
    ``mode="drop"`` (the flag makes the chunk retry/abort — the data is
    never silently wrong). ``box`` carries the dynamic geometry; ``None``
    keeps the launch-time DomainSpec box.
    """
    n = spec.topology[dim]
    box_d = spec.box[dim] if box is None else box[dim]
    pos_c, vel_c, typ_c, mask_c, n_stay = stayers
    cap = pos_c.shape[0]
    # periodic wrap for migrants crossing the box ends along dim:
    # from brick n-1 arriving at brick 0: x ~ box_d -> x - box_d;
    # from brick 0 arriving at brick n-1: x < 0 -> x + box_d.
    ilp, ilv, ilt, ilval = in_l
    irp, irv, irt, irval = in_r
    ilp = ilp.at[:, dim].set(jnp.where(
        (idx_s == 0) & ilval & (ilp[:, dim] >= box_d),
        ilp[:, dim] - box_d, ilp[:, dim]))
    irp = irp.at[:, dim].set(jnp.where(
        (idx_s == n - 1) & irval & (irp[:, dim] < 0),
        irp[:, dim] + box_d, irp[:, dim]))

    arr_pos = jnp.concatenate([ilp, irp], 0)
    arr_vel = jnp.concatenate([ilv, irv], 0)
    arr_typ = jnp.concatenate([ilt, irt], 0)
    arr_val = jnp.concatenate([ilval, irval], 0)
    # place arrival j at slot n_stay + rank(j); invalid/overflow -> cap
    # (out of range, dropped by mode="drop")
    rank = jnp.cumsum(arr_val) - 1
    slot = jnp.where(arr_val, n_stay + rank, cap).astype(jnp.int32)
    m_ovf = jnp.maximum(jnp.max(jnp.where(arr_val, slot, 0)) - (cap - 1), 0)
    pos_c = pos_c.at[slot].set(arr_pos, mode="drop")
    vel_c = vel_c.at[slot].set(arr_vel, mode="drop")
    typ_c = typ_c.at[slot].set(arr_typ, mode="drop")
    mask_c = mask_c.at[slot].set(arr_val, mode="drop")
    return (pos_c, vel_c, typ_c, mask_c), m_ovf


def _migrate_local(pos, vel, typ, mask, spec: DomainSpec, spatial_axis,
                   box=None):
    """Per-shard migration: staged per-axis sweeps of split -> ppermute
    both ways -> merge.

    Fully traceable with static shapes — safe under ``lax.scan`` (the outer
    program folds this into the scanned trajectory at segment cadence).
    After the axis-a sweep every atom sits in the right brick COLUMN along
    a; the next sweep routes it within that column, so corner-crossers
    arrive in two/three hops. Returns squeezed ``((pos, vel, typ, mask),
    per_axis_overflow (ndim,))``; callers pmax the flags over the spatial
    axis. ``box`` carries the dynamic geometry (brick boundaries move with
    the barostat); ``None`` keeps the launch-time DomainSpec values.
    """
    topo = spec.topo
    idx_s = _flat_rank(spatial_axis)
    ovfs = []
    for a in topo.axes:
        coord = topo.coord_along(idx_s, a)
        width = (spec.box[a] if box is None else box[a]) / float(topo.shape[a])
        face_lo = coord.astype(jnp.float32) * width
        stayers, left_pkt, right_pkt, pack_ovf = split_migrants(
            pos, vel, typ, mask, spec, face_lo, width, a)
        in_l = jax.tree.map(
            lambda t: jax.lax.ppermute(t, spatial_axis, topo.plus_ring(a)),
            right_pkt)     # from the minus neighbor along a
        in_r = jax.tree.map(
            lambda t: jax.lax.ppermute(t, spatial_axis, topo.minus_ring(a)),
            left_pkt)      # from the plus neighbor along a
        (pos, vel, typ, mask), m_ovf = merge_arrivals(
            stayers, in_l, in_r, coord, spec, box, a)
        ovfs.append(jnp.maximum(pack_ovf, m_ovf))
    return (pos, vel, typ, mask), jnp.stack(ovfs)


def make_migration_step(spec: DomainSpec, mesh: Mesh,
                        spatial_axis: str = "data"):
    """Move atoms that crossed a brick boundary to the neighbor brick.

    Runs at neighbor-rebuild cadence. Capacity-bounded ppermute sends with
    overflow flags; periodic wrap is applied per axis to the migrated
    copies. ``migrate(state, box=None)``: pass the current carried box when
    a barostat moved it (brick boundaries scale with the box).
    """

    def migrate(state: SlabState, box):
        pos, vel, typ, mask = (x[0] for x in state)
        (pos, vel, typ, mask), ovf = _migrate_local(
            pos, vel, typ, mask, spec, spatial_axis, box)
        return SlabState(pos=pos[None], vel=vel[None], typ=typ[None],
                         mask=mask[None]), \
            jax.lax.pmax(jnp.max(ovf), spatial_axis)

    state_spec = _state_pspec(spatial_axis)
    sharded = jax.jit(jax.shard_map(migrate, mesh=mesh,
                                    in_specs=(state_spec, P()),
                                    out_specs=(state_spec, P()),
                                    check_vma=False))

    def migrate_entry(state: SlabState, box=None):
        from repro.md import stepper
        if box is None:
            box = stepper.pack_box(spec.box)
        return sharded(state, jnp.asarray(box))

    return migrate_entry


# ------------------------------------------- whole-trajectory outer program

class OuterMDProgram:
    """Distributed MD with migration + rebuild folded into ONE program.

    ``run(state, params, n_segments, seg_len, ens, box, baro)`` executes
    ``n_segments x seg_len`` steps as a single jitted shard_map dispatch: a
    two-level ``lax.scan`` per shard — outer over segments (each segment
    starts with scan-safe staged-sweep migration, then the halo-sweep +
    rebuild + ensemble step scanned ``seg_len`` times inside; the ensemble
    state, the DYNAMIC box and the barostat state ride in the carry through
    both scan levels — migration and the per-step brick geometry read the
    box the barostat actually produced). Host round-trips drop from one per
    segment to one per chunk; overflow flags (halo, neighbor, geometry,
    per-axis migration) come back stacked in the thermo fetch and are
    checked by :func:`check_segment_thermo` once per chunk.

    Jitted programs are cached per ``(n_segments, seg_len)``; ``build``
    exposes the raw callable so the production dry-run can lower/compile it
    at paper scale (including multi-axis spatial topologies).
    """

    def __init__(self, cfg: DPConfig, spec: DomainSpec, mesh: Mesh,
                 masses: Tuple[float, ...], dt_fs: float,
                 impl: Optional[str] = None, spatial_axis="data",
                 model_axis: str = "model", decomp: str = "atoms",
                 neighbor: str = "cells", donate: Optional[bool] = None,
                 potential: Optional[api.Potential] = None,
                 ensemble: Optional[api.Ensemble] = None,
                 barostat: Optional[api.Barostat] = None):
        self._step_local = make_local_md_step(
            cfg, spec, mesh, masses, dt_fs, impl=impl,
            spatial_axis=spatial_axis, model_axis=model_axis, decomp=decomp,
            neighbor=neighbor, potential=potential, ensemble=ensemble,
            barostat=barostat)
        self.ensemble = ensemble or api.NVE()
        self.barostat = barostat
        self._spec = spec
        self._mesh = mesh
        self._spatial_axis = spatial_axis
        if donate is None:
            donate = jax.default_backend() != "cpu"
        self._donate = donate
        self._jits: dict = {}
        self.state_pspec = _state_pspec(spatial_axis)
        self.thermo_pspec = {**{k: P() for k in THERMO_KEYS},
                             "mig_overflow": P()}

    def init_ensemble_state(self):
        """Sharded per-brick ensemble state for :meth:`run` (empty pytree
        for stateless ensembles)."""
        return init_ensemble_state(self.ensemble, self._spec.n_slabs,
                                   self._mesh, self._spatial_axis)

    def init_box(self):
        """The (3,) dynamic-box carry entry from the launch DomainSpec."""
        from repro.md import stepper
        return stepper.pack_box(self._spec.box)

    def init_barostat_state(self):
        """REPLICATED barostat state (every brick draws the same noise)."""
        return (self.barostat.init_state()
                if self.barostat is not None else ())

    def build(self, n_segments: int, seg_len: int):
        """The un-jitted shard_map'd ``(params, state, ens, box, baro) ->
        (state, ens, box, baro, thermo)``.

        thermo leaves are stacked ``(n_segments, seg_len)`` (psum'd scalars
        per step; the stress tensor stacks ``(n_segments, seg_len, 3, 3)``)
        plus ``mig_overflow`` stacked ``(n_segments, ndim)`` — one flag per
        staged migration sweep axis. The ensemble, box and barostat state
        thread through BOTH scan levels in the carry.
        """
        spec, spatial_axis = self._spec, self._spatial_axis
        step_local = self._step_local

        def program(params, state: SlabState, ens, box, baro):
            pos, vel, typ, mask = (x[0] for x in state)
            ens_l = jax.tree.map(lambda x: x[0], ens)

            def seg_body(st, _):
                pos, vel, typ, mask, e, box, baro = st
                (pos, vel, typ, mask), m_ovf = _migrate_local(
                    pos, vel, typ, mask, spec, spatial_axis, box)

                def step_body(s, _):
                    return step_local(params, *s)

                st, th = jax.lax.scan(step_body,
                                      (pos, vel, typ, mask, e, box, baro),
                                      None, length=seg_len)
                th["mig_overflow"] = jax.lax.pmax(m_ovf, spatial_axis)
                return st, th

            (pos, vel, typ, mask, ens_l, box, baro), th = jax.lax.scan(
                seg_body, (pos, vel, typ, mask, ens_l, box, baro), None,
                length=n_segments)
            new_state = SlabState(pos=pos[None], vel=vel[None], typ=typ[None],
                                  mask=mask[None])
            return (new_state, jax.tree.map(lambda x: x[None], ens_l),
                    box, baro, th)

        return jax.shard_map(program, mesh=self._mesh,
                             in_specs=(P(), self.state_pspec,
                                       P(spatial_axis), P(), P()),
                             out_specs=(self.state_pspec, P(spatial_axis),
                                        P(), P(), self.thermo_pspec),
                             check_vma=False)

    def run(self, state: SlabState, params, n_segments: int, seg_len: int,
            ens=(), box=None, baro=()):
        """One jitted dispatch; returns ``(state, ens, box, baro, thermo)``.

        ``box`` defaults to the launch DomainSpec box on the first chunk;
        pass the returned box (and ``baro``) back in on the next chunk so
        the dynamic geometry carries across dispatches.
        """
        if box is None:
            box = self.init_box()
        key = (n_segments, seg_len)
        fn = self._jits.get(key)
        if fn is None:
            fn = jax.jit(self.build(n_segments, seg_len),
                         donate_argnums=(1,) if self._donate else ())
            self._jits[key] = fn
        return fn(params, state, ens, jnp.asarray(box), baro)


def make_outer_md_program(cfg: DPConfig, spec: DomainSpec, mesh: Mesh,
                          masses: Tuple[float, ...], dt_fs: float,
                          **kw) -> OuterMDProgram:
    return OuterMDProgram(cfg, spec, mesh, masses, dt_fs, **kw)
