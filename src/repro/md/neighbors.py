"""O(N) cell-list neighbor search with PBC and type-sectioned padded lists.

Output layout matches the descriptor's expectation: for each atom, slots
[0, sel_0) hold type-0 neighbors, [sel_0, sel_0+sel_1) type-1, ... with -1
padding — the DeePMD type-sectioned convention that makes per-type embedding
nets static slices.

All shapes are static (fixed capacities), so the search jits and shards;
capacity overflow is *reported* (flags), never silently truncated — the
driver escalates capacities on overflow (the fault-tolerance policy for
density fluctuations).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class NeighborSpec:
    rcut_nbr: float              # rcut + skin buffer (paper: +2 A)
    sel: Tuple[int, ...]         # per-type slot capacities
    cell_capacity: int = 64      # max atoms per cell-list bin

    @property
    def nsel(self) -> int:
        return int(sum(self.sel))


#: Overflow-flag sentinel: the DYNAMIC box has shrunk below the static cell
#: grid's validity (a cell dimension < rcut_nbr, so the +/-1 stencil no
#: longer covers the cutoff). Raised by both the single-process 27-stencil
#: here and the brick-frame grid in ``md/slab_cells.py`` (non-periodic on
#: decomposed topology axes). Escalating slot capacities cannot fix this —
#: the driver must re-derive the grid from the current box. Far above any
#: real capacity excess, so ``flag >= GRID_INVALID`` is unambiguous.
GRID_INVALID = np.int32(1 << 20)

#: Name scope of every neighbor build's device ops (the cell list and the
#: brute-force path), so a profiler trace attributes them to this layer.
SCOPE = "md.neighbors"


def cell_capacity_for(n_atoms: int, box, rcut_nbr: float,
                      floor: int = 64) -> int:
    """Cell-bin capacity for ``n_atoms`` in ``box`` on the grid that
    :func:`make_cell_list_fn` builds: 1.5x the mean atoms per cell (room
    for thermal density fluctuations), rounded up to 8, at least ``floor``.

    Sizing the bins from the system keeps the first build from overflowing
    them: an overflow escalates ``sel`` together with the bins, and at
    copper width (~150 atoms per 10 A cell against a 64-slot default) three
    escalations took ``sel`` from 512 to 2112 slots.
    """
    ncell = np.maximum(np.floor(np.asarray(box, float) / rcut_nbr), 1)
    mean = n_atoms / float(np.prod(ncell))
    return max(int(floor), 8 * int(np.ceil(1.5 * mean / 8)))


def pair_dist2(pos: jax.Array, centers: jax.Array, cand: jax.Array,
               box: Optional[jax.Array]) -> jax.Array:
    """Squared min-image distance from each center i to its candidates j.

    ``centers`` is (N, 3); ``cand`` is (N, C) indices into ``pos`` with -1
    for no candidate (the result there is garbage; callers mask it). Built
    one coordinate at a time so that every temporary is (N, C) with the
    candidate axis minor: an (N, C, 3) array pads its trailing 3 to 128
    lanes on the TPU, a 40x blow-up that does not fit the chip at 16k
    atoms.
    """
    j = cand.clip(0)
    d2 = jnp.zeros(cand.shape, pos.dtype)
    for a in range(3):
        d = pos[:, a][j] - centers[:, a][:, None]
        if box is not None:
            d = d - box[a] * jnp.round(d / box[a])
        d2 = d2 + d * d
    return d2


def pack_type_sections(
    cand: jax.Array,      # (N, C) candidate indices (-1 invalid)
    valid: jax.Array,     # (N, C) candidate validity (already distance-gated)
    cand_type: jax.Array, # (N, C)
    sel: Tuple[int, ...],
) -> Tuple[jax.Array, jax.Array]:
    """Pack valid candidates into the DeePMD type-sectioned padded layout.

    For each atom, slots [0, sel_0) hold type-0 neighbors, the next sel_1
    type-1, ... with -1 padding. Pure static-shape masked form (stable
    argsort compaction, no data-dependent shapes) — traceable under
    ``lax.scan``, shared by the single-process, slab-cell, and brute-force
    rebuild paths. Returns (nlist (N, nsel), overflow excess count).
    """
    sections = []
    overflow = jnp.zeros((), jnp.int32)
    for t, cap_t in enumerate(sel):
        vt = valid & (cand_type == t)
        # Stable-sort invalids to the back; ties keep candidate order.
        order = jnp.argsort(jnp.where(vt, 0, 1), axis=1, stable=True)
        packed = jnp.take_along_axis(cand, order, axis=1)
        pvalid = jnp.take_along_axis(vt, order, axis=1)
        if packed.shape[1] < cap_t:   # fewer candidates than capacity: pad
            pad = cap_t - packed.shape[1]
            packed = jnp.pad(packed, ((0, 0), (0, pad)), constant_values=-1)
            pvalid = jnp.pad(pvalid, ((0, 0), (0, pad)))
        sec = jnp.where(pvalid[:, :cap_t], packed[:, :cap_t], -1)
        overflow = jnp.maximum(overflow, jnp.max(jnp.sum(vt, axis=1)) - cap_t)
        sections.append(sec)
    return jnp.concatenate(sections, axis=1), overflow


def _pack_sections(
    cand: jax.Array,
    dist2: jax.Array,
    cand_type: jax.Array,
    spec: NeighborSpec,
    rc2: float,
) -> Tuple[jax.Array, jax.Array]:
    """Distance-gate candidates, then pack into type sections."""
    return pack_type_sections(cand, (cand >= 0) & (dist2 < rc2), cand_type,
                              spec.sel)


@jax.named_scope(SCOPE)
def _brute_force_neighbors(
    pos: jax.Array, atype: jax.Array, spec: NeighborSpec,
    box: Optional[jax.Array] = None, amask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """O(N^2) reference / small-box fallback (cells would alias under PBC).

    Un-jitted traceable form — embeddable inside a ``lax.scan`` body."""
    n = pos.shape[0]
    cand = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :], (n, n))
    d2 = pair_dist2(pos, pos, cand, box)
    self_mask = jnp.eye(n, dtype=bool)
    valid = ~self_mask
    if amask is not None:
        valid &= (amask > 0)[None, :] & (amask > 0)[:, None]
    cand = jnp.where(valid, cand, -1)
    d2 = jnp.where(valid, d2, jnp.inf)
    ctype = atype[cand.clip(0)]
    return _pack_sections(cand, d2, ctype, spec, spec.rcut_nbr**2)


@functools.partial(jax.jit, static_argnames=("spec",))
def brute_force_neighbors(
    pos: jax.Array, atype: jax.Array, spec: NeighborSpec,
    box: Optional[jax.Array] = None, amask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Jitted entry point over :func:`_brute_force_neighbors`."""
    return _brute_force_neighbors(pos, atype, spec, box, amask)


def make_cell_list_fn(spec: NeighborSpec, box: np.ndarray, jit: bool = True,
                      dynamic_box: bool = False):
    """Build an O(N) neighbor function for an orthorhombic box.

    Static form (default): ``fn(pos, atype, amask=None)`` with the box baked
    in. Dynamic form (``dynamic_box=True``): ``fn(pos, atype, box,
    amask=None)`` — the cell COUNTS stay compile-time constants derived from
    the reference ``box`` given here, while cell sizes and the min-image
    wrap are recomputed from the traced per-call box (the box that rides in
    the scan carry under a barostat). If the traced box shrinks until a cell
    dimension no longer covers ``rcut_nbr`` (27-stencil would miss pairs),
    the overflow flag returns ``>= GRID_INVALID``: the driver must re-derive
    the grid from the current box — capacity escalation cannot fix geometry.

    Falls back to brute force when the reference box is too small for 3
    cells per dimension (always box-correct: min-image uses the traced box).

    With ``jit=False`` the raw traceable function is returned instead of a
    jitted wrapper — the form the outer engine embeds inside its segment
    ``lax.scan`` (everything is static-shape, sort-based binning with
    capacity slots; overflow is a flag in the trace, never a host branch).
    """
    ncell = np.maximum(np.floor(box / spec.rcut_nbr).astype(int), 1)
    if np.any(ncell < 3):
        if dynamic_box:
            def small_dyn_fn(pos, atype, box_t, amask=None):
                return _brute_force_neighbors(pos, atype, spec,
                                              jnp.asarray(box_t), amask)
            return jax.jit(small_dyn_fn) if jit else small_dyn_fn

        def small_fn(pos, atype, amask=None):
            return _brute_force_neighbors(
                pos, atype, spec, jnp.asarray(box), amask)
        return jax.jit(small_fn) if jit else small_fn

    ncells = int(np.prod(ncell))
    offsets = np.stack(
        np.meshgrid(*[[-1, 0, 1]] * 3, indexing="ij"), axis=-1
    ).reshape(-1, 3)                                   # (27, 3)

    @jax.named_scope(SCOPE)
    def core(pos, atype, box_t, amask):
        n = pos.shape[0]
        cap = spec.cell_capacity
        box_t = jnp.asarray(box_t)
        cell_size = box_t / jnp.asarray(ncell, box_t.dtype)
        # grid validity under a traced box: every cell dim must still cover
        # the cutoff, or the +/-1 stencil silently misses pairs
        grid_bad = jnp.any(cell_size < spec.rcut_nbr).astype(jnp.int32)
        cidx3 = jnp.clip((pos / cell_size).astype(jnp.int32),
                         0, jnp.asarray(ncell - 1))
        cflat = (cidx3[:, 0] * ncell[1] + cidx3[:, 1]) * ncell[2] + cidx3[:, 2]
        if amask is not None:
            cflat = jnp.where(amask > 0, cflat, ncells)   # park invalid atoms

        # Bucket atoms: rank within cell via sorted order.
        order = jnp.argsort(cflat)
        sorted_cells = cflat[order]
        starts = jnp.searchsorted(sorted_cells, jnp.arange(ncells + 1))
        rank = jnp.arange(n) - starts[sorted_cells]
        if amask is not None:
            # parked atoms share bin ncells; exclude their ranks (sorted
            # order!) from the capacity check or they false-trigger it.
            cell_overflow = jnp.max(
                jnp.where((amask > 0)[order], rank, 0)) - (cap - 1)
        else:
            cell_overflow = jnp.max(rank) - (cap - 1)
        # Out-of-capacity or parked atoms drop (mode="drop").
        table = jnp.full((ncells + 1, cap), -1, jnp.int32)
        table = table.at[sorted_cells, rank].set(
            order.astype(jnp.int32), mode="drop")

        # Candidates: 27 neighbor cells per atom.
        nbr3 = (cidx3[:, None, :] + jnp.asarray(offsets)[None, :, :]) % jnp.asarray(ncell)
        nbrflat = (nbr3[..., 0] * ncell[1] + nbr3[..., 1]) * ncell[2] + nbr3[..., 2]
        cand = table[nbrflat].reshape(n, 27 * cap)
        self_mask = cand == jnp.arange(n, dtype=jnp.int32)[:, None]
        cand = jnp.where(self_mask, -1, cand)

        d2 = jnp.where(cand >= 0, pair_dist2(pos, pos, cand, box_t), jnp.inf)
        ctype = atype[cand.clip(0)]
        nlist, sec_overflow = _pack_sections(
            cand, d2, ctype, spec, spec.rcut_nbr**2)
        overflow = jnp.maximum(sec_overflow, cell_overflow)
        return nlist, jnp.maximum(overflow, grid_bad * GRID_INVALID)

    if dynamic_box:
        def dyn_fn(pos, atype, box_t, amask=None):
            return core(pos, atype, box_t, amask)
        return jax.jit(dyn_fn) if jit else dyn_fn

    def fn(pos, atype, amask=None):
        return core(pos, atype, box, amask)

    return jax.jit(fn) if jit else fn
