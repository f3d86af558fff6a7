"""O(N) cell-list neighbor search inside one brick (+ ghost shell).

Geometry is static per DomainSpec: on every DECOMPOSED axis the brick frame
spans [-rc_halo, width_a + rc_halo) (ghosts included, non-periodic — ghosts
ARE the periodicity there), undecomposed axes are periodic via min-image.
A ``(k,)`` topology reproduces the legacy 1-D slab grid exactly. All shapes
are static so the search lowers inside the shard_map'd MD step — this is
the path the multi-pod MD dry-run compiles at 122,779 atoms/chip (paper
weak-scaling parity; the brute-force O(N^2) variant is for tests only).
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.types import DPConfig
from repro.md.neighbors import GRID_INVALID, pack_type_sections, pair_dist2


def _allowed(n: int, periodic: bool):
    # With <3 cells on a periodic dim, +/-1 offsets alias the same cell
    # (duplicate candidates); keep a duplicate-free covering stencil.
    # Non-periodic dims keep the full stencil: out-of-range offsets are
    # routed to the always-empty dump row instead of wrapping.
    if n >= 3 or not periodic:
        return [-1, 0, 1]
    return [-1, 0] if n == 2 else [0]


def make_slab_neighbor_fn(cfg: DPConfig, box: Tuple[float, float, float],
                          slab_width: float, rc_halo: float,
                          n_centers: int, cell_capacity: int = 96,
                          topology: Optional[Tuple[int, ...]] = None,
                          max_density: Optional[float] = None):
    """Neighbor lists for ``n_centers`` center atoms of a brick array.

    Returns fn(pos_all, typ_all, mask_all, brick_lo, center_start,
    box=None, widths=None) -> (nlist (n_centers, nsel), overflow);
    ``center_start`` may be traced (model shards pass axis_index *
    n_centers in atom-decomposition mode). pos_all = owned atoms then the
    staged-sweep ghosts; nlist indexes pos_all rows. ``brick_lo`` is the
    brick's low-face position: a scalar (legacy 1-D spelling, the x face)
    or a (3,) vector (undecomposed entries ignored).

    ``topology`` names the decomposed axes (``None`` -> the legacy
    ``(k,)`` x-slab layout whose x-width is ``slab_width``). The cell
    COUNTS are static, derived from the launch-time ``box`` / brick widths
    given here; the optional per-call ``box``/``widths`` (traced values
    from the carried box under a barostat) move the cell SIZES. If the
    carried box shrinks until a cell dimension no longer covers
    ``rc_halo`` (the stencil would miss pairs), the overflow flag returns
    ``>= GRID_INVALID`` — geometry, not capacity.

    ``max_density`` (atoms per A^3, an upper bound for the brick) grows
    ``cell_capacity`` to hold a cell of this grid at that density.
    """
    rc2 = rc_halo * rc_halo
    shape = tuple(int(s) for s in topology) if topology is not None else None
    ndim = len(shape) if shape is not None else 1
    box_static = tuple(float(b) for b in box)
    if shape is not None:
        widths_static = tuple(box_static[a] / shape[a] for a in range(ndim))
    else:
        widths_static = (float(slab_width),)
    decomposed = tuple(a < ndim for a in range(3))

    # static cell grid: brick+ghost span on decomposed axes (non-periodic —
    # ghosts cover the wrap), the full box on undecomposed axes (periodic)
    ncs, cs0 = [], []
    for a in range(3):
        if decomposed[a]:
            span = widths_static[a] + 2 * rc_halo
        else:
            span = box_static[a]
        nc = max(int(np.floor(span / rc_halo)), 1)
        ncs.append(nc)
        cs0.append(span / nc)
    ncx, ncy, ncz = ncs
    ncells = ncx * ncy * ncz
    if max_density is not None:
        need = max_density * float(np.prod(cs0))
        cell_capacity = max(cell_capacity, 8 * int(np.ceil(need / 8)))

    offsets = np.array([
        (ox, oy, oz)
        for ox in _allowed(ncx, not decomposed[0])
        for oy in _allowed(ncy, not decomposed[1])
        for oz in _allowed(ncz, not decomposed[2])
    ])

    def fn(pos_all, typ_all, mask_all, brick_lo, center_start=0,
           box=None, widths=None):
        # brick_lo: scalar (legacy x-face) or vector (per-axis faces)
        lo_v = jnp.asarray(brick_lo, jnp.float32).reshape(-1)
        lo = [lo_v[min(a, lo_v.shape[0] - 1)] if decomposed[a] else 0.0
              for a in range(3)]
        if box is None:
            cs = list(cs0)
            grid_bad = jnp.zeros((), jnp.int32)
            boxj = jnp.asarray([1e30 if decomposed[a] else box_static[a]
                                for a in range(3)], jnp.float32)
        else:
            # dynamic geometry from the carried box: static counts, traced
            # sizes — flag the grid when a cell stops covering rc_halo
            cs = []
            for a in range(3):
                if decomposed[a]:
                    w = (widths[a] if widths is not None
                         else widths_static[a])
                    cs.append((w + 2 * rc_halo) / ncs[a])
                else:
                    cs.append(box[a] / ncs[a])
            grid_bad = jnp.zeros((), jnp.bool_)
            for a in range(3):
                grid_bad = grid_bad | (cs[a] < rc_halo)
            grid_bad = grid_bad.astype(jnp.int32)
            # min-image on undecomposed axes only: decomposed axes are
            # ghost-resolved (see domain.py)
            boxj = jnp.stack([jnp.float32(1e30) if decomposed[a] else box[a]
                              for a in range(3)])
        n_all = pos_all.shape[0]
        # per-axis cell index: brick frame (shifted so the low ghost shell
        # starts at 0, clipped) on decomposed axes; periodic bins elsewhere
        cidx = []
        for a in range(3):
            if decomposed[a]:
                xf = pos_all[:, a] - lo[a] + rc_halo
                cidx.append(jnp.clip((xf / cs[a]).astype(jnp.int32),
                                     0, ncs[a] - 1))
            else:
                cidx.append(jnp.floor(pos_all[:, a] / cs[a])
                            .astype(jnp.int32) % ncs[a])
        ci, cj, ck = cidx
        cflat = (ci * ncy + cj) * ncz + ck
        cflat = jnp.where(mask_all, cflat, ncells)          # park invalid

        order = jnp.argsort(cflat)
        sorted_cells = cflat[order]
        starts = jnp.searchsorted(sorted_cells, jnp.arange(ncells + 1))
        rank = jnp.arange(n_all) - starts[sorted_cells]
        # row ncells: parked invalid atoms; row ncells+1: ALWAYS EMPTY —
        # the dump target for out-of-range stencil cells (distinct rows, or
        # padding atoms would leak back in as candidates).
        # rank is in SORTED atom order — align the validity mask before
        # reducing, or parked atoms' ranks (bin ncells) leak into the max.
        cell_ovf = jnp.max(jnp.where(mask_all[order], rank, 0)) \
            - (cell_capacity - 1)
        table = jnp.full((ncells + 2, cell_capacity), -1, jnp.int32)
        table = table.at[sorted_cells, rank].set(order.astype(jnp.int32),
                                                 mode="drop")

        start = jnp.asarray(center_start, jnp.int32)
        csl = lambda a: jax.lax.dynamic_slice_in_dim(a, start, n_centers, 0)
        nbr3 = jnp.stack([csl(ci), csl(cj), csl(ck)], -1)
        nbr3 = nbr3[:, None, :] + jnp.asarray(offsets)[None, :, :]
        # decomposed axes are NON-periodic in the brick frame (ghosts cover
        # the wrap): out-of-range stencil cells go to the dump row
        valid_cell = jnp.ones(nbr3.shape[:-1], bool)
        nbrc = []
        for a in range(3):
            if decomposed[a]:
                valid_cell = valid_cell & (nbr3[..., a] >= 0) \
                    & (nbr3[..., a] <= ncs[a] - 1)
                nbrc.append(jnp.clip(nbr3[..., a], 0, ncs[a] - 1))
            else:
                nbrc.append(nbr3[..., a] % ncs[a])
        nbrflat = (nbrc[0] * ncy + nbrc[1]) * ncz + nbrc[2]
        nbrflat = jnp.where(valid_cell, nbrflat, ncells + 1)
        cand = table[nbrflat].reshape(n_centers, len(offsets) * cell_capacity)
        self_idx = start + jnp.arange(n_centers, dtype=jnp.int32)[:, None]
        cand = jnp.where(cand == self_idx, -1, cand)

        center_pos = jax.lax.dynamic_slice_in_dim(pos_all, start, n_centers, 0)
        # Gate by CENTER validity too (as the brute-force reference does):
        # an invalidated slot can hold a stale copy of a migrated atom whose
        # live ghost sits at the SAME coordinates — a d2 == 0 "pair" whose
        # norm has a NaN gradient that survives the energy mask (0 * nan).
        center_mask = jax.lax.dynamic_slice_in_dim(mask_all, start,
                                                   n_centers, 0)
        d2 = jnp.where(cand >= 0,
                       pair_dist2(pos_all, center_pos, cand, boxj), jnp.inf)
        ctype = typ_all[cand.clip(0)]

        valid = (cand >= 0) & (d2 < rc2) & center_mask[:, None]
        nlist, sec_ovf = pack_type_sections(cand, valid, ctype, cfg.sel)
        overflow = jnp.maximum(sec_ovf, cell_ovf)
        return nlist, jnp.maximum(overflow, grid_bad * GRID_INVALID)

    return fn
