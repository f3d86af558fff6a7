"""Operations and bytes the Deep Potential step needs, from its sizes.

Counts are over the real neighbors (those within rcut), never over padded
``sel`` slots, so a change that removes padding cannot raise them, and a
share of a peak computed from them cannot pass 100% by counting work the
model does not need. Multiply-adds count as 2 operations.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import numpy as np


def _fit_flops(model: Dict[str, Any]) -> int:
    """Forward operations of one fitting net on one atom."""
    d_in = int(model["axis_neuron"]) * int(model["embed_widths"][-1])
    total = 0
    for w in list(model["fit_widths"]) + [1]:
        total += 2 * d_in * int(w)
        d_in = int(w)
    return total


def model_flops_per_step(model: Dict[str, Any], n_atoms: int,
                         n_neighbors: int) -> float:
    """Operations of one force evaluation that every implementation rung
    must do, for ``n_atoms`` atoms with ``n_neighbors`` real neighbors in
    all: the contraction T = R~^T G (2*4*M per neighbor), the descriptor
    D = (T<)^T T (2*4*M<*M per atom) and the atom's own fitting net, each
    forward and backward. The backward of a product of two inputs costs two
    products (a gradient for each); the fitting net's backward needs the
    gradient of its input only, one product per layer. The embedding net is
    left out, since the rungs compute it in different ways (net, table,
    fused kernel), so this is a lower bound on the work of any rung."""
    m = int(model["embed_widths"][-1])
    m_sub = int(model["axis_neuron"])
    contraction = 3 * (2 * 4 * m) * n_neighbors
    descriptor = 3 * (2 * 4 * m_sub * m) * n_atoms
    fitting = 2 * _fit_flops(model) * n_atoms
    return float(contraction + descriptor + fitting)


def dp_fused_cost_per_step(model: Dict[str, Any], n_atoms: int,
                           n_neighbors: int) -> Dict[str, float]:
    """Operations and HBM bytes of the ``dp_fused`` forward and backward
    kernels in one force evaluation, as ``kernels/dp_fused/dp_fused.py``
    computes them: in Chebyshev space, T = (R~^T B) C and its backward
    through Q = dT C^T, so that G = B C is never formed.

    Per real neighbor and Chebyshev term k < K: the forward's basis
    recurrence T_k+1 = 2u T_k - T_k-1 (3) and its accumulation into
    P = R~^T B (2*4); the backward's recurrence (3), its derivative's
    T'_k+1 = 2 T_k + 2u T'_k - T'_k-1 (5), dR~ += Q_k T_k (2*4),
    r_k = sum_c R~_c Q_ck (7) and ds += T'_k r_k (2). Per atom and per
    type section: T = P C and Q = dT C^T, 2*4*K*M each. The per-neighbor
    terms run over every section's real neighbors once, so they do not
    grow with the sections; the per-atom terms do, as do T's and dT's
    bytes and the tables. Left out: a few operations per neighbor outside
    the loop over k (u from s, the domain mask) and the lane sums that
    close P, whose length is the kernel's tile, not the model's. Bytes are
    what the kernels must move: s and R~ in (4 + 16 B) each way, ds and
    dR~ out (20 B), T out and dT in (4*M floats per atom and section), and
    each section's table once per kernel."""
    k = int(model["cheb_order"])
    m = int(model["embed_widths"][-1])
    sections = int(model["ntypes"])
    per_nbr_fwd = (3 + 2 * 4) * k
    per_nbr_bwd = (3 + 5 + 2 * 4 + 7 + 2) * k
    per_atom = sections * 2 * (2 * 4 * k * m)
    f32 = 4
    nbr_bytes = (4 + 16) * 2 + (4 + 16)                    # fwd in, bwd in+out
    atom_bytes = sections * 2 * 4 * m * f32      # T out, dT in, per section
    table_bytes = 2 * sections * k * m * f32
    return {"flops": float((per_nbr_fwd + per_nbr_bwd) * n_neighbors
                           + per_atom * n_atoms),
            "bytes": float(nbr_bytes * n_neighbors + atom_bytes * n_atoms
                           + table_bytes)}


def neighbor_total(counts: Sequence[np.ndarray]) -> int:
    """Real neighbors of all atoms, summed over neighbor types."""
    return int(sum(int(np.asarray(c).sum()) for c in counts))
