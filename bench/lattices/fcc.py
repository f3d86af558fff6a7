"""Face-centred cubic crystal of one type: four atoms to a cube of edge
``A``, ``system["cells"]`` cubes along each axis.

A copy of the program's ``md/lattice.py`` ``fcc_copper``, so that the
benchmark's atoms do not move when that changes. Draws nothing from the
seed: ``systems.build_system`` adds the jitter.
"""

import numpy as np

A = 3.634                  # copper's lattice constant (A), paper Sec. 4
BASE = np.array([[0.0, 0.0, 0.0], [0.5, 0.5, 0.0], [0.5, 0.0, 0.5],
                 [0.0, 0.5, 0.5]])


def build(system, rng):
    """(positions (N, 3), types (N,), box (3,)), N = 4 * prod(cells)."""
    n = tuple(system["cells"])
    grid = np.stack(np.meshgrid(*[np.arange(k) for k in n], indexing="ij"),
                    axis=-1).reshape(-1, 1, 3)
    pos = (grid + BASE[None]).reshape(-1, 3) * A
    return pos, np.zeros(len(pos), np.int32), np.asarray(n, float) * A
