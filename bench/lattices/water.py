"""Water at 1 g/cm^3: a cube of ``CELL_A`` holding 64 rigid molecules,
``system["cells"]`` cubes along each axis (paper Sec. 4: 192-atom cells).

The paper replicates an equilibrated cell; this one is built from the
molecule's geometry alone. Each cube puts an O at the centre of each of
its 4 x 4 x 4 sub-cubes, and each molecule its two H at ``OH`` from the O
with the angle ``HOH_DEG`` between them, turned by a rotation of its own
drawn from ``rng`` (a uniform unit quaternion). So every seed gives the
same atoms, types and box; only the orientations move, and
``systems.build_system`` adds the jitter. Atoms are in molecule order,
O, H, H; types 0 = O, 1 = H. Before jitter two atoms of different
molecules are at least ``CELL_A / 4 - 2 * OH`` = 1.19 A apart.
"""

import numpy as np

CELL_A = 12.42             # 64 x 18.015 amu in 12.42^3 A^3: 0.999 g/cm^3
GRID = 4                   # O sites along each edge of a cube
OH = 0.9572                # O-H bond (A)
HOH_DEG = 104.52           # H-O-H angle


def rotations(q):
    """Rotation matrices (n, 3, 3) of the quaternions ``q`` (n, 4)."""
    w, x, y, z = (q / np.linalg.norm(q, axis=1, keepdims=True)).T
    return np.stack([
        np.stack([1 - 2 * (y * y + z * z), 2 * (x * y - w * z),
                  2 * (x * z + w * y)], -1),
        np.stack([2 * (x * y + w * z), 1 - 2 * (x * x + z * z),
                  2 * (y * z - w * x)], -1),
        np.stack([2 * (x * z - w * y), 2 * (y * z + w * x),
                  1 - 2 * (x * x + y * y)], -1)], axis=1)


def build(system, rng):
    """(positions (N, 3), types (N,), box (3,)), N = 192 * prod(cells)."""
    n = np.asarray(system["cells"], int)
    spacing = CELL_A / GRID
    grid = np.stack(np.meshgrid(*[np.arange(GRID * k) for k in n],
                                indexing="ij"), axis=-1).reshape(-1, 3)
    o = (grid + 0.5) * spacing                            # (M, 3)
    ang = np.deg2rad(HOH_DEG)
    arms = np.array([[OH, 0.0, 0.0],
                     [OH * np.cos(ang), OH * np.sin(ang), 0.0]])
    rot = rotations(rng.normal(size=(len(o), 4)))
    h = np.einsum("mij,hj->mhi", rot, arms)               # (M, 2, 3)
    pos = np.concatenate([o[:, None, :], o[:, None, :] + h], axis=1)
    typ = np.tile(np.array([0, 1, 1], np.int32), len(o))
    return pos.reshape(-1, 3), typ, n * CELL_A
