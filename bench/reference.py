"""Plain Deep Potential (se_e2_a) reference, written from the paper.

Nothing here imports the program. Neighbors come from a brute-force search
over every atom (minimum image, in blocks of centers); the model is the
published one: environment matrix R~ with the DeePMD switching function,
the 1 -> 32 -> 64 -> 128 residual tanh embedding net per neighbor type,
T = R~^T G / Nm, D = (T<)^T T, the 240^3 residual fitting net per center
type, and forces and virial from ``jax.grad`` of the energy with respect to
the pair vectors; ``integrate`` runs plain velocity Verlet on those forces
in float64 on the host. Every matrix product takes an explicit precision, so the
same code is the reference (``highest``) and its lower-precision control
(``bf16``: operands rounded to bf16, as the TPU's default precision does).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

FORCE_TO_ACC = 9.64853329045e-3          # (eV/A)/amu in A/fs^2


def _mm(a, b, prec: str):
    """a @ b at ``highest`` (f32), or with both operands rounded to bf16
    and f32 accumulation -- what the TPU's default precision does to an
    f32 product -- on any backend."""
    if prec == "bf16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _einsum(spec: str, a, b, prec: str):
    if prec == "bf16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


# ------------------------------------------------------------- neighbors

def neighbor_capacity(model: Dict[str, Any], n_of_type: Sequence[int],
                      volume: float, skin: float = 0.0) -> Tuple[int, ...]:
    """Per-type list widths for the brute-force search: 1.5x the mean count
    of each type inside rcut + skin, plus 16, rounded up to 8. A center
    that fills its width raises, so a width can be too large but never
    truncates."""
    rc = float(model["rcut"]) + skin
    sphere = 4.0 / 3.0 * math.pi * rc ** 3
    return tuple(8 * math.ceil((1.5 * sphere * n / volume + 16) / 8)
                 for n in n_of_type)


@functools.partial(jax.jit, static_argnames=("rcut", "ks", "block"))
def _neighbors(pos, typ, box, *, rcut, ks, block):
    n = pos.shape[0]
    n_blocks = n // block

    def one(b):
        i0 = b * block
        ctr = jax.lax.dynamic_slice_in_dim(pos, i0, block)
        d2 = jnp.zeros((block, n), pos.dtype)
        for a in range(3):
            d = pos[None, :, a] - ctr[:, a, None]
            d = d - box[a] * jnp.round(d / box[a])
            d2 = d2 + d * d
        self_ = jnp.arange(n)[None, :] == (i0 + jnp.arange(block))[:, None]
        inside = (d2 < rcut * rcut) & ~self_
        out = []
        for t, k in enumerate(ks):
            ok = inside & (typ[None, :] == t)
            # nearest first; slots past the real count get index -1
            _, idx = jax.lax.top_k(jnp.where(ok, -d2, -jnp.inf), k)
            cnt = ok.sum(axis=1)
            idx = jnp.where(jnp.arange(k)[None, :] < cnt[:, None], idx, -1)
            out.append((idx.astype(jnp.int32), cnt))
        return out

    res = jax.lax.map(one, jnp.arange(n_blocks))
    lists = [r[0].reshape(n, -1) for r in res]
    counts = [r[1].reshape(n) for r in res]
    return lists, counts


def neighbor_lists(pos, typ, box, rcut: float, ks: Sequence[int],
                   block: int = 256):
    """Every neighbor within ``rcut`` of every atom, one (N, k_t) list per
    neighbor type (-1 past the real count), and the per-atom counts.
    Raises where a center fills a list, which could hide a neighbor."""
    n = pos.shape[0]
    block = math.gcd(n, block)
    lists, counts = _neighbors(jnp.asarray(pos, jnp.float32),
                               jnp.asarray(typ, jnp.int32),
                               jnp.asarray(box, jnp.float32), rcut=float(rcut),
                               ks=tuple(int(k) for k in ks), block=block)
    counts = [np.asarray(c) for c in counts]
    for t, (c, k) in enumerate(zip(counts, ks)):
        if c.max(initial=0) >= k:
            raise RuntimeError(f"type-{t} neighbors fill the brute-force "
                               f"width {k}: raise it")
    return lists, counts


# ------------------------------------------------------------------ model

def switching_s(r, rcut_smth: float, rcut: float):
    """s(r) = w(r) / r with the C2 quintic switch between rcut_smth and rcut."""
    u = jnp.clip((r - rcut_smth) / (rcut - rcut_smth), 0.0, 1.0)
    w = u * u * u * (-6.0 * u * u + 15.0 * u - 10.0) + 1.0
    return jnp.where(r < rcut, w / r, 0.0)


def resnet_tanh(layers: List[Dict[str, Any]], x, prec):
    """DeePMD residual MLP: identity shortcut where a width repeats, (x, x)
    where it doubles, none otherwise."""
    h = x
    for lyr in layers:
        y = jnp.tanh(_mm(h, lyr["w"], prec) + lyr["b"])
        d_in, d_out = lyr["w"].shape
        if d_out == d_in:
            h = h + y
        elif d_out == 2 * d_in:
            h = jnp.concatenate([h, h], axis=-1) + y
        else:
            h = y
    return h


def atomic_energy(params, model, rij_t, valid_t, center_typ, prec):
    """Per-center energies. ``rij_t[t]`` (B, k_t, 3) pair vectors to the
    type-t neighbors, ``valid_t[t]`` (B, k_t) their mask."""
    dstd = params["dstd"][center_typ]                          # (B, 4)
    t_mat = 0.0
    for t, (rij, valid) in enumerate(zip(rij_t, valid_t)):
        r = jnp.sqrt(jnp.sum(jnp.where(valid[..., None], rij, 1.0) ** 2, -1))
        s = jnp.where(valid, switching_s(r, model["rcut_smth"],
                                         model["rcut"]), 0.0)
        env = jnp.concatenate([s[..., None], (s / r)[..., None] * rij], -1)
        env = jnp.where(valid[..., None], env, 0.0) / dstd[:, None, :]
        g = resnet_tanh(params["embed"][str(t)],
                        (s / dstd[:, None, 0])[..., None], prec)
        t_mat = t_mat + _einsum("bka,bkm->bam", env, g, prec)
    t_mat = t_mat / float(sum(model["sel"]))
    m_sub = int(model["axis_neuron"])
    d = _einsum("bam,ban->bmn", t_mat[..., :m_sub], t_mat, prec) \
        .reshape(t_mat.shape[0], -1)
    e = jnp.zeros(d.shape[0], d.dtype)
    for c in range(int(model["ntypes"])):
        net = params["fit"][str(c)]
        h = resnet_tanh(net["hidden"], d, prec)
        e_c = (_mm(h, net["head"]["w"], prec)[:, 0]
               + net["head"]["b"][0])
        e = jnp.where(center_typ == c, e_c, e)
    return e + params["ebias"][center_typ]


def _pair_vectors(pos, box, lists, i0, block):
    ctr = jax.lax.dynamic_slice_in_dim(pos, i0, block)
    rij_t, valid_t, idx_t = [], [], []
    for lst in lists:
        idx = jax.lax.dynamic_slice_in_dim(lst, i0, block)
        valid = idx >= 0
        j = jnp.maximum(idx, 0)
        rij = pos[j] - ctr[:, None, :]
        rij = rij - box * jnp.round(rij / box)
        rij_t.append(jnp.where(valid[..., None], rij, 0.0))
        valid_t.append(valid)
        idx_t.append(j)
    return rij_t, valid_t, idx_t


@functools.partial(jax.jit, static_argnames=("model_key", "block", "prec"))
def _efw(params, pos, typ, box, lists, *, model_key, block, prec):
    model = dict(model_key)
    n = pos.shape[0]

    def one(carry, b):
        e_acc, f_acc, w_acc = carry
        i0 = b * block
        rij_t, valid_t, idx_t = _pair_vectors(pos, box, lists, i0, block)
        ctyp = jax.lax.dynamic_slice_in_dim(typ, i0, block)

        def e_of(rij_t):
            return jnp.sum(atomic_energy(params, model, rij_t, valid_t, ctyp,
                                         prec))

        e, grads = jax.value_and_grad(e_of)(rij_t)
        for rij, g, j, valid in zip(rij_t, grads, idx_t, valid_t):
            g = jnp.where(valid[..., None], g, 0.0)
            # E depends on r_ij = x_j - x_i: dE/dx_j = g, dE/dx_i = -sum g
            f_acc = f_acc.at[j.reshape(-1)].add(-g.reshape(-1, 3))
            f_acc = jax.lax.dynamic_update_slice_in_dim(
                f_acc, jax.lax.dynamic_slice_in_dim(f_acc, i0, block)
                + g.sum(axis=1), i0, 0)
            w_acc = w_acc - _einsum("bki,bkj->ij", rij, g, prec)
        return (e_acc + e, f_acc, w_acc), None

    init = (jnp.zeros((), jnp.float32), jnp.zeros((n, 3), jnp.float32),
            jnp.zeros((3, 3), jnp.float32))
    (e, f, w), _ = jax.lax.scan(one, init, jnp.arange(n // block))
    return e, f, w


def model_key(model: Dict[str, Any]):
    """A hashable form of a config's ``model`` dict."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))


def energy_forces_virial(params, model, pos, typ, box, lists,
                         prec: str = "highest", block: int = 128):
    """(E, F (N, 3), W (3, 3)) of the frame, as float64 host arrays.
    W = -sum_ij r_ij (x) dE/dr_ij, the convention of the stress
    (K + W) / V."""
    n = pos.shape[0]
    block = math.gcd(n, block)
    e, f, w = _efw(params, jnp.asarray(pos, jnp.float32),
                   jnp.asarray(typ, jnp.int32), jnp.asarray(box, jnp.float32),
                   [jnp.asarray(lst) for lst in lists],
                   model_key=model_key(model), block=block, prec=prec)
    return (float(e), np.asarray(f, np.float64), np.asarray(w, np.float64))


def integrate(params, model, pos, vel, typ, box, masses, dt: float,
              steps: int, prec: str = "highest", skin: float = 1.0,
              kick_sign: float = 1.0, block: int = 512,
              state=np.float64) -> Dict[str, Any]:
    """Velocity Verlet (half kick, drift, force, half kick) for ``steps``
    steps of ``dt`` fs from the frame ``(pos, vel)``, in float64 on the
    host with the forces above. Neighbors come from a brute-force list out
    to rcut + ``skin``, built again whenever an atom has moved more than
    ``skin / 2`` since the last build; pairs past rcut add exactly nothing.
    Positions stay wrapped into the box. ``kick_sign`` -1 flips the kicks
    (a broken integrator, for the checks' faults). ``state`` np.float32
    rounds positions and velocities to float32 after every update, as a
    float32 program keeps them (a witness, not the check). Returns the final
    ``pos``, ``vel``, ``force`` and ``virial``, and ``pe`` and ``ke`` (eV)
    after every step."""
    box = np.asarray(box, np.float64)
    typ = np.asarray(typ, np.int32)
    n_of_type = [int((typ == t).sum()) for t in range(int(model["ntypes"]))]
    ks = neighbor_capacity(model, n_of_type, float(np.prod(box)), skin)
    radius = float(model["rcut"]) + skin
    acc = FORCE_TO_ACC / np.asarray(masses, np.float64)[:, None]
    x = np.mod(np.asarray(pos, np.float64), box).astype(state)
    v = np.asarray(vel, np.float64).astype(state)

    def forces(x):
        return energy_forces_virial(params, model, x, typ, box, lists, prec,
                                    block)

    x_built = x.copy()
    lists, _ = neighbor_lists(x, typ, box, radius, ks)
    e, f, w = forces(x)
    pe, ke = [], []
    for _ in range(steps):
        v = (v + kick_sign * 0.5 * dt * f * acc).astype(state)
        x = np.mod(x + dt * v, box).astype(state)
        moved = x - x_built
        moved -= box * np.round(moved / box)
        if np.max(np.sum(moved * moved, axis=1)) > (0.5 * skin) ** 2:
            x_built = x.copy()
            lists, _ = neighbor_lists(x, typ, box, radius, ks)
        e, f, w = forces(x)
        v = (v + kick_sign * 0.5 * dt * f * acc).astype(state)
        pe.append(e)
        ke.append(0.5 * float(np.sum(v * v / acc)))
    return {"pos": x, "vel": v, "force": f, "virial": w,
            "pe": np.asarray(pe), "ke": np.asarray(ke)}


@functools.partial(jax.jit, static_argnames=("model_key", "block"))
def _s2_sums(pos, typ, box, lists, *, model_key, block):
    model = dict(model_key)
    ntypes = int(model["ntypes"])

    def one(carry, b):
        s2_acc, cnt_acc = carry
        i0 = b * block
        rij_t, valid_t, _ = _pair_vectors(pos, box, lists, i0, block)
        ctyp = jax.lax.dynamic_slice_in_dim(typ, i0, block)
        onehot = (ctyp[:, None] == jnp.arange(ntypes)[None, :]) \
            .astype(pos.dtype)                                  # (B, T)
        for rij, valid in zip(rij_t, valid_t):
            r = jnp.sqrt(jnp.sum(jnp.where(valid[..., None], rij, 1.0) ** 2,
                                 -1))
            s = jnp.where(valid, switching_s(r, model["rcut_smth"],
                                             model["rcut"]), 0.0)
            s2_acc = s2_acc + (s * s).sum(axis=1) @ onehot
            cnt_acc = cnt_acc + valid.sum(axis=1).astype(pos.dtype) @ onehot
        return (s2_acc, cnt_acc), None

    init = (jnp.zeros(ntypes, jnp.float32), jnp.zeros(ntypes, jnp.float32))
    (s2, cnt), _ = jax.lax.scan(one, init, jnp.arange(pos.shape[0] // block))
    return s2, cnt


def env_stats(model, pos, typ, box, lists, block: int = 256):
    """dstd (ntypes, 4): rms of the environment-matrix columns over every
    real neighbor of each center type (the radial column apart, the three
    angular ones pooled: their squares add up to s^2), at least 1e-2 --
    as DeePMD takes it from data."""
    block = math.gcd(pos.shape[0], block)
    s2, cnt = _s2_sums(jnp.asarray(pos, jnp.float32),
                       jnp.asarray(typ, jnp.int32),
                       jnp.asarray(box, jnp.float32),
                       [jnp.asarray(x) for x in lists],
                       model_key=model_key(model), block=block)
    ms = np.asarray(s2, np.float64) / np.maximum(np.asarray(cnt), 1.0)
    rad, ang = np.sqrt(ms), np.sqrt(ms / 3.0)
    return np.maximum(np.stack([rad, ang, ang, ang], axis=1), 1e-2) \
        .astype(np.float32)
