"""Deep Potential model assembly: energy, forces, virial; impl dispatch.

The implementation ladder follows the paper's optimization story:

  impl="mlp"         baseline — full embedding-net matmuls, G materialized
  impl="quintic"     + Sec. 3.2 tabulation (fifth-order polynomials)
  impl="cheb"        + TPU-adapted Chebyshev tabulation (basis matmul)
  impl="cheb_pallas" + Sec. 3.4.1 kernel fusion and Sec. 3.4.2 redundancy
                       removal (Pallas kernel; G never materialized)

Each sublayer runs under a ``jax.named_scope`` that profiler traces show
on its device ops: ``dp.env``, ``dp.embed``, ``dp.fitting`` and
``dp.scatter`` (autodiff names their backward ``transpose(jvp(dp.*))``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import descriptor, embedding, fitting, tabulation
from repro.core.types import DPConfig


def _dtype(cfg: DPConfig):
    return jnp.dtype(cfg.dtype)


def init_dp_params(key: jax.Array, cfg: DPConfig, dstd: Optional[jax.Array] = None) -> Dict[str, Any]:
    """Initialize a Deep Potential parameter pytree."""
    cfg.validate()
    dt = _dtype(cfg)
    k_embed, k_fit = jax.random.split(key)
    if dstd is None:
        dstd = jnp.ones((cfg.ntypes, 4), dt)
    return {
        "embed": embedding.init_embedding_params(k_embed, cfg, dt),
        "fit": fitting.init_fitting_params(k_fit, cfg, dt),
        "dstd": dstd.astype(dt),
        "ebias": jnp.zeros((cfg.ntypes,), dt),
    }


def tabulate_model(params: Dict[str, Any], cfg: DPConfig, kind: str = "quintic",
                   step: Optional[float] = None, order: Optional[int] = None) -> Dict[str, Any]:
    """Compress the embedding nets into tables (paper Sec. 3.2 post-processing).

    Returns a new params pytree with a "table" entry; the embedding MLP
    weights are retained (oracle / fallback) but unused by tabulated impls.
    """
    tables = {}
    for idx, net in params["embed"].items():
        g = embedding.embedding_scalar_fn(net)
        # Built once, so always at f32 accuracy: at the TPU's default (one
        # bf16 pass per matmul) the K=32 Chebyshev coefficients carry
        # rounding noise that differentiation amplifies, 5.7e-2 rms-relative
        # force error at copper width.
        with jax.default_matmul_precision("highest"):
            if kind == "quintic":
                tables[idx] = tabulation.build_quintic_table(
                    g, cfg.table_lower, cfg.table_upper, step or cfg.table_step
                )
            elif kind == "cheb":
                tables[idx] = tabulation.build_cheb_table(
                    g, cfg.table_lower, cfg.table_upper, order or cfg.cheb_order
                )
            else:
                raise ValueError(f"unknown table kind {kind}")
    out = dict(params)
    out["table"] = {"nets": tables}   # kind is carried by cfg.impl / impl arg
    return out


def _g_section(params: Dict[str, Any], cfg: DPConfig, impl: str, net_idx: int,
               s_n: jax.Array) -> jax.Array:
    """Embedding matrix section G (..., sel_t, M) for one embedding-net index."""
    key = str(net_idx)
    if impl == "mlp":
        return embedding.embed_net_apply(params["embed"][key], s_n)
    table = params["table"]["nets"][key]
    if impl == "quintic":
        return tabulation.quintic_eval(table, s_n)
    if impl == "cheb":
        return tabulation.cheb_eval(table, s_n)
    raise ValueError(f"impl {impl} not handled here")


def _t_matrix_onetype(params, cfg: DPConfig, impl: str, center_type: int,
                      env_n: jax.Array, s_n: jax.Array) -> jax.Array:
    """T = R~^T G (..., 4, M) for a fixed center type (paper's fused target)."""
    sections = cfg.sel_sections()
    t_parts = []
    for nbr_type, (a, b) in enumerate(sections):
        idx = embedding.embed_index(cfg, center_type, nbr_type)
        env_sec = env_n[..., a:b, :]                     # (..., sel_t, 4)
        s_sec = s_n[..., a:b]
        if impl == "cheb_pallas":
            from repro.kernels.dp_fused import ops as dp_fused_ops

            table = params["table"]["nets"][str(idx)]
            # Domain bounds are static (from cfg), not traced pytree leaves.
            t_parts.append(dp_fused_ops.fused_env_tab_contract(
                env_sec, s_sec, table["coeffs"],
                cfg.table_lower, cfg.table_upper,
                interpret=cfg.kernel_interpret,
            ))
        else:
            g_sec = _g_section(params, cfg, impl, idx, s_sec)   # (..., sel_t, M)
            t_parts.append(jnp.einsum("...na,...nm->...am", env_sec, g_sec))
    return sum(t_parts)


def dp_atomic_energy(params: Dict[str, Any], cfg: DPConfig, rij: jax.Array,
                     nmask: jax.Array, atype: jax.Array,
                     impl: Optional[str] = None,
                     axis_name: Optional[str] = None,
                     nsel_norm: Optional[int] = None) -> jax.Array:
    """Per-atom potential energies E_i.

    Args:
      rij:   (..., Na, Nm, 3) relative neighbor positions (ghost-resolved).
      nmask: (..., Na, Nm) neighbor validity.
      atype: (..., Na) center atom types.
      axis_name: neighbor-dimension force decomposition (distributed MD):
        each shard of this mesh axis holds a SLICE of every atom's neighbor
        list (cfg.sel describes the slice); the partial T matrices are
        psum-reduced before the descriptor. 95% of the FLOPs (the embedding)
        split across the axis.
      nsel_norm: global neighbor capacity for descriptor normalization when
        cfg.sel is a per-shard slice.
    """
    impl = impl or cfg.impl
    with jax.named_scope("dp.env"):
        env, s = descriptor.env_matrix(rij, nmask, cfg.rcut_smth, cfg.rcut)
        env_n, s_n = descriptor.normalize_env(env, s, atype, params["dstd"])

    with jax.named_scope("dp.embed"):
        if cfg.ntypes == 1 or cfg.type_one_side:
            t_mat = _t_matrix_onetype(params, cfg, impl, 0, env_n, s_n)
        else:
            t_mat = None
            for ct in range(cfg.ntypes):
                t_ct = _t_matrix_onetype(params, cfg, impl, ct, env_n, s_n)
                sel = (atype == ct)[..., None, None]
                t_mat = jnp.where(sel, t_ct, t_mat) if t_mat is not None else jnp.where(sel, t_ct, 0.0)

    if axis_name is not None:
        t_mat = jax.lax.psum(t_mat, axis_name)
    with jax.named_scope("dp.fitting"):
        d = descriptor.descriptor_from_t(t_mat, cfg.axis_neuron,
                                         nsel_norm or cfg.nsel)
        e_i = fitting.fitting_energy(params["fit"], cfg, d, atype)
        return e_i + params["ebias"][atype]


def dp_energy(params: Dict[str, Any], cfg: DPConfig, rij: jax.Array,
              nmask: jax.Array, atype: jax.Array, amask: jax.Array,
              impl: Optional[str] = None,
              nsel_norm: Optional[int] = None) -> jax.Array:
    """Total energy E = sum_i E_i over valid atoms."""
    e_i = dp_atomic_energy(params, cfg, rij, nmask, atype, impl,
                           nsel_norm=nsel_norm)
    with jax.named_scope("dp.fitting"):
        return jnp.sum(e_i * amask, axis=(-1,))


def gather_rij(pos: jax.Array, nlist: jax.Array, box: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """Relative positions from a neighbor index list.

    nlist: (Na, Nm) int32 indices into pos, -1 for padding. With ``box``
    (orthorhombic lengths (3,)), the minimum-image convention is applied —
    used by single-process MD; the distributed path resolves images via
    ghost atoms instead.
    """
    nmask = nlist >= 0
    j = jnp.maximum(nlist, 0)
    rij = pos[j] - pos[:, None, :]
    if box is not None:
        rij = rij - box * jnp.round(rij / box)
    rij = jnp.where(nmask[..., None], rij, 0.0)
    return rij, nmask


@functools.partial(jax.jit, static_argnames=("cfg", "impl", "nsel_norm"))
def dp_energy_forces(params: Dict[str, Any], cfg: DPConfig, pos: jax.Array,
                     nlist: jax.Array, atype: jax.Array,
                     box: Optional[jax.Array] = None,
                     impl: Optional[str] = None,
                     nsel_norm: Optional[int] = None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Single-process energy, forces, virial.

    Forces come from reverse-mode autodiff (the paper's backward
    propagation); the virial is the pair-wise contraction
    W = -sum_ij r_ij (x) dE/dr_ij (the analogue of ProdVirialSeA).

    ``nsel_norm`` pins the descriptor normalization to a model's native
    neighbor capacity when ``cfg.sel`` has been escalated past it (the
    overflow fault-tolerance path): capacities change, physics does not.
    """
    amask = jnp.ones(pos.shape[0], _dtype(cfg))

    def e_of_rij(rij, nmask):
        return dp_energy(params, cfg, rij, nmask, atype, amask, impl,
                         nsel_norm=nsel_norm)

    with jax.named_scope("dp.env"):
        rij, nmask = gather_rij(pos, nlist, box)
    e, de_drij = jax.value_and_grad(e_of_rij)(rij, nmask)

    with jax.named_scope("dp.scatter"):
        # Pair forces: f_ij = -dE/dr_ij acts on atom j, reaction +dE/dr_ij
        # on i.
        f = jnp.zeros_like(pos)
        nmaskf = nmask[..., None].astype(de_drij.dtype)
        f = f.at[jnp.maximum(nlist, 0)].add(-de_drij * nmaskf)
        f = f + jnp.sum(de_drij * nmaskf, axis=1)

        virial = -jnp.einsum("ijk,ijl->kl", rij, de_drij * nmaskf)
    return e, f, virial
