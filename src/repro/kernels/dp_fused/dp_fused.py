"""Pallas TPU kernels: fused Chebyshev tabulation + R~^T G contraction.

Dataflow per (atom-tile i, neighbor-tile j) grid cell:

    s tile (TA, TN)  --VPU recurrence-->  basis B (TA, TN, K)
    B @ C (MXU)      -->  G tile (TA, TN, M)        [VMEM only, never HBM]
    env tile (TA, TN, 4) ^T G tile (MXU, batched)  -->  += out (TA, 4, M)

Redundancy removal: per-atom-tile real-neighbor counts are scalar-prefetched;
neighbor tiles with j*TN >= count are skipped entirely (`pl.when`). Padded
slots inside a live tile need no masking because padded env rows are exactly
zero (descriptor invariant), so their contraction contribution vanishes.

Grid iteration: atom tiles are "parallel"; the neighbor dimension is
"arbitrary" (sequential) so the VMEM accumulator pattern (init at j==0,
accumulate after) is sound.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scoped-VMEM budget for both kernels. At copper width (K=32, M=128, one
# (8, 128) tile) the compiler asks for ~16.1 MiB forward and ~17.1 MiB
# backward, just past its 16 MiB default; a v5e TensorCore has 128 MiB.
VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def _cheb_basis_pair(u: jax.Array, order: int, with_deriv: bool):
    """T_k(u) (and optionally T_k'(u)) for k < order, stacked on axis -1."""
    t_prev = jnp.ones_like(u)
    t_cur = u
    ts = [t_prev, t_cur]
    if with_deriv:
        d_prev = jnp.zeros_like(u)
        d_cur = jnp.ones_like(u)
        ds = [d_prev, d_cur]
    for _ in range(order - 2):
        t_next = 2.0 * u * t_cur - t_prev
        if with_deriv:
            d_next = 2.0 * t_cur + 2.0 * u * ds[-1] - ds[-2]
            ds.append(d_next)
        t_prev, t_cur = t_cur, t_next
        ts.append(t_cur)
    basis = jnp.stack(ts[:order], axis=-1)
    if with_deriv:
        return basis, jnp.stack(ds[:order], axis=-1)
    return basis, None


def _u_of_s(s: jax.Array, lower: float, upper: float):
    u_raw = (2.0 * s - lower - upper) / (upper - lower)
    return jnp.clip(u_raw, -1.0, 1.0), u_raw


def _fwd_kernel(counts_ref, s_ref, env_ref, c_ref, out_ref, *, lower, upper):
    i = pl.program_id(0)
    j = pl.program_id(1)
    block_n = s_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(j * block_n < counts_ref[i])
    def _compute():
        order, m = c_ref.shape
        ta, tn = s_ref.shape
        u, _ = _u_of_s(s_ref[...], lower, upper)
        basis, _ = _cheb_basis_pair(u, order, with_deriv=False)   # (TA, TN, K)
        g = jax.lax.dot_general(
            basis.reshape(ta * tn, order), c_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(ta, tn, m)
        part = jax.lax.dot_general(
            env_ref[...], g,
            (((1,), (1,)), ((0,), (0,))),                          # contract TN
            preferred_element_type=jnp.float32,
        )                                                           # (TA, 4, M)
        out_ref[...] += part.astype(out_ref.dtype)


def _bwd_kernel(counts_ref, s_ref, env_ref, c_ref, dt_ref, ds_ref, denv_ref,
                *, lower, upper):
    i = pl.program_id(0)
    j = pl.program_id(1)
    block_n = s_ref.shape[1]
    live = j * block_n < counts_ref[i]

    @pl.when(jnp.logical_not(live))
    def _skip():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        denv_ref[...] = jnp.zeros_like(denv_ref)

    @pl.when(live)
    def _compute():
        order, m = c_ref.shape
        ta, tn = s_ref.shape
        u, u_raw = _u_of_s(s_ref[...], lower, upper)
        basis, dbasis = _cheb_basis_pair(u, order, with_deriv=True)
        c = c_ref[...]
        g = jax.lax.dot_general(
            basis.reshape(ta * tn, order), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).reshape(ta, tn, m)
        gp = jax.lax.dot_general(
            dbasis.reshape(ta * tn, order), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).reshape(ta, tn, m)

        dt = dt_ref[...]                                            # (TA, 4, M)
        # dL/denv[a,n,:] = G[a,n,:] @ dT[a]^T
        denv = jax.lax.dot_general(
            g, dt, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)                     # (TA, TN, 4)
        # W[a,n,:] = env[a,n,:] @ dT[a]; dL/ds = sum_m W * dG/ds
        w = jax.lax.dot_general(
            env_ref[...], dt, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)                     # (TA, TN, M)
        du_ds = 2.0 / (upper - lower)
        in_dom = (jnp.abs(u_raw) < 1.0).astype(w.dtype)
        ds = jnp.sum(w * gp, axis=-1) * du_ds * in_dom
        ds_ref[...] = ds.astype(ds_ref.dtype)
        denv_ref[...] = denv.astype(denv_ref.dtype)


def _grid_and_specs(a_pad: int, n_pad: int, m: int, order: int,
                    block_a: int, block_n: int):
    grid = (a_pad // block_a, n_pad // block_n)
    # index_map signature with scalar prefetch: (i, j, counts_ref).
    s_spec = pl.BlockSpec((block_a, block_n), lambda i, j, _: (i, j))
    env_spec = pl.BlockSpec((block_a, block_n, 4), lambda i, j, _: (i, j, 0))
    c_spec = pl.BlockSpec((order, m), lambda i, j, _: (0, 0))
    return grid, s_spec, env_spec, c_spec


@functools.partial(
    jax.jit,
    static_argnames=("lower", "upper", "block_a", "block_n", "interpret"),
)
def fused_fwd(
    s: jax.Array,            # (A, N) normalized s, zero-padded
    env: jax.Array,          # (A, N, 4) env matrix, zero rows for padding
    coeffs: jax.Array,       # (K, M)
    tile_counts: jax.Array,  # (A // block_a,) int32 max real count per tile
    *,
    lower: float,
    upper: float,
    block_a: int,
    block_n: int,
    interpret: bool,
) -> jax.Array:
    a_pad, n_pad = s.shape
    order, m = coeffs.shape
    grid, s_spec, env_spec, c_spec = _grid_and_specs(
        a_pad, n_pad, m, order, block_a, block_n)
    out_spec = pl.BlockSpec((block_a, 4, m), lambda i, j, _: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, lower=lower, upper=upper),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[s_spec, env_spec, c_spec],
            out_specs=out_spec,
        ),
        out_shape=jax.ShapeDtypeStruct((a_pad, 4, m), s.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(tile_counts, s, env, coeffs)


@functools.partial(
    jax.jit,
    static_argnames=("lower", "upper", "block_a", "block_n", "interpret"),
)
def fused_bwd(
    s: jax.Array,
    env: jax.Array,
    coeffs: jax.Array,
    tile_counts: jax.Array,
    dt: jax.Array,           # (A, 4, M) cotangent of T
    *,
    lower: float,
    upper: float,
    block_a: int,
    block_n: int,
    interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
    a_pad, n_pad = s.shape
    order, m = coeffs.shape
    grid, s_spec, env_spec, c_spec = _grid_and_specs(
        a_pad, n_pad, m, order, block_a, block_n)
    dt_spec = pl.BlockSpec((block_a, 4, m), lambda i, j, _: (i, 0, 0))
    ds_spec = pl.BlockSpec((block_a, block_n), lambda i, j, _: (i, j))
    denv_spec = pl.BlockSpec((block_a, block_n, 4), lambda i, j, _: (i, j, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, lower=lower, upper=upper),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[s_spec, env_spec, c_spec, dt_spec],
            out_specs=[ds_spec, denv_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((a_pad, n_pad), s.dtype),
            jax.ShapeDtypeStruct((a_pad, n_pad, 4), env.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(tile_counts, s, env, coeffs, dt)
