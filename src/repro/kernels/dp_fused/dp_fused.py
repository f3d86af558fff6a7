"""Pallas TPU kernels: fused Chebyshev tabulation + R~^T G contraction,
contracted in Chebyshev space so that G is never formed.

The table C (K, M) is shared by every pair of a type section, so for each
atom a

    T_a = sum_n R~_an (x) (B_an C) = (sum_n R~_an (x) B_an) C = P_a C,

with B_an = [T_0(u_an) .. T_{K-1}(u_an)] the Chebyshev basis and P_a only
(4, K). The backward is the same reassociation with Q_a = dT_a C^T (4, K):

    dR~_an,i = sum_k B_ank Q_aik
    ds_an    = du/ds [|u_raw| < 1] sum_k B'_ank sum_i R~_ani Q_aik

Operands are lane-dense along neighbors: s (A, N), env and dR~ (4, A, N),
so every per-pair quantity of a (TA, TN) tile is a few (8, 128) vregs.

Dataflow per (atom tile i, neighbor tile j) grid cell, all on the VPU:

    forward:  s tile --recurrence--> T_k (TA, TN), k < K
              acc[c, k] += R~_c * T_k                 [lane-partial P, VMEM]
              last j:  T_c = sum_k (lane-sum acc[c, k]) C[k, :]  --> out
    backward: first j: Q_c[:, k] = lane-sum(dT_c * C[k, :]), broadcast
                       along lanes                    [VMEM, per atom tile]
              s tile --recurrence--> T_k, T'_k
              dR~_c = sum_k Q[c, k] T_k;  ds = sum_k T'_k sum_c R~_c Q[c, k]

Redundancy removal: per-atom-tile real-neighbor counts are scalar-prefetched;
neighbor tiles with j*TN >= count are skipped entirely (`pl.when`). Padded
slots inside a live tile need no masking because padded env rows are exactly
zero (descriptor invariant), so their contribution vanishes.

Grid iteration: atom tiles are "parallel"; the neighbor dimension is
"arbitrary" (sequential), so the per-atom-tile VMEM scratch (init at j == 0,
used after) is sound.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scoped-VMEM budget for both kernels; the (4, K, TA, TN) scratch is the
# largest buffer (8 MiB at K=32 and a (64, 256) tile). A v5e TensorCore
# has 128 MiB.
VMEM_LIMIT_BYTES = 48 * 1024 * 1024
# Atoms per inner step: one (8, 128) f32 vreg per array and neighbor chunk.
ROWS = 8
# Chebyshev terms per step of the in-kernel loops over k: short enough that
# tracing and lowering the kernels stays cheap.
UNROLL = 4


def _u_of_s(s: jax.Array, lower: float, upper: float):
    u_raw = (2.0 * s - lower - upper) / (upper - lower)
    return jnp.clip(u_raw, -1.0, 1.0), u_raw


def _row_groups(block_a: int, body):
    """Run ``body(rows)`` over the tile's groups of ``ROWS`` atoms, so that
    every per-pair array in ``body`` is one vreg per neighbor chunk whatever
    the atom tile's height."""
    def step(g, carry):
        body(pl.ds(pl.multiple_of(g * ROWS, ROWS), ROWS))
        return carry

    jax.lax.fori_loop(0, block_a // ROWS, step, 0)


def _over_k(order: int, body, carry):
    """``carry = body(k, carry)`` for k < order, ``UNROLL`` terms a step
    (Mosaic unrolls a loop fully or not at all)."""
    n = math.gcd(order, UNROLL)

    def step(g, carry):
        for r in range(n):
            carry = body(g * n + r, carry)
        return carry

    return jax.lax.fori_loop(0, order // n, step, carry)


# The Chebyshev recurrences T_{k+1} = 2u T_k - T_{k-1} and
# T'_{k+1} = 2 T_k + 2u T'_k - T'_{k-1} run from k = 0 with T_{-1} = T_1 = u
# and T'_{-1} = T'_1 = 1, so every k takes the same step.
def _cheb_start(u):
    return u, jnp.ones_like(u)                              # T_{-1}, T_0


def _cheb_step(u, t_prev, t_cur):
    return t_cur, 2.0 * u * t_cur - t_prev


def _fwd_kernel(counts_ref, s_ref, env_ref, c_ref, out_ref, acc_ref,
                *, lower, upper):
    i = pl.program_id(0)
    j = pl.program_id(1)
    block_a, block_n = s_ref.shape
    order, m = c_ref.shape

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block_n < counts_ref[i])
    def _accumulate():
        def body(rows):
            u, _ = _u_of_s(s_ref[rows, :], lower, upper)
            env = [env_ref[c, rows, :] for c in range(4)]

            def term(k, ts):                                # ts: T_{k-1}, T_k
                for c in range(4):
                    acc_ref[c, k, rows, :] += env[c] * ts[1]
                return _cheb_step(u, *ts)

            _over_k(order, term, _cheb_start(u))

        _row_groups(block_a, body)

    @pl.when(j == pl.num_programs(1) - 1)
    def _contract():
        def body(rows):
            def term(k, out):
                c_k = c_ref[pl.ds(k, 1), :]                        # (1, M)
                return tuple(
                    out[c] + jnp.sum(acc_ref[c, k, rows, :], axis=-1,
                                     keepdims=True) * c_k
                    for c in range(4))

            out = _over_k(order, term, tuple(
                jnp.zeros((ROWS, m), jnp.float32) for _ in range(4)))
            for c in range(4):
                out_ref[c, rows, :] = out[c].astype(out_ref.dtype)

        _row_groups(block_a, body)


def _bwd_kernel(counts_ref, s_ref, env_ref, c_ref, dt_ref, ds_ref, denv_ref,
                q_ref, *, lower, upper):
    i = pl.program_id(0)
    j = pl.program_id(1)
    block_a, block_n = s_ref.shape
    order, m = c_ref.shape
    live = j * block_n < counts_ref[i]

    @pl.when(j == 0)
    def _init():
        def body(rows):
            dt = [dt_ref[c, rows, :] for c in range(4)]            # (ROWS, M)

            def term(k, carry):
                c_k = c_ref[pl.ds(k, 1), :]
                for c in range(4):
                    q = jnp.sum(dt[c] * c_k, axis=-1, keepdims=True)
                    q_ref[c, k, rows, :] = jnp.broadcast_to(q, (ROWS, block_n))
                return carry

            _over_k(order, term, 0)

        _row_groups(block_a, body)

    @pl.when(jnp.logical_not(live))
    def _skip():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        denv_ref[...] = jnp.zeros_like(denv_ref)

    @pl.when(live)
    def _compute():
        def body(rows):
            u, u_raw = _u_of_s(s_ref[rows, :], lower, upper)
            env = [env_ref[c, rows, :] for c in range(4)]

            def term(k, carry):
                ts, tp_prev, tp_cur, denv, ds = carry    # tp: T'_{k-1}, T'_k
                q = [q_ref[c, k, rows, :] for c in range(4)]
                rk = env[0] * q[0] + env[1] * q[1] + env[2] * q[2] \
                    + env[3] * q[3]
                denv = tuple(denv[c] + q[c] * ts[1] for c in range(4))
                ds = ds + tp_cur * rk
                tp_next = 2.0 * ts[1] + 2.0 * u * tp_cur - tp_prev
                return _cheb_step(u, *ts), tp_cur, tp_next, denv, ds

            zero = jnp.zeros_like(u)
            _, _, _, denv, ds = _over_k(order, term, (
                _cheb_start(u), jnp.ones_like(u), zero, (zero,) * 4, zero))
            du_ds = 2.0 / (upper - lower)
            in_dom = (jnp.abs(u_raw) < 1.0).astype(ds.dtype)
            ds_ref[rows, :] = (ds * du_ds * in_dom).astype(ds_ref.dtype)
            for c in range(4):
                denv_ref[c, rows, :] = denv[c].astype(denv_ref.dtype)

        _row_groups(block_a, body)


def _grid_and_specs(a_pad: int, n_pad: int, m: int, order: int,
                    block_a: int, block_n: int):
    grid = (a_pad // block_a, n_pad // block_n)
    # index_map signature with scalar prefetch: (i, j, counts_ref).
    s_spec = pl.BlockSpec((block_a, block_n), lambda i, j, _: (i, j))
    env_spec = pl.BlockSpec((4, block_a, block_n), lambda i, j, _: (0, i, j))
    c_spec = pl.BlockSpec((order, m), lambda i, j, _: (0, 0))
    t_spec = pl.BlockSpec((4, block_a, m), lambda i, j, _: (0, i, 0))
    return grid, s_spec, env_spec, c_spec, t_spec


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES,
    )


@functools.partial(
    jax.jit,
    static_argnames=("lower", "upper", "block_a", "block_n", "interpret"),
)
def fused_fwd(
    s: jax.Array,            # (A, N) normalized s, zero-padded
    env: jax.Array,          # (4, A, N) env matrix, zero for padding
    coeffs: jax.Array,       # (K, M)
    tile_counts: jax.Array,  # (A // block_a,) int32 max real count per tile
    *,
    lower: float,
    upper: float,
    block_a: int,
    block_n: int,
    interpret: bool,
) -> jax.Array:
    """T (4, A, M): T[c, a] = sum_n env[c, a, n] G(s[a, n])."""
    a_pad, n_pad = s.shape
    order, m = coeffs.shape
    grid, s_spec, env_spec, c_spec, t_spec = _grid_and_specs(
        a_pad, n_pad, m, order, block_a, block_n)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, lower=lower, upper=upper),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[s_spec, env_spec, c_spec],
            out_specs=t_spec,
            scratch_shapes=[
                pltpu.VMEM((4, order, block_a, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((4, a_pad, m), s.dtype),
        compiler_params=_compiler_params(),
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
    dt: jax.Array,           # (4, A, M) cotangent of T
    *,
    lower: float,
    upper: float,
    block_a: int,
    block_n: int,
    interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
    """(ds (A, N), denv (4, A, N)), the cotangents of s and env."""
    a_pad, n_pad = s.shape
    order, m = coeffs.shape
    grid, s_spec, env_spec, c_spec, t_spec = _grid_and_specs(
        a_pad, n_pad, m, order, block_a, block_n)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, lower=lower, upper=upper),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[s_spec, env_spec, c_spec, t_spec],
            out_specs=[s_spec, env_spec],
            scratch_shapes=[
                pltpu.VMEM((4, order, block_a, block_n), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((a_pad, n_pad), s.dtype),
            jax.ShapeDtypeStruct((4, a_pad, n_pad), env.dtype),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(tile_counts, s, env, coeffs, dt)
