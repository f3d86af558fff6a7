"""jit'd public wrapper for the fused kernel: padding, counts, custom VJP.

Notes:
  * Tables are post-training artifacts (paper Sec. 3.2); gradients do not
    flow into the Chebyshev coefficients (stop_gradient) — training always
    runs impl="mlp". Forces = dE/dpositions DO flow through s and env via
    the custom VJP (the paper evaluates forces in backward propagation
    through the tabulated model the same way).
  * ``interpret`` is always the caller's choice: the model path compiles
    the kernel for the TPU (``DPConfig.kernel_interpret`` is False), and
    CPU tests ask for interpret mode themselves.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.dp_fused import dp_fused

# The (atoms, neighbor slots) tile of one grid step: the fastest of the
# tiles from (8, 128) to (128, 512) on one v5e at copper's shape (16,384
# atoms, 512 slots). ``tile`` cuts it to the operands' size.
DEFAULT_BLOCK_A = 64
DEFAULT_BLOCK_N = 256


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def tile(a: int, n: int, block_a: int = DEFAULT_BLOCK_A,
         block_n: int = DEFAULT_BLOCK_N) -> Tuple[int, int]:
    """The kernels' tile for ``a`` atoms and ``n`` slots: at most
    (block_a, block_n), and no larger than the operands rounded up to
    (8, 128), the smallest tile the TPU lowering accepts."""
    rows = dp_fused.ROWS
    return (min(block_a, -(-a // rows) * rows),
            min(block_n, -(-n // 128) * 128))


def _tile_counts(s: jax.Array, block_a: int) -> jax.Array:
    """Per-atom-tile upper bound on live neighbor slots (s != 0)."""
    a, n = s.shape
    slot = jnp.arange(1, n + 1, dtype=jnp.int32)
    per_atom = jnp.max(jnp.where(s != 0.0, slot, 0), axis=1)     # (A,)
    return jnp.max(per_atom.reshape(a // block_a, block_a), axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fused(env, s, coeffs, lower, upper, block_a, block_n, interpret):
    out, _ = _fused_fwd(env, s, coeffs, lower, upper, block_a, block_n, interpret)
    return out


def _fused_fwd(env, s, coeffs, lower, upper, block_a, block_n, interpret):
    a = s.shape[0]
    # The kernels' lane-dense layout, padded to whole tiles: s (A', N'),
    # env (4, A', N'); T comes back as (4, A', M).
    s_p = _pad_to(_pad_to(s, 0, block_a), 1, block_n)
    env_p = _pad_to(_pad_to(jnp.moveaxis(env, -1, 0), 1, block_a), 2, block_n)
    counts = _tile_counts(s_p, block_a)
    out = dp_fused.fused_fwd(
        s_p, env_p, coeffs, counts,
        lower=lower, upper=upper, block_a=block_a, block_n=block_n,
        interpret=interpret,
    )
    return jnp.moveaxis(out[:, :a], 0, 1), (s, s_p, env_p, counts, coeffs)


def _fused_bwd(lower, upper, block_a, block_n, interpret, res, dt):
    s, s_p, env_p, counts, coeffs = res
    a, n = s.shape
    dt_p = _pad_to(jnp.moveaxis(dt, 1, 0), 1, block_a)
    ds, denv = dp_fused.fused_bwd(
        s_p, env_p, coeffs, counts, dt_p,
        lower=lower, upper=upper, block_a=block_a, block_n=block_n,
        interpret=interpret,
    )
    # Tables are frozen artifacts: zero cotangent (training uses impl="mlp").
    return (jnp.moveaxis(denv[:, :a, :n], 0, -1), ds[:a, :n],
            jnp.zeros_like(coeffs))


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_env_tab_contract(
    env: jax.Array,
    s: jax.Array,
    coeffs: jax.Array,
    lower: float,
    upper: float,
    *,
    block_a: int = DEFAULT_BLOCK_A,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool,
) -> jax.Array:
    """T = R~^T G with G = ChebBasis(s) @ coeffs, computed as
    (R~^T ChebBasis(s)) @ coeffs: G is never formed.

    env: (..., N, 4); s: (..., N); coeffs: (K, M). Returns (..., 4, M).
    Leading batch dims are flattened into the atom axis. ``interpret=True``
    runs the kernel through the Pallas interpreter (any backend).
    """
    batch_shape = s.shape[:-1]
    n = s.shape[-1]
    env2 = env.reshape(-1, n, 4)
    s2 = s.reshape(-1, n)
    block_a, block_n = tile(s2.shape[0], n, int(block_a), int(block_n))
    coeffs = jax.lax.stop_gradient(coeffs)
    out = _fused(env2, s2, coeffs, float(lower), float(upper),
                 block_a, block_n, bool(interpret))
    m = coeffs.shape[1]
    return out.reshape(*batch_shape, 4, m)
