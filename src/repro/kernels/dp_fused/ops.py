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

import jax
import jax.numpy as jnp

from repro.kernels.dp_fused import dp_fused

DEFAULT_BLOCK_A = 8
# (8, 128) is the smallest tile the TPU lowering accepts for the s block.
DEFAULT_BLOCK_N = 128


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


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
    a, n = s.shape
    s_p = _pad_to(_pad_to(s, 0, block_a), 1, block_n)
    env_p = _pad_to(_pad_to(env, 0, block_a), 1, block_n)
    counts = _tile_counts(s_p, block_a)
    out = dp_fused.fused_fwd(
        s_p, env_p, coeffs, counts,
        lower=lower, upper=upper, block_a=block_a, block_n=block_n,
        interpret=interpret,
    )[:a]
    return out, (env, s, coeffs)


def _fused_bwd(lower, upper, block_a, block_n, interpret, res, dt):
    env, s, coeffs = res
    a, n = s.shape
    s_p = _pad_to(_pad_to(s, 0, block_a), 1, block_n)
    env_p = _pad_to(_pad_to(env, 0, block_a), 1, block_n)
    counts = _tile_counts(s_p, block_a)
    dt_p = _pad_to(dt, 0, block_a)
    ds, denv = dp_fused.fused_bwd(
        s_p, env_p, coeffs, counts, dt_p,
        lower=lower, upper=upper, block_a=block_a, block_n=block_n,
        interpret=interpret,
    )
    # Tables are frozen artifacts: zero cotangent (training uses impl="mlp").
    return denv[:a, :n], ds[:a, :n], jnp.zeros_like(coeffs)


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
    """T = R~^T G, G tabulated on the fly (never materialized in HBM).

    env: (..., N, 4); s: (..., N); coeffs: (K, M). Returns (..., 4, M).
    Leading batch dims are flattened into the atom axis. ``interpret=True``
    runs the kernel through the Pallas interpreter (any backend).
    """
    batch_shape = s.shape[:-1]
    n = s.shape[-1]
    env2 = env.reshape(-1, n, 4)
    s2 = s.reshape(-1, n)
    coeffs = jax.lax.stop_gradient(coeffs)
    out = _fused(env2, s2, coeffs, float(lower), float(upper),
                 int(block_a), int(block_n), bool(interpret))
    m = coeffs.shape[1]
    return out.reshape(*batch_shape, 4, m)
