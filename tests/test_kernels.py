"""dp_fused Pallas kernel: shape/dtype sweeps + grads vs the ref.py oracle,
including hypothesis-generated ragged neighbor counts. Every call runs the
kernel in interpret mode; tests/test_tpu_compile.py compiles it for the
TPU."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.dp_fused import ops as fused_ops
from repro.kernels.dp_fused import ref as fused_ref

LOWER, UPPER = -1.0, 9.0


def _fused(env, s, coeffs, **kw):
    return fused_ops.fused_env_tab_contract(env, s, coeffs, LOWER, UPPER,
                                            interpret=True, **kw)


def _mk_inputs(key, a, n, k, m, dtype, counts=None):
    k1, k2, k3 = jax.random.split(key, 3)
    s = jax.random.uniform(k1, (a, n), dtype, 0.1, 8.0)
    env = jax.random.normal(k2, (a, n, 4), dtype) * 0.3
    if counts is not None:
        slot = jnp.arange(n)[None, :]
        mask = slot < jnp.asarray(counts)[:, None]
        s = s * mask
        env = env * mask[..., None]
    coeffs = jax.random.normal(k3, (k, m), dtype) * 0.1
    return s, env, coeffs


@pytest.mark.parametrize("a,n,k,m", [
    (8, 64, 16, 32), (16, 128, 48, 128), (5, 96, 32, 64), (1, 256, 96, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_fused_matches_oracle(a, n, k, m, dtype):
    s, env, coeffs = _mk_inputs(jax.random.PRNGKey(0), a, n, k, m, dtype)
    out = _fused(env, s, coeffs)
    ref = fused_ref.fused_env_tab_contract_ref(env, s, coeffs, LOWER, UPPER)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_fused_batch_dims():
    s, env, coeffs = _mk_inputs(jax.random.PRNGKey(1), 12, 64, 24, 32,
                                jnp.float32)
    s3 = s.reshape(3, 4, 64)
    env3 = env.reshape(3, 4, 64, 4)
    out = _fused(env3, s3, coeffs)
    assert out.shape == (3, 4, 4, 32)
    ref = fused_ref.fused_env_tab_contract_ref(env3, s3, coeffs, LOWER, UPPER)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_fused_grads_match_oracle_grads():
    s, env, coeffs = _mk_inputs(jax.random.PRNGKey(2), 8, 64, 24, 32,
                                jnp.float32)

    def loss_kernel(env, s):
        out = _fused(env, s, coeffs)
        return jnp.sum(jnp.sin(out))

    def loss_ref(env, s):
        out = fused_ref.fused_env_tab_contract_ref(env, s, coeffs, LOWER,
                                                   UPPER)
        return jnp.sum(jnp.sin(out))

    genv_k, gs_k = jax.grad(loss_kernel, argnums=(0, 1))(env, s)
    genv_r, gs_r = jax.grad(loss_ref, argnums=(0, 1))(env, s)
    np.testing.assert_allclose(np.asarray(genv_k), np.asarray(genv_r),
                               rtol=3e-4, atol=3e-5)
    np.testing.assert_allclose(np.asarray(gs_k), np.asarray(gs_r),
                               rtol=3e-4, atol=3e-5)


@settings(max_examples=12, deadline=None)
@given(
    a=st.integers(1, 12),
    n_pow=st.integers(4, 7),
    counts=st.data(),
)
def test_fused_ragged_counts_property(a, n_pow, counts):
    """Block-skipping correctness: any ragged per-atom count pattern gives
    the oracle's answer (padded slots are exact zeros by the env invariant)."""
    n = 2 ** n_pow
    cts = counts.draw(st.lists(st.integers(0, n), min_size=a, max_size=a))
    s, env, coeffs = _mk_inputs(jax.random.PRNGKey(3), a, n, 16, 32,
                                jnp.float32, counts=cts)
    out = _fused(env, s, coeffs)
    ref = fused_ref.fused_env_tab_contract_ref(env, s, coeffs, LOWER, UPPER)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_block_skipping_actually_skips():
    """Tiles past each atom-tile's count must not contribute: poison padded
    env rows with NaN — if a skipped tile were computed unmasked the NaNs
    would propagate into the accumulator."""
    a, n = 8, 128
    s, env, coeffs = _mk_inputs(jax.random.PRNGKey(4), a, n, 16, 32,
                                jnp.float32, counts=[32] * a)
    # block_n=64 is legal only in interpret mode (the TPU needs 128)
    kw = dict(block_a=8, block_n=64)     # tiles: [0,64) live, [64,128) skipped
    ref = _fused(env, s, coeffs, **kw)
    # s==0 marks padding; env NaNs live ONLY in the fully-skipped tile
    env_poison = env.at[:, 64:, :].set(jnp.nan)
    out = _fused(env_poison, s, coeffs, **kw)
    assert not bool(jnp.isnan(out).any())
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def _grads(fn, env, s, coeffs, **kw):
    def loss(env, s):
        return jnp.sum(jnp.sin(fn(env, s, coeffs, **kw)))

    return jax.grad(loss, argnums=(0, 1))(env, s)


def _ref(env, s, coeffs):
    return fused_ref.fused_env_tab_contract_ref(env, s, coeffs, LOWER, UPPER)


def test_fused_copper_shape_ragged_matches_oracle():
    """Copper's widths (K=32, M=128, 512 slots) with three atom tiles whose
    ragged counts leave 1, 2 and 3 of the four neighbor tiles live: T and
    both gradients. At this size the f32 oracle's own rounding of ds (sums
    of ~400 terms) passes atol, so the oracle runs in float64 here. The env
    cotangent is compared on the real slots; past a tile's last live slot
    the kernel writes 0 (padding env rows are constants of the model, and
    its mask drops their cotangent)."""
    counts = [17, 100, 128, 3, 0, 64, 90, 1,            # 1 live tile
              129, 255, 40, 200, 7, 131, 0, 250,        # 2
              256, 384, 300, 12, 380, 260, 200, 399]    # 3
    a, n = len(counts), 512
    s, env, coeffs = _mk_inputs(jax.random.PRNGKey(5), a, n, 32, 128,
                                jnp.float32, counts=counts)
    out = _fused(env, s, coeffs)
    genv_k, gs_k = _grads(_fused, env, s, coeffs)
    with jax.enable_x64(True):
        x64 = [jnp.asarray(np.asarray(x), jnp.float64)
               for x in (env, s, coeffs)]
        out_r = np.asarray(_ref(*x64))
        genv_r, gs_r = (np.asarray(g) for g in _grads(_ref, *x64))
    np.testing.assert_allclose(np.asarray(out), out_r, rtol=2e-5, atol=2e-5)
    real = (np.arange(n)[None, :] < np.asarray(counts)[:, None])[..., None]
    np.testing.assert_allclose(np.where(real, np.asarray(genv_k), 0.0),
                               np.where(real, genv_r, 0.0),
                               rtol=3e-4, atol=3e-5)
    np.testing.assert_allclose(np.asarray(gs_k), gs_r, rtol=3e-4, atol=3e-5)


def test_block_skipping_backward_writes_zeros():
    """The backward skips the same tiles: with NaN env rows in the skipped
    tile, ds and the env cotangent there are exactly 0, and the live tile's
    are the unpoisoned run's."""
    a, n = 8, 128
    s, env, coeffs = _mk_inputs(jax.random.PRNGKey(6), a, n, 16, 32,
                                jnp.float32, counts=[32] * a)
    kw = dict(block_a=8, block_n=64)     # tiles: [0,64) live, [64,128) skipped
    genv_ref, gs_ref = _grads(_fused, env, s, coeffs, **kw)
    env_poison = env.at[:, 64:, :].set(jnp.nan)
    genv, gs = _grads(_fused, env_poison, s, coeffs, **kw)
    assert np.all(np.asarray(genv[:, 64:]) == 0.0)
    assert np.all(np.asarray(gs[:, 64:]) == 0.0)
    np.testing.assert_array_equal(np.asarray(genv), np.asarray(genv_ref))
    np.testing.assert_array_equal(np.asarray(gs), np.asarray(gs_ref))
