"""Ahead-of-time compiles for a described TPU v5e, at the paper's widths.

Nothing runs: the TPU compiler that ships with libtpu compiles for a chip
that is described, not attached. That catches what interpret mode cannot --
a kernel asking for more scoped VMEM than the compiler allows, a program
that does not fit the chip's 16 GiB of HBM -- at no chip time.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load libtpu, and every
test worker imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import dp_model
from repro.core.types import COPPER_DP, WATER_DP
from repro.kernels.dp_fused import dp_fused, ops as fused_ops
from repro.md import api, driver, lattice, stepper

HBM_BYTES = 16 * 2**30          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    """A single described v5e device; the persistent compile cache is off
    meanwhile (entries compiled for a described chip cannot be read back
    without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits_chip(compiled) -> int:
    ma = compiled.memory_analysis()
    total = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert total <= HBM_BYTES, f"{total / 2**30:.2f} GiB > 16 GiB"
    return total


# copper's one 512-slot section; water's O (46) and H (92) sections
@pytest.mark.parametrize("sel", [COPPER_DP.sel[0], *WATER_DP.sel])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_dp_fused_compiles_for_v5e(one_chip, sel, direction):
    """The fused kernel at the paper's widths (1024 atoms, K=32, M=128)
    with the wrapper's tile for the section, compiled for the TPU."""
    a, k, m = 1024, COPPER_DP.cheb_order, COPPER_DP.m_embed
    ba, bn = fused_ops.tile(a, sel)
    n_pad = -(-sel // bn) * bn
    args = [_shape(one_chip, (a, n_pad)), _shape(one_chip, (4, a, n_pad)),
            _shape(one_chip, (k, m)),
            _shape(one_chip, (a // ba,), jnp.int32)]
    fn = dp_fused.fused_fwd
    if direction == "bwd":
        args.append(_shape(one_chip, (4, a, m)))
        fn = dp_fused.fused_bwd
    kw = dict(lower=COPPER_DP.table_lower, upper=COPPER_DP.table_upper,
              block_a=ba, block_n=bn, interpret=False)
    compiled = jax.jit(lambda *x: fn(*x, **kw)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_chip(compiled)


def _copper_params(one_chip, pot):
    return jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype),
                        jax.eval_shape(pot.init_params,
                                       jax.random.PRNGKey(0)))


def test_copper_cheb_pallas_forces_compile_for_v5e(one_chip):
    """Full-width copper energy/forces/virial on the Pallas rung at 4,000
    atoms: the kernel is compiled in (no interpret mode) and fits."""
    n, cfg = 4000, COPPER_DP
    pot = api.make_potential("dp", cfg, impl="cheb_pallas")
    compiled = dp_model.dp_energy_forces.lower(
        _copper_params(one_chip, pot), cfg, _shape(one_chip, (n, 3)),
        _shape(one_chip, (n, cfg.nsel), jnp.int32),
        _shape(one_chip, (n,), jnp.int32), _shape(one_chip, (3,)),
        impl="cheb_pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_chip(compiled)


def test_copper_outer_chunk_16k_atoms_fits_v5e(one_chip):
    """The whole single-chip outer program (in-scan neighbor rebuild + 20
    Pallas-rung steps) at 16x16x16 FCC cells fits one chip. The neighbor
    search once built (N, C, 3) temporaries whose trailing 3 pads to 128
    lanes: 35 GiB at this size."""
    nc, cfg = 16, COPPER_DP
    n = 4 * nc ** 3
    _, _, box = lattice.fcc_copper(nc, nc, nc)
    pot = api.make_potential("dp", cfg, impl="cheb_pallas")
    spec = driver.neighbor_spec(pot, 2.0, n, box)
    eng = stepper.md_outer_engine(
        pot, api.NVE(), spec, stepper.grid_key_for(spec, np.asarray(box)),
        True, None)
    vec = _shape(one_chip, (n, 3))
    carry = stepper.OuterCarry(vec, vec, vec,
                               _shape(one_chip, (), jnp.int32), (),
                               _shape(one_chip, (3,)), ())
    compiled = eng.jitted(1, 20).lower(
        carry, _copper_params(one_chip, pot),
        _shape(one_chip, (n,), jnp.int32), _shape(one_chip, (n,)),
        1.0).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_chip(compiled)

