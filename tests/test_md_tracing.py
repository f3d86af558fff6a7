"""The MD program names its layers: device-op scopes in the chunk program,
host spans on the profiler's clock, and the neighbor counters in
``MDResult``."""

import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.md import api, driver, lattice, neighbors, stepper

SCOPES = ("md.neighbors", "dp.env", "dp.embed", "dp.fitting", "dp.scatter",
          "md.integrate")
SPANS = ("md.run", "md.first_build", "md.first_force", "md.chunk",
         "md.snapshot", "md.dispatch", "md.sync", "md.thermo_fetch",
         "md.final_fetch")


def _kw(**over):
    kw = dict(steps=40, dt_fs=1.0, temp_k=100.0, skin=0.5, rebuild_every=10,
              thermo_every=20)
    kw.update(over)
    return kw


@pytest.mark.parametrize("impl", ["cheb", "cheb_pallas"])
def test_chunk_program_carries_every_scope(tiny_cfg, impl):
    """The chunk program the outer engine compiles (a cell-list build and
    two steps) names each layer in its op metadata, and autodiff marks the
    potential's backward as ``transpose(jvp(dp.``."""
    cfg = dataclasses.replace(tiny_cfg, kernel_interpret=True)
    pos, typ, box = lattice.fcc_copper(4, 4, 4)
    pot = api.make_potential("dp", cfg, impl=impl)
    params = pot.init_params(jax.random.PRNGKey(0))
    spec = driver.neighbor_spec(pot, 0.5, len(pos), box)
    key = stepper.grid_key_for(spec, np.asarray(box))
    assert min(key) >= 3                    # the cell list, not brute force
    eng = stepper.md_outer_engine(pot, api.NVE(), spec, key, False, None)
    p = jnp.asarray(pos, jnp.float32)
    carry = stepper.OuterCarry(p, jnp.zeros_like(p), jnp.zeros_like(p),
                               jnp.zeros((), jnp.int32), (),
                               jnp.asarray(box, jnp.float32), ())
    text = eng.jitted(1, 2).lower(
        carry, params, jnp.asarray(typ, jnp.int32), jnp.ones(len(pos)),
        1.0).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in SCOPES:
        assert any(scope in n for n in op_names), scope
    assert any("transpose(jvp(dp." in n for n in op_names)
    assert not any("transpose(jvp(md." in n for n in op_names)


def test_brute_force_build_is_scoped(tiny_cfg):
    """The small-box path names its ops as the cell list does."""
    pos, typ, box = lattice.fcc_copper(2, 2, 2)
    spec = neighbors.NeighborSpec(rcut_nbr=4.5, sel=tiny_cfg.sel)
    fn = neighbors.make_cell_list_fn(spec, np.asarray(box, float))
    text = fn.lower(jnp.asarray(pos, jnp.float32),
                    jnp.asarray(typ, jnp.int32)).compile().as_text()
    assert neighbors.SCOPE in text


def _live(nlist) -> int:
    return int(np.sum(np.asarray(nlist) >= 0))


def test_outer_counters_exact(tiny_cfg, tiny_params):
    """A 99-step outer run at rebuild 20: the host-path build, then one
    in-program build per segment (four in the first chunk, one in the
    trailing partial chunk); the live slots of the perfect lattice at rest
    (no force moves an atom off its site) are the same at every build."""
    pos, typ, box = lattice.fcc_copper(3, 3, 3)
    res = driver.run_md(tiny_cfg, tiny_params, pos, typ, box,
                        engine="outer", **_kw(steps=99, rebuild_every=20,
                                              temp_k=0.0))
    assert res.escalations == 0
    assert res.nbr_builds == 1 + 4 + 1
    spec = neighbors.NeighborSpec(rcut_nbr=tiny_cfg.rcut + 0.5,
                                  sel=tiny_cfg.sel)
    nlist, ovf = neighbors.make_cell_list_fn(spec, np.asarray(box, float))(
        jnp.asarray(pos, jnp.float32), jnp.asarray(typ, jnp.int32))
    assert int(ovf) <= 0
    assert res.nbr_live_slots == 5 * _live(nlist)
    assert res.nbr_slots == 5 * len(pos) * tiny_cfg.nsel
    assert 0 < res.nbr_live_slots < res.nbr_slots


def test_outer_counters_count_replayed_chunks(tiny_cfg, tiny_params):
    """The undersized-``sel`` chunk replay on the outer engine: every
    attempt's in-program builds count in ``nbr_builds``; the live and total
    slots are those of the attempt the run stepped with, checked against a
    separate cell-list call at the same (resting) positions."""
    pos, typ, box = lattice.fcc_copper(3, 3, 3)
    posj = jnp.asarray(pos, jnp.float32)
    typj = jnp.asarray(typ, jnp.int32)
    boxj = jnp.asarray(box, jnp.float32)
    masses = jnp.asarray(lattice.masses_for(tiny_cfg.type_map,
                                            np.asarray(typ)))
    pot = api.DPPotential(tiny_cfg, impl=None, nsel_norm=tiny_cfg.nsel)
    spec_ok = neighbors.NeighborSpec(rcut_nbr=tiny_cfg.rcut + 0.5,
                                     sel=tiny_cfg.sel)
    build_ok = stepper.build_neighbors_escalating(
        tiny_cfg, spec_ok, np.asarray(box, float), posj, typj)
    _, f0, _ = pot.energy_forces(tiny_params, posj, typj, build_ok.nlist,
                                 box=boxj)
    small = stepper.NeighborBuild(
        nlist=build_ok.nlist, cfg_run=dataclasses.replace(tiny_cfg, sel=(4,)),
        spec=dataclasses.replace(spec_ok, sel=(4,)), escalations=0)
    res = driver._run_md_outer(
        pot, api.NVE(), tiny_params, posj, jnp.zeros_like(posj), f0, typj,
        boxj, np.asarray(box, float), masses, small, steps=40, dt_fs=1.0,
        rebuild_every=10, thermo_every=20, chunk_segments=8, escalation=None,
        escalations0=0)
    assert res.escalations > 0
    # one host-path build, then 4 segments in each of escalations+1 attempts
    assert res.nbr_builds == 1 + 4 * (res.escalations + 1)
    sel = (4,)
    for _ in range(res.escalations):
        sel = (stepper.EscalationPolicy().grow(sel[0]),)
    assert res.nbr_slots == 4 * len(pos) * sel[0]
    assert res.nbr_live_slots == 4 * _live(build_ok.nlist)


def test_scan_and_python_count_builds(tiny_cfg, tiny_params):
    """Host-path engines: one build before the loop and one per rebuild
    (no in-program builds, so no slots counted)."""
    pos, typ, box = lattice.fcc_copper(3, 3, 3)
    for engine in ("scan", "python"):
        res = driver.run_md(tiny_cfg, tiny_params, pos, typ, box,
                            engine=engine, **_kw())
        # scan rebuilds between segments; python also after the last step
        assert res.nbr_builds == {"scan": 4, "python": 5}[engine], engine
        assert res.nbr_live_slots == res.nbr_slots == 0


def test_spans_on_the_profiler_clock(tiny_cfg, tiny_params, tmp_path):
    """Under the profiler an outer run writes each host span, and each
    ``md.chunk`` carries its chunk index and replay attempt."""
    from jax.profiler import ProfileData
    pos, typ, box = lattice.fcc_copper(3, 3, 3)
    kw = _kw(steps=25, chunk_segments=1)
    driver.run_md(tiny_cfg, tiny_params, pos, typ, box, engine="outer", **kw)
    with jax.profiler.trace(str(tmp_path)):
        driver.run_md(tiny_cfg, tiny_params, pos, typ, box, engine="outer",
                      **kw)
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[0]
    seen, chunks = set(), []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                seen.add(ev.name)
                if ev.name == "md.chunk":
                    stats = dict(ev.stats)
                    chunks.append((stats["chunk"], stats["attempt"]))
    assert set(SPANS) <= seen
    assert sorted(chunks) == [(0, 0), (1, 0), (2, 0)]
