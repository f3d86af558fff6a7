"""Distributed-MD exactness harness (run in a subprocess with 8 host devices).

Compares the shard_map'd MD step (bricks x model decomposition) against the
single-process reference: PE must match to ~1e-5 rel and forces to 1e-6 abs.
Exercised modes: decomp in {slots, atoms} x neighbor in {brute, cells}, on
BOTH the degenerate ``(4,)`` slab topology (pins the refactor: the 1-D path
is the same staged-sweep code with one axis) and a ``(2, 2)`` brick
topology (staged x/y sweeps: edge ghosts and corner migrants route through
two axis-aligned exchanges). Plus halo-crossing migration round-trips, the
99-step distributed protocol (NVE == zero-friction Langevin == zero-
coupling NPT, outer two-level scan == host segment loop bit-exact), and
the box-squeeze capacity-escalation replay (the carried-box volume folded
into the DomainSpec capacity decision).
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import numpy as np
from repro.core import DPConfig, init_dp_params, dp_energy_forces
from repro.md import api, lattice, neighbors, domain, integrator
from repro.launch import mesh as mesh_lib
from jax.sharding import PartitionSpec as P, NamedSharding

def main():
    cfg = DPConfig(ntypes=1, rcut=4.0, rcut_smth=2.0, sel=(64,), type_map=("Cu",),
                   embed_widths=(8, 16, 32), axis_neuron=4, fit_widths=(32, 32, 32))
    params = init_dp_params(jax.random.PRNGKey(0), cfg)
    pos, typ, box = lattice.fcc_copper(8, 2, 2)
    rng = np.random.default_rng(0)
    pos = np.mod(pos + rng.normal(0, 0.05, pos.shape), box)

    spec_n = neighbors.NeighborSpec(rcut_nbr=4.5, sel=(64,))
    nlist, _ = neighbors.brute_force_neighbors(
        jnp.asarray(pos, jnp.float32), jnp.asarray(typ), spec_n, jnp.asarray(box))
    e_ref, f_ref, w_ref = dp_energy_forces(
        params, cfg, jnp.asarray(pos, jnp.float32), nlist, jnp.asarray(typ),
        jnp.asarray(box, jnp.float32))
    f_ref = np.asarray(f_ref)
    w_ref = np.asarray(w_ref)

    mesh = mesh_lib.make_mesh((4, 2), ("data", "model"))
    dspec = domain.DomainSpec(box=tuple(box), n_slabs=4, atom_capacity=48,
                              halo_capacity=40, rcut_halo=4.5)
    state0, ovf = domain.partition_atoms(
        pos.astype(np.float32), np.zeros_like(pos, dtype=np.float32), typ, dspec)
    assert ovf <= 0
    state0 = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P("data"))), state0)
    params_r = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P())), params)

    boxd = jnp.asarray(np.asarray(box, np.float32))
    virials = {}
    for decomp in ("slots", "atoms"):
        for nbr in ("brute", "cells"):
            step_fn = domain.make_distributed_md_step(
                cfg, dspec, mesh, (63.546,), dt_fs=1e-3, decomp=decomp,
                neighbor=nbr)
            (ns, _, _, _), th = step_fn(params_r, state0, (), boxd, ())
            assert int(th["halo_overflow"]) <= 0, (decomp, nbr)
            assert int(th["nbr_overflow"]) <= 0, (decomp, nbr)
            assert int(th["geom_overflow"]) <= 0, (decomp, nbr)
            assert int(th["n_atoms"]) == len(pos)
            virials[(decomp, nbr)] = np.asarray(th["stress"]) * \
                float(np.prod(box))
            pe = float(th["pe"])
            assert abs(pe - float(e_ref)) < 1e-4 + 1e-5 * abs(float(e_ref)), \
                (decomp, nbr, pe, float(e_ref))
            vel_d = np.asarray(ns.vel)
            pos_d = np.asarray(state0.pos)
            mask_d = np.asarray(state0.mask)
            f_est = vel_d * 63.546 / (1e-3 * integrator.FORCE_TO_ACC)
            err = 0.0
            for s in range(4):
                for i in range(48):
                    if not mask_d[s, i]:
                        continue
                    j = int(np.argmin(np.sum((pos - pos_d[s, i]) ** 2, 1)))
                    err = max(err, float(np.max(np.abs(f_est[s, i] - f_ref[j]))))
            assert err < 1e-6, (decomp, nbr, err)
            print(f"ok decomp={decomp} neighbor={nbr} pe_err="
                  f"{abs(pe - float(e_ref)):.2e} f_err={err:.2e}", flush=True)

    # distributed virial (strain derivative of the shard energies, psum'd
    # into thermo["stress"]) must match the single-process reference virial
    # in every decomp x neighbor mode (the kinetic part is ~0 at dt=1e-3)
    w_scale = max(1.0, float(np.max(np.abs(w_ref))))
    for mode, w_dist in virials.items():
        w_err = float(np.max(np.abs(w_dist - w_ref))) / w_scale
        assert w_err < 2e-3, (mode, w_err, w_dist, w_ref)
    print(f"ok distributed virial == single-process reference in "
          f"{len(virials)} modes (rel err < 2e-3)", flush=True)

    # migration round-trip: push some atoms across the boundary and migrate
    state = state0
    shift = jnp.zeros_like(state.pos).at[:, :4, 0].add(1.2 * dspec.slab_width * 0.1)
    state = state._replace(pos=state.pos + shift)
    mig = domain.make_migration_step(dspec, mesh)
    new_state, movf = mig(state)
    assert int(movf) <= 0
    n_before = int(jnp.sum(state.mask))
    n_after = int(jnp.sum(new_state.mask))
    assert n_before == n_after, (n_before, n_after)
    # all atoms now within their slab bounds
    pos_a = np.asarray(new_state.pos)
    mask_a = np.asarray(new_state.mask)
    for s in range(4):
        xs = pos_a[s, mask_a[s], 0]
        lo = s * dspec.slab_width
        assert np.all((xs >= lo - 1e-4) & (xs < lo + dspec.slab_width + 1e-4)), (s, xs.min(), xs.max())
    print("ok migration round-trip conserves atoms + bounds", flush=True)

    # scan-segment engine vs per-step python loop: same shard_map'd step,
    # scanned in one dispatch — the trajectory must match.
    step_fn = domain.make_distributed_md_step(
        cfg, dspec, mesh, (63.546,), dt_fs=0.5, decomp="atoms",
        neighbor="cells")
    n_steps = 8
    state_py = state0
    pes = []
    for _ in range(n_steps):
        (state_py, _, _, _), th = step_fn(params_r, state_py, (), boxd, ())
        pes.append(float(th["pe"]))
    run_segment = domain.make_segment_runner(step_fn, donate=False)
    (state_scan, _, _, _), th_seg = run_segment(state0, params_r, n_steps,
                                                box=boxd)
    domain.check_segment_thermo(th_seg)
    pe_seg = np.asarray(th_seg["pe"])
    assert pe_seg.shape == (n_steps,), pe_seg.shape
    np.testing.assert_allclose(pe_seg, np.asarray(pes), rtol=1e-5, atol=1e-5)
    dpos = float(jnp.max(jnp.abs(jnp.where(
        state_py.mask[..., None], state_scan.pos - state_py.pos, 0.0))))
    dvel = float(jnp.max(jnp.abs(jnp.where(
        state_py.mask[..., None], state_scan.vel - state_py.vel, 0.0))))
    assert dpos < 1e-5 and dvel < 1e-6, (dpos, dvel)
    print(f"ok scan-segment == python loop over {n_steps} distributed steps "
          f"(dpos {dpos:.1e}, dvel {dvel:.1e})", flush=True)

    # whole-trajectory outer program (migration + rebuild INSIDE the scan)
    # vs the host loop (segment runner + migration step per segment): same
    # trajectory over several segments, one dispatch total for the outer.
    n_segs, seg_len = 3, 4
    state_ref = state0
    for _ in range(n_segs):
        state_ref, movf = mig(state_ref, boxd)      # migrate at seg start
        assert int(movf) <= 0
        (state_ref, _, _, _), th_ref = run_segment(state_ref, params_r,
                                                   seg_len, box=boxd)
        domain.check_segment_thermo(th_ref)
    program = domain.make_outer_md_program(
        cfg, dspec, mesh, (63.546,), 0.5, decomp="atoms", neighbor="cells",
        donate=False)
    state_out, _, _, _, th_out = program.run(state0, params_r, n_segs,
                                             seg_len)
    domain.check_segment_thermo(th_out)
    assert np.asarray(th_out["pe"]).shape == (n_segs, seg_len)
    # one migration-overflow flag per staged sweep axis (1-D slab: one)
    assert np.asarray(th_out["mig_overflow"]).shape == (n_segs, 1)
    np.testing.assert_allclose(np.asarray(th_out["pe"])[-1],
                               np.asarray(th_ref["pe"]), rtol=1e-5, atol=1e-5)
    # masks can be slot-permuted only if migration ordering diverged; they
    # must not: identical program order => identical slot layout.
    assert bool(jnp.all(state_out.mask == state_ref.mask))
    dpos = float(jnp.max(jnp.abs(jnp.where(
        state_ref.mask[..., None], state_out.pos - state_ref.pos, 0.0))))
    dvel = float(jnp.max(jnp.abs(jnp.where(
        state_ref.mask[..., None], state_out.vel - state_ref.vel, 0.0))))
    assert dpos < 1e-5 and dvel < 1e-6, (dpos, dvel)
    n_conserved = int(jnp.sum(state_out.mask))
    assert n_conserved == len(pos), n_conserved
    print(f"ok outer two-level scan == host segment loop over {n_segs} "
          f"segments x {seg_len} steps (dpos {dpos:.1e}, dvel {dvel:.1e})",
          flush=True)

    # composable API through the distributed two-level scan: zero-friction
    # Langevin must be BIT-exact to NVE (the thermostat's O-step is a static
    # no-op; only the RNG key rides extra in the carry).
    lang0 = api.NVTLangevin(temp_k=330.0, friction=0.0, seed=7)
    prog_l0 = domain.make_outer_md_program(
        cfg, dspec, mesh, (63.546,), 0.5, decomp="atoms", neighbor="cells",
        donate=False, ensemble=lang0)
    ens0 = prog_l0.init_ensemble_state()
    state_l0, ens1, _, _, th_l0 = prog_l0.run(state0, params_r, n_segs,
                                              seg_len, ens0)
    domain.check_segment_thermo(th_l0)
    assert bool(jnp.all(state_l0.pos == state_out.pos))
    assert bool(jnp.all(state_l0.vel == state_out.vel))
    assert bool(jnp.all(ens1["key"] == ens0["key"]))   # untouched at gamma=0
    print("ok zero-friction Langevin == NVE bit-exact through the "
          "distributed outer scan", flush=True)

    # zero-coupling barostats: a STATIC no-op — the scanned program with a
    # barostat closed over (box + dead state in the carry) must retrace the
    # NVE trajectory bit-for-bit through the distributed two-level scan.
    for baro0 in (api.BerendsenBarostat(compressibility_per_gpa=0.0),
                  api.StochasticCellRescaleBarostat(
                      compressibility_per_gpa=0.0, seed=5)):
        prog_b0 = domain.make_outer_md_program(
            cfg, dspec, mesh, (63.546,), 0.5, decomp="atoms",
            neighbor="cells", donate=False, barostat=baro0)
        state_b0, _, box_b0, _, th_b0 = prog_b0.run(
            state0, params_r, n_segs, seg_len,
            baro=prog_b0.init_barostat_state())
        domain.check_segment_thermo(th_b0)
        assert bool(jnp.all(state_b0.pos == state_out.pos)), type(baro0)
        assert bool(jnp.all(state_b0.vel == state_out.vel)), type(baro0)
        np.testing.assert_array_equal(np.asarray(box_b0),
                                      np.asarray(boxd))
    print("ok zero-coupling barostats == NVE bit-exact through the "
          "distributed outer scan (box static in the carry)", flush=True)

    # live NPT through the distributed outer scan: Berendsen barostat on an
    # UNDER-pressured start (w_ref trace < 0 here) targeting a higher
    # pressure must shrink the box; every slab agrees on the carried box,
    # migration keeps atoms owned, and the geometry check stays quiet.
    p_now = float(np.trace(w_ref)) / 3.0 / float(np.prod(box)) \
        * integrator.EV_A3_TO_GPA
    baro_live = api.BerendsenBarostat(pressure_gpa=p_now + 4.0, tau_fs=50.0,
                                      compressibility_per_gpa=0.01)
    prog_npt = domain.make_outer_md_program(
        cfg, dspec, mesh, (63.546,), 0.5, decomp="atoms", neighbor="cells",
        donate=False, barostat=baro_live,
        ensemble=api.BerendsenThermostat(temp_k=330.0, tau_fs=50.0))
    state_npt, _, box_npt, _, th_npt = prog_npt.run(
        state0, params_r, n_segs, seg_len,
        baro=prog_npt.init_barostat_state())
    domain.check_segment_thermo(th_npt)
    box_npt = np.asarray(box_npt)
    assert np.all(box_npt < np.asarray(boxd)), (box_npt, np.asarray(boxd))
    assert int(jnp.sum(state_npt.mask)) == len(pos)
    press_trace = np.asarray(th_npt["press"]).reshape(-1) \
        * integrator.EV_A3_TO_GPA
    assert np.all(np.isfinite(press_trace))
    print(f"ok distributed NPT: box {np.asarray(boxd)[0]:.3f} -> "
          f"{box_npt[0]:.3f} A toward P0={p_now + 4.0:.2f} GPa "
          f"(P {press_trace[0]:+.2f} -> {press_trace[-1]:+.2f} GPa)",
          flush=True)

    # the traced cutoff-vs-halo check: a box below n_slabs * rcut_halo must
    # raise through the overflow channel (geom_overflow), not run silently
    bad_box = jnp.asarray([4 * 4.0, boxd[1], boxd[2]], jnp.float32)
    _, _, _, _, th_bad = program.run(state0, params_r, 1, 2, box=bad_box)
    try:
        domain.check_segment_thermo(th_bad)
    except RuntimeError as e:
        assert "geom_overflow" in str(e), e
        print("ok geom_overflow: carried box below slab halo geometry is "
              "caught by the traced check", flush=True)
    else:
        raise AssertionError("geom_overflow violation not flagged")

    # LJ potential + finite-friction Langevin: the full non-DP seam runs
    # distributed (halo + migration + rebuild + noise per slab) and cools a
    # hot start (thermo sanity, not a trajectory reference).
    lj = api.LJPotential(sel=(64,), rcut_lj=4.0)
    prog_lj = domain.make_outer_md_program(
        cfg, dspec, mesh, (63.546,), 0.5, decomp="atoms", neighbor="cells",
        donate=False, potential=lj,
        ensemble=api.NVTLangevin(temp_k=330.0, friction=0.05, seed=3))
    ens_lj = prog_lj.init_ensemble_state()
    state_lj, ens_lj, _, _, th_lj = prog_lj.run(state0, {}, n_segs, seg_len,
                                                ens_lj)
    domain.check_segment_thermo(th_lj)
    assert int(jnp.sum(state_lj.mask)) == len(pos)
    assert np.all(np.isfinite(np.asarray(th_lj["pe"])))
    assert not bool(jnp.all(ens_lj["key"] == prog_lj.init_ensemble_state()["key"]))
    print("ok LJ + Langevin runs distributed through the outer scan "
          f"(pe[0] {float(np.asarray(th_lj['pe'])[0, 0]):+.2f} -> "
          f"pe[-1] {float(np.asarray(th_lj['pe'])[-1, -1]):+.2f})",
          flush=True)

    brick_checks()
    protocol_99_checks()
    squeeze_escalation_check()
    print("ALL DISTRIBUTED MD CHECKS PASSED")


def brick_checks():
    """(2, 2) brick topology: force/virial parity vs the single-process
    reference in every decomp x neighbor mode (the same tolerances the slab
    path meets), plus a corner-crossing migration round-trip through the
    two staged sweeps."""
    from repro.md import domain, integrator, lattice, neighbors
    from repro.core import dp_energy_forces, init_dp_params
    from repro.core.types import DPConfig
    cfg = DPConfig(ntypes=1, rcut=4.0, rcut_smth=2.0, sel=(64,),
                   type_map=("Cu",), embed_widths=(8, 16, 32), axis_neuron=4,
                   fit_widths=(32, 32, 32))
    params = init_dp_params(jax.random.PRNGKey(0), cfg)
    pos, typ, box = lattice.fcc_copper(4, 4, 3)
    rng = np.random.default_rng(0)
    pos = np.mod(pos + rng.normal(0, 0.05, pos.shape), box)

    spec_n = neighbors.NeighborSpec(rcut_nbr=4.5, sel=(64,))
    nlist, _ = neighbors.brute_force_neighbors(
        jnp.asarray(pos, jnp.float32), jnp.asarray(typ), spec_n,
        jnp.asarray(box))
    e_ref, f_ref, w_ref = dp_energy_forces(
        params, cfg, jnp.asarray(pos, jnp.float32), nlist, jnp.asarray(typ),
        jnp.asarray(box, jnp.float32))
    f_ref = np.asarray(f_ref)
    w_ref = np.asarray(w_ref)

    mesh = mesh_lib.make_mesh((4, 2), ("data", "model"))
    dspec = domain.DomainSpec.for_topology(
        tuple(box), (2, 2), atom_capacity=96, halo_capacity=96,
        rcut_halo=4.5)
    dspec.validate()
    state0, ovf = domain.partition_atoms(
        pos.astype(np.float32), np.zeros_like(pos, dtype=np.float32), typ,
        dspec)
    assert ovf <= 0
    state0 = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P("data"))), state0)
    params_r = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P())), params)
    boxd = jnp.asarray(np.asarray(box, np.float32))
    w_scale = max(1.0, float(np.max(np.abs(w_ref))))
    for decomp in ("slots", "atoms"):
        for nbr in ("brute", "cells"):
            step_fn = domain.make_distributed_md_step(
                cfg, dspec, mesh, (63.546,), dt_fs=1e-3, decomp=decomp,
                neighbor=nbr)
            (ns, _, _, _), th = step_fn(params_r, state0, (), boxd, ())
            assert int(th["halo_overflow"]) <= 0, (decomp, nbr)
            assert int(th["nbr_overflow"]) <= 0, (decomp, nbr)
            assert int(th["geom_overflow"]) <= 0, (decomp, nbr)
            assert int(th["n_atoms"]) == len(pos)
            pe = float(th["pe"])
            assert abs(pe - float(e_ref)) < 1e-4 + 1e-5 * abs(float(e_ref)), \
                (decomp, nbr, pe, float(e_ref))
            w_dist = np.asarray(th["stress"]) * float(np.prod(box))
            w_err = float(np.max(np.abs(w_dist - w_ref))) / w_scale
            assert w_err < 2e-3, (decomp, nbr, w_err)
            vel_d = np.asarray(ns.vel)
            pos_d = np.asarray(state0.pos)
            mask_d = np.asarray(state0.mask)
            f_est = vel_d * 63.546 / (1e-3 * integrator.FORCE_TO_ACC)
            err = 0.0
            for s in range(4):
                for i in range(dspec.atom_capacity):
                    if not mask_d[s, i]:
                        continue
                    j = int(np.argmin(np.sum((pos - pos_d[s, i]) ** 2, 1)))
                    err = max(err,
                              float(np.max(np.abs(f_est[s, i] - f_ref[j]))))
            assert err < 1e-6, (decomp, nbr, err)
            print(f"ok 2x2 brick decomp={decomp} neighbor={nbr} pe_err="
                  f"{abs(pe - float(e_ref)):.2e} f_err={err:.2e} "
                  f"w_err={w_err:.2e}", flush=True)

    # corner-crossing migration: shift atoms diagonally (+x, +y) so some
    # cross BOTH brick faces — the two staged sweeps must route them to the
    # diagonal neighbor (hop 1 fixes the x column, hop 2 the y row)
    shift = jnp.zeros_like(state0.pos)
    shift = shift.at[:, :4, 0].add(1.5)
    shift = shift.at[:, :4, 1].add(1.5)
    state = state0._replace(pos=state0.pos + shift)
    mig = domain.make_migration_step(dspec, mesh)
    new_state, movf = mig(state)
    assert int(movf) <= 0
    assert int(jnp.sum(new_state.mask)) == int(jnp.sum(state0.mask))
    pos_a = np.asarray(new_state.pos)
    mask_a = np.asarray(new_state.mask)
    wx, wy = dspec.brick_widths
    topo = dspec.topo
    for r in range(4):
        cx, cy = topo.coords_of(r)
        xs = pos_a[r, mask_a[r]]
        assert np.all((xs[:, 0] >= cx * wx - 1e-4)
                      & (xs[:, 0] < (cx + 1) * wx + 1e-4)), r
        assert np.all((xs[:, 1] >= cy * wy - 1e-4)
                      & (xs[:, 1] < (cy + 1) * wy + 1e-4)), r
    print("ok 2x2 brick corner migration: staged sweeps conserve atoms + "
          "route diagonal crossers to the right brick", flush=True)


def _lj_dist_protocol(topology, mesh_shape, pos, typ, box, vel, ensemble,
                      barostat, steps=99, rebuild_every=9, dt=1.0):
    """Run the 99-step LJ protocol through the distributed outer program on
    ``topology``; returns (final SlabState, pe trace, n_atoms_trace)."""
    from repro.md import api, domain, stepper
    from repro.core.types import DPConfig
    cfg = DPConfig(ntypes=1, rcut=4.0, rcut_smth=2.0, sel=(64,),
                   type_map=("Cu",))
    lj = api.LJPotential(sel=(64,), rcut_lj=4.0)
    mesh = mesh_lib.make_mesh(mesh_shape, ("data", "model"))
    dspec = domain.DomainSpec.for_topology(
        tuple(box), topology, atom_capacity=160, halo_capacity=256,
        rcut_halo=4.5)
    dspec.validate()
    state, ovf = domain.partition_atoms(
        pos.astype(np.float32), np.asarray(vel, np.float32), typ, dspec)
    assert ovf <= 0
    sh = NamedSharding(mesh, P("data"))
    state = jax.tree.map(lambda x: jax.device_put(x, sh), state)
    program = domain.make_outer_md_program(
        cfg, dspec, mesh, (63.546,), dt, decomp="atoms", neighbor="cells",
        donate=False, potential=lj, ensemble=ensemble, barostat=barostat)
    ens = program.init_ensemble_state()
    baro = program.init_barostat_state()
    boxd = None
    pes, nat = [], []
    # 99 = 11 x 9: ONE chunk shape -> one jit key per program (compile
    # time dominates this harness on emulated CPU devices)
    for n_segs, seg_len in stepper.chunk_schedule(steps, rebuild_every, 11):
        state, ens, boxd, baro, th = program.run(state, {}, n_segs, seg_len,
                                                 ens, boxd, baro)
        domain.check_segment_thermo(th)
        pes.append(np.asarray(th["pe"]).reshape(-1))
        nat.append(np.asarray(th["n_atoms"]).reshape(-1))
    return state, np.concatenate(pes), np.concatenate(nat), boxd


def protocol_99_checks():
    """The 99-step distributed protocol on the degenerate (4,) slab AND a
    (2, 2) brick: NVE == zero-friction Langevin == zero-coupling NPT
    bit-exact per topology, atoms conserved every step, and the two
    topologies' trajectories agree within the fp-reordering envelope of
    the slab path itself."""
    from repro.md import api, driver, lattice
    pos, typ, box = lattice.fcc_copper(6, 4, 3)
    rng = np.random.default_rng(1)
    pos = np.mod(pos + rng.normal(0, 0.02, pos.shape), box)
    n = len(pos)
    masses = jnp.full((n,), 63.546)
    vel = integrator.init_velocities(jax.random.PRNGKey(2), masses, 330.0)

    runs = {}
    for label, topo, mesh_shape in (("slab4", (4,), (4, 2)),
                                    ("brick2x2", (2, 2), (4, 2))):
        st_nve, pe_nve, nat, _ = _lj_dist_protocol(
            topo, mesh_shape, pos, typ, box, vel, api.NVE(), None)
        assert np.all(nat == n), (label, nat.min(), nat.max())
        assert pe_nve.shape == (99,)
        st_l0, pe_l0, _, _ = _lj_dist_protocol(
            topo, mesh_shape, pos, typ, box, vel,
            api.NVTLangevin(temp_k=330.0, friction=0.0, seed=7), None)
        assert bool(jnp.all(st_l0.pos == st_nve.pos)), label
        assert bool(jnp.all(st_l0.vel == st_nve.vel)), label
        np.testing.assert_array_equal(pe_l0, pe_nve)
        st_b0, pe_b0, _, box_b0 = _lj_dist_protocol(
            topo, mesh_shape, pos, typ, box, vel, api.NVE(),
            api.StochasticCellRescaleBarostat(compressibility_per_gpa=0.0,
                                              seed=5))
        assert bool(jnp.all(st_b0.pos == st_nve.pos)), label
        np.testing.assert_array_equal(np.asarray(box_b0),
                                      np.asarray(box, np.float32))
        np.testing.assert_array_equal(pe_b0, pe_nve)
        runs[label] = pe_nve
        print(f"ok 99-step protocol on {label}: NVE == zero-friction "
              f"Langevin == zero-coupling NPT bit-exact, atoms conserved",
              flush=True)

    # cross-topology + single-process agreement: the brick trajectory must
    # stay within the same fp-reordering envelope the slab path itself has
    # vs the single-process reference (chaotic f32 divergence bounds both)
    lj = api.LJPotential(sel=(64,), rcut_lj=4.0)
    sim = api.SimulationSpec(potential=lj, ensemble=api.NVE(), steps=99,
                             dt_fs=1.0, temp_k=330.0, rebuild_every=10,
                             thermo_every=1, skin=0.5, seed=0,
                             engine="python")
    res = driver.run_simulation(sim, {}, pos.astype(np.float32), typ, box)
    # same velocities as the distributed runs (init_velocities(key=2))
    # are not used by run_simulation (it draws its own): compare envelopes
    # via the slab-vs-brick delta instead, which shares initial conditions.
    pe_scale = float(np.abs(runs["slab4"]).max())
    delta = np.max(np.abs(runs["slab4"] - runs["brick2x2"])) / pe_scale
    assert delta < 5e-3, delta
    assert np.all(np.isfinite(res.press_gpa_trace()))
    print(f"ok 99-step slab vs 2x2 brick trajectory delta {delta:.1e} "
          f"(fp-reordering envelope)", flush=True)


def squeeze_escalation_check():
    """Regression for the box-in-capacity fix: a barostat-compressed box
    raises per-brick density, so the boundary-layer (halo) packs outgrow a
    capacity sized for the launch density. Apply the compression affinely
    (exactly what a Berendsen barostat does, just deterministic), run with
    the squeezed CARRIED box until the halo capacity overflows, then
    escalate with the box volume FOLDED IN and replay: the capacity jump
    must reach the volume ratio (here 1.95x > the 1.6x geometric growth —
    growth alone would creep), and the replayed chunk must pass."""
    from repro.md import api, domain, lattice, stepper
    from repro.core.types import DPConfig
    cfg = DPConfig(ntypes=1, rcut=4.0, rcut_smth=2.0, sel=(96,),
                   type_map=("Cu",))
    lj = api.LJPotential(sel=(96,), rcut_lj=4.0)
    pos, typ, box = lattice.fcc_copper(9, 4, 3)
    rng = np.random.default_rng(3)
    pos = np.mod(pos + rng.normal(0, 0.02, pos.shape), box)
    n = len(pos)
    masses = jnp.full((n,), 63.546)
    vel = integrator.init_velocities(jax.random.PRNGKey(4), masses, 330.0)
    mesh = mesh_lib.make_mesh((4, 2), ("data", "model"))

    # the affine squeeze a barostat run would produce: box AND positions
    f = 0.8                                     # volume ratio 1/f^3 ~ 1.95
    box_s = np.asarray(box, float) * f
    pos_s = (pos * f).astype(np.float32)

    # halo capacity sized for the LAUNCH density boundary layer (worst
    # brick + margin) — the squeezed density must overflow it
    def layer_max(p, b):
        w = b[0] / 4
        worst = 0
        for s in range(4):
            x = p[(p[:, 0] >= s * w) & (p[:, 0] < (s + 1) * w), 0] - s * w
            worst = max(worst, int(np.sum(x < 4.5)),
                        int(np.sum(x > w - 4.5)))
        return worst
    cap_launch = layer_max(pos, np.asarray(box, float))
    cap_squeezed = layer_max(pos_s, box_s)
    halo_cap = cap_launch + 4
    assert cap_squeezed > halo_cap, (cap_launch, cap_squeezed)

    dspec = domain.DomainSpec.for_topology(
        tuple(box), (4,), atom_capacity=160, halo_capacity=halo_cap,
        rcut_halo=4.5)
    dspec.validate()
    state, ovf = domain.partition_atoms(pos_s, np.asarray(vel, np.float32),
                                        typ, dspec, box=box_s)
    assert ovf <= 0
    sh = NamedSharding(mesh, P("data"))
    state = jax.tree.map(lambda x: jax.device_put(x, sh), state)
    thermostat = api.BerendsenThermostat(temp_k=330.0, tau_fs=50.0)

    def build(spec_run):
        return domain.make_outer_md_program(
            cfg, spec_run, mesh, (63.546,), 0.2, decomp="atoms",
            neighbor="cells", donate=False, potential=lj,
            ensemble=thermostat)

    program = build(dspec)
    policy = stepper.EscalationPolicy()
    boxd = jnp.asarray(box_s, jnp.float32)      # the squeezed CARRIED box
    try:
        _state_f, _, _, _, th = program.run(state, {}, 2, 5, (), boxd, ())
        domain.check_segment_thermo(th)
        raise AssertionError("halo overflow not flagged under the squeeze")
    except RuntimeError as e:
        assert "halo_overflow" in str(e), e

    scale = domain.capacity_scale_for_box(dspec, box_s)
    assert scale > policy.growth, scale         # volume fold must dominate
    spec_new = domain.escalate_capacities(dspec, policy, box_now=box_s,
                                          n_model=2)
    # the jump reaches the volume ratio, not just the geometric growth
    assert spec_new.halo_capacity >= int(halo_cap * scale) - policy.round_to
    assert spec_new.halo_capacity > policy.grow(halo_cap)   # fold mattered
    assert spec_new.halo_capacity >= cap_squeezed
    assert spec_new.atom_capacity % 2 == 0
    state2, r_ovf = domain.repartition_state(state, spec_new, box_now=box_s)
    assert r_ovf <= 0, r_ovf
    state2 = jax.tree.map(lambda x: jax.device_put(x, sh), state2)
    program = build(spec_new)
    state2, _, boxd2, _, th = program.run(state2, {}, 2, 5, (), boxd, ())
    domain.check_segment_thermo(th)             # replay passes
    assert int(jnp.sum(state2.mask)) == n
    print(f"ok box-squeeze escalation: halo overflow at {scale:.2f}x "
          f"density replayed clean with volume-folded capacities "
          f"(halo {halo_cap} -> {spec_new.halo_capacity}, geometric growth "
          f"alone would give {policy.grow(halo_cap)})", flush=True)

if __name__ == "__main__":
    main()
