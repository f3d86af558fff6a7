#!/usr/bin/env python3
"""Prove the main MD path runs on one TPU chip at the paper's copper width.

    python3 chip_smoke.py                  # one TPU chip (the default)
    python3 chip_smoke.py --four-chips     # only the (4,) brick phase
    JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse [--four-chips]

One chip: the paper's copper model (``COPPER_DP``: rcut 8 A, sel 512,
32x64x128 embedding, 240^3 fitting) with seeded random weights runs NVE
through ``api.Simulation`` and the outer engine (in-scan neighbor rebuilds)
on 10x10x10 FCC cells (4,000 atoms) for the rungs ``mlp``, ``cheb`` and the
compiled Pallas kernel ``cheb_pallas``, then ``cheb_pallas`` on 16x16x16
cells (16,384 atoms). Each rung's first-step energy, forces and virial are
checked against ``mlp`` evaluated at ``highest`` matmul precision.

``--four-chips`` runs only the brick decomposition: the whole-trajectory
program (``domain.make_outer_md_program``) on a ``(4,)`` topology, one
brick per chip, against the single-process driver on the same atoms.

``--rehearse`` runs the same control flow at toy width on whatever backend
JAX has (the Pallas kernel through its interpreter) and never reports
success. Without it, any platform other than ``tpu`` is an error.

The last line of a successful run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed check or phase raises, exits non-zero and prints no such line.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# Random weights give a nearly flat energy surface (rms force ~5e-4 eV/A at
# copper width even with data-derived descriptor scales), which would make
# every force comparison vacuous. Scaling the linear fitting head scales all
# forces exactly, to the ~0.1-1 eV/A of a thermal copper configuration.
HEAD_SCALE = 500.0

# Errors against mlp at "highest" precision: rms(F - F_ref) / rms(F_ref),
# max|W - W_ref| / max|W_ref|, and |E - E_ref| / (N * sum|w_head|). The
# energy is not divided by |E_ref|: each atom's energy is a sum of 240 head
# terms of up to ~6,000 eV that cancel to ~3 eV for these weights, so
# |E_ref| is an accident of the seed. N * sum|w_head| is the scale those
# terms add up to, and the scale the rounding error follows.
TOL = {
    # the same rung at "highest": f32 roundoff (~6e-8 of the energy scale)
    # plus the K=32 Chebyshev table error (2.6e-6 rms-relative in forces,
    # below f32 resolution in energy at full width on 864 atoms)
    "highest": {"force": 1e-3, "virial": 1e-3, "energy": 1e-6},
    # the rung at default precision: the TPU feeds f32 matmuls to the MXU as
    # one bf16 pass (unit roundoff u = 2^-9 ~ 2e-3), through ~10 chained
    # matmuls (3 embedding layers, R~^T G, D = T<^T T, 3 fitting layers and
    # their transposes in the force backward pass). Rounding the head's
    # inputs alone may move the energy by 2u of its scale; random signs
    # over 240 terms keep it well under u/2.
    "default": {"force": 5e-2, "virial": 5e-2, "energy": 1e-3},
}
# Four-chip phase, both sides at "highest". First step: the decomposition
# changes only f32 summation order (forces: max error over rms force).
# Trajectory: the brick step closes each step with the start-of-step force
# instead of velocity Verlet's end-of-step force, so the two runs part at
# O(dt^2) in velocity; their PE must agree within 5% of the kinetic energy.
TOL_BRICKS = {"force": 1e-3, "energy": 1e-6, "pe_trajectory": 0.05}
# NVE over 40 steps at default precision: |E_tot - E_tot(step 1)| <= 50% of
# max KE. With bf16 matmul inputs the PE is a staircase: of the ~1e6 head
# activations at 4,000 atoms, the few % that cross a bf16 rounding boundary
# in 40 fs move it by ~0.1 eV each, ~15 eV (~10% of KE) in all. Unit, sign
# or lost-atom errors break the bound within a few steps.
DRIFT_OF_KE = 0.5


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="toy width on any backend, kernel interpreted; "
                         "never reports success")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (4,) brick phase on four devices")
    ap.add_argument("--report", default=None,
                    help="also write every measurement to this JSON file")
    return ap.parse_args(argv)


def log(msg):
    print(msg, flush=True)


# ------------------------------------------------------------ helpers

def jittered_fcc(nx, nyz, seed=0):
    """FCC copper with a seeded 0.1 A jitter (a perfect lattice has zero
    forces by symmetry, which would make the force checks vacuous)."""
    import numpy as np
    from repro.md import lattice
    pos, typ, box = lattice.fcc_copper(nx, nyz, nyz)
    rng = np.random.default_rng(seed)
    pos = np.mod(pos + rng.normal(0.0, 0.1, pos.shape), box)
    return pos.astype(np.float32), typ, box


def first_nlist(cfg, pos, typ, box, skin):
    """The neighbor list the Simulation builds before its first step."""
    import jax.numpy as jnp
    from repro.md import api, driver, stepper
    spec = driver.neighbor_spec(api.DPPotential(cfg), skin, len(pos), box)
    build = stepper.build_neighbors_escalating(
        cfg, spec, box, jnp.asarray(pos), jnp.asarray(typ),
        dynamic_box=True)
    if build.escalations:
        raise AssertionError(f"the first neighbor build escalated to "
                             f"sel={build.spec.sel}, cell_capacity="
                             f"{build.spec.cell_capacity}")
    return build


def seeded_params(pot, cfg, pos, typ, box, nlist):
    """``pot.init_params(PRNGKey(0))``; the descriptor scale comes from this
    frame's environment statistics (as DeePMD takes it from data) and the
    fitting head is scaled by ``HEAD_SCALE``. Every rung of one system
    shares the embedding and fitting weights."""
    import jax
    import jax.numpy as jnp
    from repro.core import descriptor, dp_model
    params = pot.init_params(jax.random.PRNGKey(0))
    rij, nmask = dp_model.gather_rij(jnp.asarray(pos), nlist,
                                     jnp.asarray(box, jnp.float32))
    env, _ = descriptor.env_matrix(rij, nmask, cfg.rcut_smth, cfg.rcut)
    fit = {t: {**net, "head": {**net["head"],
                               "w": net["head"]["w"] * HEAD_SCALE}}
           for t, net in params["fit"].items()}
    return {**params, "fit": fit,
            "dstd": descriptor.compute_env_stats(env, nmask, jnp.asarray(typ),
                                                 cfg.ntypes)}


def energy_forces(pot, params, pos, typ, nlist, box, precision):
    """(E, F, W) of ``pot`` on one frame, as host arrays."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    with jax.default_matmul_precision(precision):
        e, f, stats = pot.energy_forces(params, jnp.asarray(pos),
                                        jnp.asarray(typ), nlist,
                                        box=jnp.asarray(box, jnp.float32))
        return float(e), np.asarray(f), np.asarray(stats["virial"])


def has_kernel(pot, params, pos, typ, nlist, box) -> bool:
    """Whether the compiled force program holds the Pallas custom call."""
    import jax.numpy as jnp
    from repro.core import dp_model
    compiled = dp_model.dp_energy_forces.lower(
        params, pot.cfg, jnp.asarray(pos), nlist, jnp.asarray(typ),
        jnp.asarray(box, jnp.float32), impl=pot.impl,
        nsel_norm=pot.nsel_norm).compile()
    return "tpu_custom_call" in compiled.as_text()


def energy_scale(params, n_atoms) -> float:
    """N * sum|w_head| (eV), the largest type's head."""
    import numpy as np
    return n_atoms * max(float(np.abs(np.asarray(net["head"]["w"])).sum())
                         for net in params["fit"].values())


def errors(got, ref, e_scale):
    import numpy as np
    e, f, w = got
    e0, f0, w0 = ref
    return {
        "energy": abs(e - e0) / e_scale,
        "force": float(np.sqrt(np.mean((f - f0) ** 2))
                       / max(np.sqrt(np.mean(f0 ** 2)), 1e-30)),
        "virial": float(np.max(np.abs(w - w0))
                        / max(np.max(np.abs(w0)), 1e-30)),
    }


def check(name, errs, tol):
    bad = {k: v for k, v in errs.items() if not v <= tol[k]}
    line = " ".join(f"{k}_err={v:.3e}(tol {tol[k]:g})"
                    for k, v in errs.items())
    log(f"  {name}: {line}")
    if bad:
        raise AssertionError(f"{name}: errors above tolerance: {bad}")


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def device_info():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


# ------------------------------------------------------ one-chip phase

def simulate(pot, params, pos, typ, box, skin, steps, rebuild_every):
    """Run the spec twice: the first call compiles, the second is steady.
    Both end in host arrays (block_until_ready inside the driver)."""
    import jax
    import numpy as np
    from repro.md import api
    spec = api.SimulationSpec(
        potential=pot, ensemble=api.NVE(), steps=steps, dt_fs=1.0,
        temp_k=330.0, rebuild_every=rebuild_every, thermo_every=1,
        skin=skin, engine="outer")
    sim = api.Simulation(spec)
    t0 = time.perf_counter()
    sim.run(params, pos, typ, box)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = sim.run(params, pos, typ, box)
    jax.block_until_ready(res.final_pos)
    steady_s = time.perf_counter() - t0
    etot = np.array([r["etot"] for r in res.thermo])
    ke = np.array([r["ke"] for r in res.thermo])
    if not (np.all(np.isfinite(etot)) and np.all(np.isfinite(res.final_pos))):
        raise AssertionError("non-finite energies or positions")
    if len(res.final_pos) != len(pos) or len(res.thermo) != steps:
        raise AssertionError("atom count or thermo rows changed")
    drift = float(np.max(np.abs(etot - etot[0])))
    if not drift <= DRIFT_OF_KE * float(np.max(ke)):
        raise AssertionError(f"E_tot drift {drift} eV > {DRIFT_OF_KE} x "
                             f"max KE {float(np.max(ke))} eV")
    return {"first_call_s": first_s, "steady_s": steady_s,
            "compile_s": first_s - steady_s, "etot_drift_eV": drift,
            "etot_step1_eV": float(etot[0]), "ke_max_eV": float(np.max(ke)),
            "escalations": res.escalations, "host_syncs": res.host_syncs}


def one_chip_phase(cfg, sizes, skin, steps, rebuild_every, rehearse):
    import jax
    from repro.md import api
    dev = jax.devices()[0]
    runs = []
    (nc_small, rungs), (nc_big, big_rung) = sizes
    for nc, impls, ref_impl in ((nc_small, rungs, "mlp"),
                                (nc_big, (big_rung,), "cheb")):
        pos, typ, box = jittered_fcc(nc, nc)
        n = len(pos)
        build = first_nlist(cfg, pos, typ, box, skin)
        ref_pot = api.make_potential("dp", cfg, impl=ref_impl) \
            .with_layout(build.spec.sel)
        params0 = seeded_params(ref_pot, cfg, pos, typ, box, build.nlist)
        ref = energy_forces(ref_pot, params0, pos, typ, build.nlist, box,
                            "highest")
        e_scale = energy_scale(params0, n)
        log(f"system {nc}x{nc}x{nc} FCC: {n} atoms, box {box[0]:.2f} A, "
            f"reference {ref_impl}@highest E={ref[0]:.6f} eV "
            f"rms|F|={float((ref[1] ** 2).mean() ** 0.5):.4f} eV/A, "
            f"energy scale {e_scale:.6g} eV")
        for impl in impls:
            pot = api.make_potential("dp", cfg, impl=impl)
            params = seeded_params(pot, cfg, pos, typ, box, build.nlist)
            pot_run = pot.with_layout(build.spec.sel)
            errs = {}
            for prec in ("highest", "default"):
                got = energy_forces(pot_run, params, pos, typ, build.nlist,
                                    box, prec)
                errs[prec] = errors(got, ref, e_scale)
                check(f"{impl}@{prec} vs {ref_impl}@highest", errs[prec],
                      TOL[prec])
            kernel = has_kernel(pot_run, params, pos, typ, build.nlist, box)
            if impl == "cheb_pallas" and not rehearse and not kernel:
                raise AssertionError("cheb_pallas compiled without the "
                                     "Pallas kernel (tpu_custom_call)")
            row = {"impl": impl, "atoms": n, "steps": steps,
                   "tpu_custom_call": kernel, "reference": ref_impl,
                   "errors": errs}
            row.update(simulate(pot, params, pos, typ, box, skin, steps,
                                rebuild_every))
            row["peak_bytes_in_use"] = peak_bytes(dev)
            log(f"run impl={impl} atoms={n} steps={steps} "
                f"compile_s={row['compile_s']:.3f} "
                f"steady_s={row['steady_s']:.4f} "
                f"etot_drift_eV={row['etot_drift_eV']:.3e} "
                f"ke_max_eV={row['ke_max_eV']:.3e} "
                f"peak_bytes_in_use={row['peak_bytes_in_use']} "
                f"tpu_custom_call={kernel} "
                f"escalations={row['escalations']}")
            runs.append(row)
    return runs


# ----------------------------------------------------- four-chip phase

def on_four_devices(x, devices) -> bool:
    shards = x.addressable_shards
    return (len(shards) == 4 and {s.device for s in shards} == set(devices)
            and all(s.data.shape[0] == 1 for s in shards))


def four_chip_phase(cfg, nx, nyz, skin, impl):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch import mesh as mesh_lib
    from repro.md import api, domain, integrator, lattice

    if len(jax.devices()) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices, JAX has "
                           f"{len(jax.devices())}")
    devices = jax.devices()[:4]
    mesh = mesh_lib.make_mesh((4, 1), ("data", "model"), devices=devices)
    pos, typ, box = jittered_fcc(nx, nyz)
    n = len(pos)
    cap = int(n / 4 * 1.5) + 8
    spec = domain.DomainSpec(box=tuple(box), n_slabs=4, atom_capacity=cap,
                             halo_capacity=cap, rcut_halo=cfg.rcut + skin,
                             topology=(4,))
    spec.validate()
    masses = jnp.asarray(lattice.masses_for(cfg.type_map, typ))
    masses_t = tuple(lattice.MASS[t] for t in cfg.type_map)
    pot = api.make_potential("dp", cfg, impl=impl)
    build = first_nlist(cfg, pos, typ, box, skin)
    params = seeded_params(pot, cfg, pos, typ, box, build.nlist)
    rep = NamedSharding(mesh, P())
    params_r = jax.tree.map(lambda x: jax.device_put(x, rep), params)
    sh = NamedSharding(mesh, P("data"))
    log(f"bricks (4,) over {[str(d) for d in devices]}: {n} atoms, box "
        f"{np.round(box, 2).tolist()} A, brick width {box[0] / 4:.2f} A >= "
        f"rcut_halo {spec.rcut_halo} A, impl={impl}")

    def place(state):
        state = jax.tree.map(lambda x: jax.device_put(x, sh), state)
        if not on_four_devices(state.pos, devices):
            raise AssertionError("brick state is not spread one brick per "
                                 "device")
        return state

    out = {"atoms": n, "impl": impl, "topology": [4]}
    with jax.default_matmul_precision("highest"):
        # first step, as the distributed test script reads it: from rest
        # with a tiny dt the velocity after one step is F dt / m
        e_ref, f_ref, _ = energy_forces(pot.with_layout(build.spec.sel),
                                        params, pos, typ, build.nlist, box,
                                        "highest")
        dt0 = 1e-3
        step = domain.make_distributed_md_step(
            cfg, spec, mesh, masses_t, dt_fs=dt0, impl=impl, decomp="atoms",
            neighbor="cells", potential=pot)
        state0, ovf = domain.partition_atoms(pos, np.zeros_like(pos), typ,
                                             spec)
        assert ovf <= 0, f"brick capacity overflow {ovf}"
        (ns, _, _, _), th = step(params_r, place(state0), (),
                                 jnp.asarray(box, jnp.float32), ())
        domain.check_segment_thermo(th)
        p_d, v_d, _ = domain.gather_atoms(ns)
        if len(p_d) != n:
            raise AssertionError(f"first step holds {len(p_d)} atoms, not {n}")
        f_d = v_d * float(masses[0]) / (dt0 * integrator.FORCE_TO_ACC)
        # match atoms by position (they moved by ~1e-9 A)
        d2 = ((p_d[:, None, :] - pos[None, :, :]) ** 2).sum(-1)
        f_ref_m = f_ref[np.argmin(d2, axis=1)]
        errs = {"energy": abs(float(th["pe"]) - e_ref)
                / energy_scale(params, n),
                "force": float(np.max(np.abs(f_d - f_ref_m))
                               / np.sqrt(np.mean(f_ref ** 2)))}
        check("bricks first step vs single process (highest)", errs,
              TOL_BRICKS)
        out["first_step_errors"] = errs

        # a short whole-trajectory run against the single-process driver
        # from the same velocities (Simulation seeds them from PRNGKey(0))
        steps, seg = 20, 10
        vel = integrator.init_velocities(jax.random.PRNGKey(0), masses, 330.0)
        res = api.Simulation(api.SimulationSpec(
            potential=pot, steps=steps, dt_fs=1.0, temp_k=330.0,
            rebuild_every=seg, thermo_every=1, skin=skin,
            engine="outer")).run(params, pos, typ, box)
        pe_ref = np.array([r["pe"] for r in res.thermo])
        ke_max = max(r["ke"] for r in res.thermo)
        program = domain.make_outer_md_program(
            cfg, spec, mesh, masses_t, 1.0, impl=impl, decomp="atoms",
            neighbor="cells", potential=pot)
        state, ovf = domain.partition_atoms(pos, np.asarray(vel, np.float32),
                                            typ, spec)
        assert ovf <= 0, f"brick capacity overflow {ovf}"
        t0 = time.perf_counter()
        state, _, _, _, th = program.run(place(state), params_r,
                                         steps // seg, seg)
        jax.block_until_ready(state)
        out["outer_first_call_s"] = time.perf_counter() - t0
        domain.check_segment_thermo(th)
        if not on_four_devices(state.pos, devices):
            raise AssertionError("outer program output left the 4 devices")
        counts = np.asarray(th["n_atoms"]).reshape(-1)
        per_brick = np.asarray(state.mask).sum(axis=1)
        if not (np.all(counts == n) and per_brick.sum() == n
                and np.all(per_brick > 0)):
            raise AssertionError(f"atom count not conserved: {counts}, "
                                 f"per brick {per_brick}")
        # the brick step reports PE at the start of each step, the driver
        # at its end: align both on E(x_1) .. E(x_{steps-1})
        pe_d = np.asarray(th["pe"]).reshape(-1)[1:]
        pe_err = float(np.max(np.abs(pe_d - pe_ref[:-1])) / ke_max)
        check(f"bricks {steps}-step outer run vs single process (highest)",
              {"pe_trajectory": pe_err}, TOL_BRICKS)
        out.update({"outer_steps": steps, "pe_trajectory_err": pe_err,
                    "atoms_per_brick": per_brick.tolist()})
    log(f"bricks ok: {steps} steps, atoms per brick {per_brick.tolist()} "
        f"(sum {int(per_brick.sum())}), outer first call "
        f"{out['outer_first_call_s']:.3f} s")
    return out


# ----------------------------------------------------------------- main

def main(argv=None) -> int:
    args = parse_args(argv)
    import jax
    if args.rehearse and args.four_chips:
        # before the backend starts; only the CPU backend reads it
        jax.config.update("jax_num_cpu_devices", 4)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch import md_run
        from repro.launch.compile_cache import enable_compile_cache
        from repro.core.types import COPPER_DP
    except ImportError as e:
        print(f"chip_smoke: the repo's sources are not next to this script "
              f"({e})", file=sys.stderr)
        return 2

    log(f"jax {jax.__version__} devices: {jax.devices()}")
    info = device_info()
    log(f"platform={info['platform']} device_kind={info['kind']} "
        f"count={info['count']}")
    if info["platform"] != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform {info['platform']}); "
              f"use --rehearse for a CPU run", file=sys.stderr)
        return 1
    log(f"compile cache: {enable_compile_cache()}")

    import dataclasses
    if args.rehearse:
        cfg = dataclasses.replace(md_run.TOY_COPPER, kernel_interpret=True)
        skin, steps, rebuild_every = 1.0, 8, 4
        sizes = ((5, ("mlp", "cheb", "cheb_pallas")), (6, "cheb_pallas"))
        bricks = (6, 4)
    else:
        cfg = COPPER_DP
        skin, steps, rebuild_every = 2.0, 40, 20
        sizes = ((10, ("mlp", "cheb", "cheb_pallas")), (16, "cheb_pallas"))
        bricks = (12, 9)
    log(f"model: rcut {cfg.rcut} sel {cfg.sel} embed {cfg.embed_widths} "
        f"fit {cfg.fit_widths} cheb_order {cfg.cheb_order} "
        f"kernel_interpret={cfg.kernel_interpret}")

    t0 = time.perf_counter()
    if args.four_chips:
        report = {"four_chips": four_chip_phase(cfg, *bricks, skin=skin,
                                                impl="cheb_pallas")}
    else:
        report = {"runs": one_chip_phase(cfg, sizes, skin, steps,
                                         rebuild_every, args.rehearse)}
    report.update({"device": info, "rehearse": args.rehearse,
                   "wall_s": time.perf_counter() - t0})
    if args.report:
        os.makedirs(os.path.dirname(os.path.abspath(args.report)),
                    exist_ok=True)
        with open(args.report, "w") as f:
            json.dump(report, f, indent=1, default=str)
    if args.rehearse:
        log(f"rehearsal passed on {info['platform']} in "
            f"{report['wall_s']:.1f} s (toy width; not a chip result)")
        return 0
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
