"""Distributed MD driver: run the paper's protocol on whatever devices exist.

  PYTHONPATH=src python -m repro.launch.md_run --nx 10 --nyz 10 \
      --impl cheb_pallas --steps 40              # one chip, paper's copper
  PYTHONPATH=src python -m repro.launch.md_run --config toy --slabs 4 \
      --model-axis 2 --nx 8 --steps 99           # CPU smoke, toy net
  PYTHONPATH=src python -m repro.launch.md_run --config toy \
      --topology 2x2x2 --nx 6 --nyz 6 --steps 99

``--profile DIR`` runs the simulation under ``jax.profiler.trace(DIR)``;
the README's "Tracing a run" names the scopes, spans and counters it shows.

``--config`` picks the model: the paper's ``copper`` (default) or
``water`` (``configs/dpmd_*``: published widths, seeded random weights) or
a ``toy`` copper net (rcut 4, sel 96) small enough for CPU smoke runs.
Copper runs on an FCC lattice of ``nx x nyz x nyz`` cells, water on
replicated 64-molecule cells.

Uses the shard_map'd brick-decomposition step (staged per-axis halo sweeps
+ reverse force comm + model-axis decomposition). ``--topology`` picks the
N-D brick shape over the spatial mesh axis (``2x2x2`` = 8 bricks, one per
device at ``--model-axis 1``); ``--slabs k`` is the legacy 1-D spelling
``(k,)``. Per decomposed axis the box must satisfy
``box[a]/shape[a] >= rcut_halo``. Two engines:

  --engine outer  (default) the whole-trajectory program: migration +
                  rebuild folded INTO one two-level lax.scan; one dispatch
                  and one host sync (thermo + overflow flags) per chunk of
                  segments.
  --engine scan   one scan dispatch per rebuild segment, migration at
                  segment boundaries from the host loop.

On a single device both degenerate to 1 slab x 1 shard of the same program.

The force model and the thermostat plug in through the composable
simulation API (``--potential dp|quintic|cheb|lj``, ``--impl`` picks the
DP rung up to the fused Pallas kernel ``cheb_pallas``, ``--ensemble
nve|nvt_langevin|berendsen``): the same scanned programs run the DP ladder
or the near-free analytic LJ, NVE or thermostatted, single-process or
slab-decomposed.
"""

import argparse
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import dpmd_copper, dpmd_water
from repro.core.types import DPConfig
from repro.launch import mesh as mesh_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.md import api, domain, integrator, lattice, stepper
from repro.md.topology import Topology

#: small copper net for CPU smoke runs (not a published model)
TOY_COPPER = DPConfig(ntypes=1, rcut=4.0, rcut_smth=2.0, sel=(96,),
                      type_map=("Cu",), embed_widths=(8, 16, 32),
                      axis_neuron=4, fit_widths=(32, 32, 32))
CONFIGS = {"copper": dpmd_copper.CONFIG, "water": dpmd_water.CONFIG,
           "toy": TOY_COPPER}


def build_system(config: str, nx: int, nyz: int):
    """(pos, typ, box) for a config: water cells for water, FCC otherwise."""
    if config == "water":
        return lattice.water_box(nx, nyz, nyz)
    return lattice.fcc_copper(nx, nyz, nyz)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="copper", choices=tuple(CONFIGS),
                    help="DP model: the paper's copper or water at "
                         "published width, or the toy net for CPU smoke")
    ap.add_argument("--nx", type=int, default=8,
                    help="lattice cells along x (FCC, or 64-molecule water)")
    ap.add_argument("--nyz", type=int, default=3,
                    help="lattice cells along y/z (min-image needs box >= "
                         "2*rcut_halo)")
    ap.add_argument("--slabs", type=int, default=None,
                    help="spatial slabs (default: n_devices / model_axis); "
                         "legacy 1-D spelling of --topology k")
    ap.add_argument("--topology", default=None,
                    help="N-D brick shape over the spatial axis, e.g. "
                         "2x2x2 or 2x4 (overrides --slabs); per axis "
                         "box[a]/shape[a] >= rcut_halo must hold")
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--steps", type=int, default=99)
    ap.add_argument("--dt", type=float, default=None,
                    help="timestep (fs); default 0.5 for water, 1 otherwise")
    ap.add_argument("--temp", type=float, default=330.0)
    ap.add_argument("--rebuild-every", type=int, default=20)
    ap.add_argument("--engine", default="outer", choices=("outer", "scan"))
    ap.add_argument("--chunk-segments", type=int, default=8,
                    help="outer engine: rebuild segments fused per dispatch")
    ap.add_argument("--impl", default="mlp",
                    choices=("mlp", "quintic", "cheb", "cheb_pallas"))
    ap.add_argument("--potential", default="dp",
                    choices=api.POTENTIAL_CHOICES,
                    help="force model (lj needs no DP params at all)")
    ap.add_argument("--ensemble", default="nve",
                    choices=api.ENSEMBLE_CHOICES,
                    help="npt_* names pair a thermostat with a barostat: "
                         "the box rides in the scan carry")
    ap.add_argument("--friction", type=float, default=0.1,
                    help="nvt_langevin friction (1/fs)")
    ap.add_argument("--tau", type=float, default=100.0,
                    help="berendsen time constant (fs)")
    ap.add_argument("--pressure", type=float, default=None,
                    help="target pressure (GPa); with a non-NPT ensemble "
                         "this attaches a Berendsen barostat (matching the "
                         "SimulationSpec.pressure_gpa behavior)")
    ap.add_argument("--ptau", type=float, default=500.0,
                    help="barostat time constant (fs)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="run the simulation under jax.profiler.trace(DIR): "
                         "the device ops carry the program's md.*/dp.* "
                         "scopes, the host its md.* spans")
    args = ap.parse_args(argv)
    enable_compile_cache()
    profile = (jax.profiler.trace(args.profile) if args.profile
               else contextlib.nullcontext())
    with profile:
        simulate(args)


def simulate(args):
    """Build the system and run it as ``args`` ask (see :func:`main`)."""
    dt = args.dt if args.dt is not None else \
        (0.5 if args.config == "water" else 1.0)

    n_dev = len(jax.devices())
    if args.topology:
        topo = Topology.parse(args.topology)
    elif args.slabs:
        topo = Topology((args.slabs,)) if args.slabs >= 2 else None
    else:
        k = max(n_dev // args.model_axis, 1)
        topo = Topology((k,)) if k >= 2 else None
    n_slabs = topo.n_ranks if topo is not None else 1

    cfg = CONFIGS[args.config]
    masses_t = tuple(lattice.MASS[t] for t in cfg.type_map)
    # resolve_ensemble owns the coupling policy: npt_* names expand to a
    # thermostat + barostat pair, and an explicit --pressure attaches a
    # Berendsen barostat to any ensemble (same as SimulationSpec)
    ensemble, barostat = api.resolve_ensemble(
        args.ensemble, temp_k=args.temp, friction=args.friction,
        tau_fs=args.tau, pressure_gpa=args.pressure, ptau_fs=args.ptau)
    if args.potential == "lj":
        potential = api.LJPotential(sel=cfg.sel, rcut_lj=cfg.rcut)
        params = {}
    else:
        # make_potential resolves "dp" + a tabulated --impl to the
        # tabulated adapter, which owns the params post-processing
        potential = api.make_potential(args.potential, cfg, impl=args.impl)
        params = potential.init_params(jax.random.PRNGKey(0))

    if n_slabs < 2:
        # no decomposition to exercise — the single-process driver is the
        # right tool (the slab machinery assumes >= 2 slabs so that ghost
        # images never alias their owners).
        pos, typ, box = build_system(args.config, args.nx, args.nyz)
        sim = api.SimulationSpec(
            potential=potential, ensemble=ensemble, steps=args.steps,
            dt_fs=dt, temp_k=args.temp, skin=0.5,
            rebuild_every=args.rebuild_every, thermo_every=33,
            engine=args.engine, chunk_segments=args.chunk_segments,
            barostat=barostat)
        res = api.Simulation(sim).run(params, pos, typ, box)
        for row in res.thermo:
            print(f"step {row['step']:4d}  E_pot {row['pe']:+.4f}  "
                  f"E_tot {row['etot']:+.4f}  T {row['temp']:.0f} K")
        print(f"{res.us_per_step_atom:.2f} us/step/atom wall "
              f"(single process, {res.n_atoms} atoms)")
        return

    mesh = mesh_lib.make_mesh((n_slabs, args.model_axis), ("data", "model"))

    pos, typ, box = build_system(args.config, args.nx, args.nyz)
    rng = np.random.default_rng(0)
    pos = np.mod(pos + rng.normal(0, 0.02, pos.shape), box)
    n = len(pos)
    cap = int(n / n_slabs * 1.5) + 8
    # later sweeps pack owned atoms PLUS earlier sweeps' ghosts, so the
    # per-side send capacity grows with the decomposed rank
    halo_cap = cap * (2 ** (topo.ndim - 1))
    spec = domain.DomainSpec(box=tuple(box), n_slabs=n_slabs,
                             atom_capacity=cap - cap % args.model_axis,
                             halo_capacity=halo_cap,
                             rcut_halo=cfg.rcut + 0.5,
                             topology=topo.shape)
    spec.validate()

    masses = jnp.asarray(lattice.masses_for(cfg.type_map, typ))
    vel = integrator.init_velocities(jax.random.PRNGKey(1), masses, args.temp)
    state, ovf = domain.partition_atoms(
        pos.astype(np.float32), np.asarray(vel, np.float32), typ, spec)
    assert ovf <= 0, f"slab capacity overflow {ovf}"
    sh = NamedSharding(mesh, P("data"))
    state = jax.tree.map(lambda x: jax.device_put(x, sh), state)
    params_r = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P())), params)

    print(f"{n} atoms, topology {topo.label()} ({n_slabs} bricks) x "
          f"{args.model_axis} model shards on {n_dev} devices, "
          f"engine={args.engine}, potential={args.potential}, "
          f"ensemble={args.ensemble}"
          + (f", P0={args.pressure or 0.0} GPa"
             if barostat is not None else ""))

    def show(thermo, base, count):
        pe = np.asarray(thermo["pe"]).reshape(-1)
        ke = np.asarray(thermo["ke"]).reshape(-1)
        natoms = np.asarray(thermo["n_atoms"]).reshape(-1)
        press = np.asarray(thermo["press"]).reshape(-1)
        vol = np.asarray(thermo["vol"]).reshape(-1)
        for i in range(count):
            gstep = base + i + 1
            if gstep % 33 == 0 or gstep == 1:
                print(f"step {gstep:4d}  E_pot {pe[i]:+.4f}  "
                      f"E_tot {pe[i]+ke[i]:+.4f}  "
                      f"P {press[i] * integrator.EV_A3_TO_GPA:+.2f} GPa  "
                      f"V {vol[i]:.0f} A^3  atoms {int(natoms[i])}",
                      flush=True)

    boxd = None     # dynamic box: carried across dispatches (None: launch)
    if args.engine == "outer":
        policy = stepper.EscalationPolicy()

        def build_program(spec_run):
            return domain.make_outer_md_program(
                cfg, spec_run, mesh, masses_t, dt, impl=args.impl,
                decomp="atoms", neighbor="cells", potential=potential,
                ensemble=ensemble, barostat=barostat)

        spec_run = spec
        program = build_program(spec_run)
        ens = program.init_ensemble_state()
        baro = program.init_barostat_state()
        t0 = time.time()
        base = 0
        for n_segs, seg_len in stepper.chunk_schedule(
                args.steps, args.rebuild_every, args.chunk_segments):
            # ONE dispatch per chunk of segments; migration + rebuild run
            # inside the scanned program. One host fetch checks the chunk's
            # stacked overflow flags and prints its thermo; the dynamic box
            # and barostat state come back in the same carry. A capacity
            # overflow (a barostat-squeezed box raises per-brick density)
            # REPLAYS the chunk from its entry snapshot with DomainSpec
            # capacities escalated by the carried-box volume ratio and the
            # atoms re-partitioned into the new layout.
            for attempt in range(policy.max_attempts + 1):
                snap = (jax.device_get((state, ens, boxd, baro))
                        if program._donate else (state, ens, boxd, baro))
                try:
                    state, ens, boxd, baro, thermo = program.run(
                        state, params_r, n_segs, seg_len, ens, boxd, baro)
                    domain.check_segment_thermo(thermo)
                    break
                except RuntimeError as e:
                    if "geom_overflow" in str(e) \
                            or attempt == policy.max_attempts:
                        raise
                    state, ens, boxd, baro = snap
                    box_now = np.asarray(
                        boxd if boxd is not None else spec.box, float)
                    spec_run = domain.escalate_capacities(
                        spec_run, policy, box_now=box_now,
                        n_model=args.model_axis)
                    print(f"  capacity overflow ({e}); replaying chunk "
                          f"with atom_capacity={spec_run.atom_capacity}, "
                          f"halo_capacity={spec_run.halo_capacity} "
                          f"(carried-box volume folded in)", flush=True)
                    state, r_ovf = domain.repartition_state(
                        state, spec_run, box_now=box_now)
                    assert r_ovf <= 0, f"repartition overflow {r_ovf}"
                    state = jax.tree.map(lambda x: jax.device_put(x, sh),
                                         state)
                    program = build_program(spec_run)
            show(thermo, base, n_segs * seg_len)
            base += n_segs * seg_len
    else:
        step = domain.make_distributed_md_step(
            cfg, spec, mesh, masses_t, dt, impl=args.impl,
            decomp="atoms", neighbor="cells", potential=potential,
            ensemble=ensemble, barostat=barostat)
        run_segment = domain.make_segment_runner(step)
        migrate = domain.make_migration_step(spec, mesh)
        ens = domain.init_ensemble_state(ensemble, n_slabs, mesh)
        baro = barostat.init_state() if barostat is not None else ()
        boxd = stepper.pack_box(box)
        t0 = time.time()
        base = 0
        for seg_len in stepper.segment_schedule(args.steps,
                                                args.rebuild_every):
            # one scan dispatch per segment; thermo/overflow fetched after
            (state, ens, boxd, baro), thermo = run_segment(
                state, params_r, seg_len, ens, boxd, baro)
            domain.check_segment_thermo(thermo)
            show(thermo, base, seg_len)
            base += seg_len
            if seg_len == args.rebuild_every:  # full segment: migration
                state, movf = migrate(state, boxd)
                assert int(movf) <= 0, "migration overflow"
    jax.block_until_ready(state)
    dt_wall = time.time() - t0
    if boxd is not None and barostat is not None:
        print(f"final box {np.round(np.asarray(boxd), 3)} A")
    print(f"{dt_wall/args.steps*1e6/n:.2f} us/step/atom wall (this host)")


if __name__ == "__main__":
    main()
