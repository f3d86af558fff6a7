"""MD stepping-engine benchmark: python-loop vs scan-segment vs outer scan.

Times the three engines of ``md/driver.py`` on the copper protocol (CPU,
small box — where per-step dispatch and per-segment host-sync overhead are
the dominant taxes the fused engines remove) and, optionally, the
distributed slab driver's whole-trajectory outer program on forced host
devices. Writes ``BENCH_md.json`` so CI records the perf trajectory per PR:

  PYTHONPATH=src python benchmarks/md_step_time.py [--tiny] [--out BENCH_md.json]
  PYTHONPATH=src python benchmarks/md_step_time.py --dist-slabs 2   # + brick driver

Engines are warmed first (compiles cached at module level), then reps are
INTERLEAVED across engines (load spikes on shared runners tax everyone
equally) and both median and min us/step/atom recorded; headline speedups
use the min. The default rebuild cadence (2) keeps segment boundaries
dense: the scan engine pays one host rebuild + overflow sync + thermo
fetch per segment, the outer engine folds all of it into its chunked scan
— that per-segment saving is what ``speedup_outer_over_scan`` tracks.

The distributed legs run in this same process, on the devices it has: a
chip belongs to one process, so a child could not open it. On CPU the
script asks JAX for as many host devices as the largest topology needs
before the backend starts.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time
import traceback

import jax

from repro.core import dp_model
from repro.core.types import DPConfig
from repro.launch import mesh as mesh_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.md import api, driver, lattice
from repro.md.topology import Topology


def copper_cfg(tiny: bool) -> DPConfig:
    if tiny:
        return DPConfig(ntypes=1, rcut=4.0, rcut_smth=2.0, sel=(32,),
                        type_map=("Cu",), embed_widths=(8, 16, 32),
                        axis_neuron=4, fit_widths=(24, 24, 24))
    return DPConfig(ntypes=1, rcut=4.0, rcut_smth=2.0, sel=(48,),
                    type_map=("Cu",), embed_widths=(8, 16, 32),
                    axis_neuron=4, fit_widths=(24, 24, 24))


ENGINES = ("python", "scan", "outer")


def bench_single_process(args, steps: int, reps: int):
    cfg = copper_cfg(args.tiny)
    if args.potential == "lj":
        # near-free force eval: what remains is pure engine machinery —
        # dispatch, rebuild, sync — benchmarkable at much larger --nx
        params = {}
        potential = api.LJPotential(sel=cfg.sel, rcut_lj=cfg.rcut)
    else:
        params = dp_model.init_dp_params(jax.random.PRNGKey(0), cfg)
        if args.impl != "mlp":
            params = dp_model.tabulate_model(
                params, cfg, "quintic" if args.impl == "quintic" else "cheb")
        potential = None                    # run_md wraps cfg/impl
    ensemble, barostat = (None, None) if args.ensemble == "nve" \
        else api.resolve_ensemble(args.ensemble)
    pos, typ, box = lattice.fcc_copper(args.nx, args.nx, args.nx)
    kw = dict(steps=steps, dt_fs=1.0, temp_k=330.0, skin=1.0,
              rebuild_every=args.rebuild_every, thermo_every=50,
              impl=args.impl, chunk_segments=args.chunk_segments,
              potential=potential, ensemble=ensemble, barostat=barostat)

    print(f"{len(pos)} Cu atoms, {steps} steps, rebuild every "
          f"{args.rebuild_every}, impl={args.impl}, "
          f"potential={args.potential}, ensemble={args.ensemble}, "
          f"reps={reps}")
    syncs, times = {}, {e: [] for e in ENGINES}
    for engine in ENGINES:                                           # warm
        syncs[engine] = driver.run_md(cfg, params, pos, typ, box,
                                      engine=engine, **kw).host_syncs
    # INTERLEAVED reps: background load on shared CI runners then taxes
    # every engine equally instead of whichever ran during the spike
    for _ in range(reps):
        for engine in ENGINES:
            times[engine].append(driver.run_md(
                cfg, params, pos, typ, box, engine=engine,
                **kw).us_per_step_atom)
    results = {}
    for engine in ENGINES:
        results[engine] = {
            "us_per_step_atom_median": statistics.median(times[engine]),
            "us_per_step_atom_min": min(times[engine]),
            "us_per_step_atom_all": times[engine],
            "host_syncs": syncs[engine],
        }
        print(f"  engine={engine:7s} median "
              f"{results[engine]['us_per_step_atom_median']:8.2f} "
              f"us/step/atom  (min {min(times[engine]):.2f}, "
              f"host_syncs {syncs[engine]})")
    return results, len(pos)


def _time_distributed(topo: Topology, potential_name: str,
                      ensemble_name: str, rebuild_every: int, steps: int,
                      reps: int) -> dict:
    """Time the brick driver's whole-trajectory outer program (migration +
    rebuild in the scan) on ``topo``, one brick per device."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.md import domain, integrator, stepper

    n_slabs = topo.n_ranks
    if len(jax.devices()) < n_slabs:
        raise RuntimeError(
            f"topology {topo.label()} needs {n_slabs} devices, this process "
            f"has {len(jax.devices())}")
    # always the full config: the tiny sel=(32,) cannot hold the 4.5 A
    # copper neighborhood (~42 neighbors) and DomainSpec escalation is a
    # host replay — keep the timed loop overflow-free by construction
    cfg = copper_cfg(False)
    ensemble, barostat = api.resolve_ensemble(ensemble_name)
    if potential_name == "lj":
        potential = api.LJPotential(sel=cfg.sel, rcut_lj=cfg.rcut)
        params = {}
    else:
        potential = None
        params = dp_model.init_dp_params(jax.random.PRNGKey(0), cfg)
    # WEAK SCALING: constant atoms per brick — the lattice grows with the
    # topology shape (3 FCC cells per brick per decomposed axis; >= 3
    # cells along every axis so min-image stays valid on undecomposed
    # dims and bricks cover rcut_halo on decomposed ones)
    dims = [3 * topo.shape[a] if a < topo.ndim else 3 for a in range(3)]
    pos, typ, box = lattice.fcc_copper(*dims)
    n = len(pos)
    mesh = mesh_lib.make_mesh((n_slabs, 1), ("data", "model"),
                              devices=jax.devices()[:n_slabs])
    cap = int(n / n_slabs * 1.5) + 8
    # skin 0.5: sel=(48,) holds the 4.5 A copper neighborhood with margin;
    # a 1.0 skin overflows it at 330 K. Later halo sweeps pack earlier
    # sweeps' ghosts too, so the send capacity grows with the topology rank
    spec = domain.DomainSpec(box=tuple(box), n_slabs=n_slabs,
                             atom_capacity=cap,
                             halo_capacity=cap * (2 ** (topo.ndim - 1)),
                             rcut_halo=cfg.rcut + 0.5, topology=topo.shape)
    spec.validate()
    masses = jnp.full((n,), 63.546)
    vel = integrator.init_velocities(jax.random.PRNGKey(1), masses, 330.0)
    state0, ovf = domain.partition_atoms(
        pos.astype(np.float32), np.asarray(vel, np.float32), typ, spec)
    assert ovf <= 0
    sh = NamedSharding(mesh, P("data"))
    state0 = jax.tree.map(lambda x: jax.device_put(x, sh), state0)
    params_r = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P())), params)
    program = domain.make_outer_md_program(
        cfg, spec, mesh, (63.546,), 1.0, decomp="atoms", neighbor="cells",
        donate=False, potential=potential, ensemble=ensemble,
        barostat=barostat)
    ens0 = program.init_ensemble_state()
    sched = stepper.chunk_schedule(steps, rebuild_every, 8)

    def one_run():
        state = state0
        ens = ens0
        baro = program.init_barostat_state()
        box_d = None
        t0 = time.time()
        for n_segs, seg_len in sched:
            state, ens, box_d, baro, thermo = program.run(
                state, params_r, n_segs, seg_len, ens, box_d, baro)
            domain.check_segment_thermo(thermo)
        jax.block_until_ready(state)
        return (time.time() - t0) * 1e6 / (steps * n)

    one_run()                                                        # warm
    times = [one_run() for _ in range(reps)]
    return {
        "slabs": n_slabs, "topology": topo.label(), "n_atoms": n,
        "atoms_per_rank": n // n_slabs, "devices": n_slabs,
        "engine": "outer_distributed",
        "potential": potential_name, "ensemble": ensemble_name,
        "us_per_step_atom_median": statistics.median(times),
        "us_per_step_atom_min": min(times),
        "us_per_step_atom_all": times,
    }


def bench_distributed(args, steps: int, reps: int, topology=None,
                      potential=None, ensemble=None):
    """One distributed row, timed in this process; a failure becomes a
    ``{"status": "failed"}`` row (and a nonzero exit from :func:`main`)."""
    topo = Topology.parse(topology or args.dist_topology or args.dist_slabs)
    try:
        row = _time_distributed(topo, potential or args.potential,
                                ensemble or args.ensemble,
                                args.rebuild_every, steps, reps)
    except Exception as e:
        traceback.print_exc()
        print(f"  distributed bench FAILED: {type(e).__name__}: {e}")
        return {"status": "failed", "error": f"{type(e).__name__}: {e}"}
    print(f"  engine=outer_distributed (topology {row['topology']}, "
          f"{row['n_atoms']} atoms, {row['atoms_per_rank']}/rank) median "
          f"{row['us_per_step_atom_median']:8.2f} us/step/atom "
          f"(min {row['us_per_step_atom_min']:.2f})")
    return row


WEAK_SCALING_TOPOLOGIES = ("2", "2x2", "2x2x2")


def bench_weak_scaling(args, steps: int, reps: int):
    """LJ weak-scaling sweep: constant atoms/rank, growing brick topology
    (2 -> 2x2 -> 2x2x2) + one NPT row — per-rank cost should stay ~flat
    as axes are added (the point of the N-D decomposition)."""
    rows = []
    for t in WEAK_SCALING_TOPOLOGIES:
        rows.append(bench_distributed(args, steps, reps, topology=t,
                                      potential="lj", ensemble="nve"))
    rows.append(bench_distributed(args, steps, reps, topology="2x2",
                                  potential="lj", ensemble="npt_berendsen"))
    return rows


def git_sha() -> str:
    """Current commit (env override for CI checkouts), 'unknown' offline."""
    sha = os.environ.get("GITHUB_SHA", "")
    if not sha:
        try:
            sha = subprocess.run(
                ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
                cwd=os.path.dirname(os.path.abspath(__file__))).stdout.strip()
        except OSError:
            sha = ""
    return sha[:12] or "unknown"


def append_trajectory(path: str, payload: dict) -> None:
    """Accumulate per-PR perf history instead of overwriting it.

    The artifact keeps the full ``payload`` of the LATEST run plus a
    ``trajectory`` list of headline rows keyed by git sha (+ the bench
    shape), so speedups are comparable PR-over-PR. Re-running on the same
    sha/shape replaces that entry rather than duplicating it.
    """
    old = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                old = json.load(f)
        except (OSError, ValueError):
            old = {}
    entry = {
        "git_sha": git_sha(),
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "system": payload["system"],
        "n_atoms": payload["n_atoms"],
        "steps": payload["steps"],
        "rebuild_every": payload["rebuild_every"],
        "tiny": payload["tiny"],
        "impl": payload["impl"],
        "potential": payload.get("potential", "dp"),
        "ensemble": payload.get("ensemble", "nve"),
        "us_per_step_atom_min": {
            "python": payload["python_loop"]["us_per_step_atom_min"],
            "scan": payload["scan_segment"]["us_per_step_atom_min"],
            "outer": payload["outer_scan"]["us_per_step_atom_min"],
        },
        "speedup_scan_over_python": payload["speedup_scan_over_python"],
        "speedup_outer_over_scan": payload["speedup_outer_over_scan"],
    }
    # the distributed worker honors --potential/--ensemble, but its timing
    # only belongs on this entry when they match the single-process legs
    # (a DP entry must not carry an LJ worker's number)
    dist = payload.get("distributed", {})
    if dist.get("us_per_step_atom_min") and \
            (entry["potential"], entry["ensemble"]) == \
            (dist.get("potential", "dp"), dist.get("ensemble", "nve")):
        entry["us_per_step_atom_min"]["outer_distributed"] = \
            dist["us_per_step_atom_min"]
        entry["distributed_topology"] = dist.get("topology")

    def _key(e):
        # the full protocol shape: entries measured under different
        # steps/rebuild cadence (or topology) are NOT comparable and must
        # coexist
        return (e.get("git_sha"), e.get("benchmark", "md_step_time"),
                e.get("system"), e.get("steps"), e.get("rebuild_every"),
                e.get("tiny"), e.get("impl"), e.get("potential", "dp"),
                e.get("ensemble", "nve"), e.get("topology"))

    new_entries = [entry]
    for row in payload.get("weak_scaling", []):
        if row.get("status") == "failed" or \
                not row.get("us_per_step_atom_min"):
            continue
        # weak-scaling rows are keyed by TOPOLOGY shape: the trajectory
        # tracks per-rank cost as decomposition axes are added, PR-over-PR
        new_entries.append({
            "git_sha": entry["git_sha"], "utc": entry["utc"],
            "benchmark": "md_weak_scaling",
            "topology": row["topology"],
            "potential": row["potential"], "ensemble": row["ensemble"],
            "n_atoms": row["n_atoms"],
            "atoms_per_rank": row["atoms_per_rank"],
            "steps": payload["steps"],
            "rebuild_every": payload["rebuild_every"],
            "us_per_step_atom_min": row["us_per_step_atom_min"],
        })
    keys = {_key(e) for e in new_entries}
    traj = [e for e in old.get("trajectory", []) if _key(e) not in keys]
    traj.extend(new_entries)
    payload["trajectory"] = traj


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke shape: smallest box/model, fewer steps")
    ap.add_argument("--nx", type=int, default=2, help="FCC supercell edge")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--rebuild-every", type=int, default=2,
                    help="segment length; small by design — the benchmark "
                         "measures segment-BOUNDARY overhead (host rebuild "
                         "+ sync for scan, none for outer), so boundaries "
                         "are kept dense")
    ap.add_argument("--reps", type=int, default=None)
    ap.add_argument("--chunk-segments", type=int, default=32,
                    help="outer engine: segments fused per dispatch")
    ap.add_argument("--impl", default="mlp", choices=("mlp", "quintic", "cheb"))
    ap.add_argument("--potential", default="dp", choices=("dp", "lj"),
                    help="lj: near-free forces isolate engine overhead "
                         "(and allow much larger --nx)")
    ap.add_argument("--ensemble", default="nve",
                    choices=api.ENSEMBLE_CHOICES)
    ap.add_argument("--min-speedup", type=float, default=None,
                    help="exit nonzero if scan/python speedup falls below")
    ap.add_argument("--min-outer-speedup", type=float, default=None,
                    help="exit nonzero if outer/scan speedup falls below")
    ap.add_argument("--dist-slabs", type=int, default=0,
                    help="also benchmark the distributed brick driver on "
                         "this many forced host devices (0: skip); legacy "
                         "1-D spelling of --dist-topology k")
    ap.add_argument("--dist-topology", default=None,
                    help="benchmark the distributed driver on this brick "
                         "topology (e.g. 2x2x2), one brick per device; on "
                         "CPU this process gets prod(shape) host devices")
    ap.add_argument("--weak-scaling", action="store_true",
                    help="LJ weak-scaling sweep: constant atoms/rank over "
                         "topologies 2 -> 2x2 -> 2x2x2 (+ one NPT row), "
                         "appended to the BENCH trajectory keyed by "
                         "topology shape")
    ap.add_argument("--out", default="BENCH_md.json")
    args = ap.parse_args(argv)

    steps = args.steps or 99
    reps = args.reps or (3 if args.tiny else 5)
    ranks = [Topology.parse(t).n_ranks for t in
             ([args.dist_topology or args.dist_slabs]
              if args.dist_slabs or args.dist_topology else [])
             + (list(WEAK_SCALING_TOPOLOGIES) if args.weak_scaling else [])]
    if ranks and jax.config.jax_num_cpu_devices < max(ranks):
        # must precede the first JAX op; only the CPU backend reads it
        jax.config.update("jax_num_cpu_devices", max(ranks))
    enable_compile_cache()

    results, n_atoms = bench_single_process(args, steps, reps)

    # speedups from per-engine MIN: on time-shared runners the min is the
    # least load-polluted estimate of each engine's true cost (medians of
    # interleaved reps still swing tens of percent under noisy neighbors)
    speedup = (results["python"]["us_per_step_atom_min"]
               / results["scan"]["us_per_step_atom_min"])
    outer_speedup = (results["scan"]["us_per_step_atom_min"]
                     / results["outer"]["us_per_step_atom_min"])
    print(f"scan-segment speedup over python-loop: {speedup:.2f}x")
    print(f"outer-scan speedup over scan-segment:  {outer_speedup:.2f}x")

    payload = {
        "benchmark": "md_step_time",
        "system": f"fcc_cu_{args.nx}x{args.nx}x{args.nx}",
        "n_atoms": n_atoms,
        "steps": steps,
        "rebuild_every": args.rebuild_every,
        "impl": args.impl,
        "potential": args.potential,
        "ensemble": args.ensemble,
        "tiny": args.tiny,
        "backend": jax.default_backend(),
        "jax_version": jax.__version__,
        "python_loop": results["python"],
        "scan_segment": results["scan"],
        "outer_scan": results["outer"],
        "speedup_scan_over_python": speedup,
        "speedup_outer_over_scan": outer_speedup,
    }
    if args.dist_slabs or args.dist_topology:
        payload["distributed"] = bench_distributed(args, steps, reps)
    if args.weak_scaling:
        payload["weak_scaling"] = bench_weak_scaling(args, steps, reps)
    append_trajectory(args.out, payload)
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {args.out} ({len(payload['trajectory'])} trajectory "
          f"entries)")

    rc = 0
    if payload.get("distributed", {}).get("status") == "failed":
        # a broken distributed leg must fail the job, not just the artifact
        print("FAIL: distributed benchmark worker failed")
        rc = 1
    if any(r.get("status") == "failed"
           for r in payload.get("weak_scaling", [])):
        print("FAIL: weak-scaling benchmark worker failed")
        rc = 1
    if args.min_speedup is not None and speedup < args.min_speedup:
        print(f"FAIL: scan speedup {speedup:.2f}x < required "
              f"{args.min_speedup:.2f}x")
        rc = 1
    if (args.min_outer_speedup is not None
            and outer_speedup < args.min_outer_speedup):
        print(f"FAIL: outer speedup {outer_speedup:.2f}x < required "
              f"{args.min_outer_speedup:.2f}x")
        rc = 1
    return rc


def run():
    """``benchmarks.run`` entry: tiny shape, one rep, headline CSV rows.

    Writes/extends ``BENCH_md.json`` exactly like the CLI (the trajectory
    list accumulates across PRs, keyed by git sha + protocol shape). A
    second NPT invocation appends an ``npt_berendsen`` trajectory row so
    the artifact tracks the carried-box overhead vs the NVE path.
    """
    rc_npt = main(["--tiny", "--reps", "1", "--steps", "40",
                   "--ensemble", "npt_berendsen"])
    rc = main(["--tiny", "--reps", "1", "--steps", "40"])
    with open("BENCH_md.json") as f:
        payload = json.load(f)
    rows = [{"engine": name,
             "us_per_step_atom_min": payload[key]["us_per_step_atom_min"],
             "host_syncs": payload[key]["host_syncs"],
             "failed": rc != 0}
            for name, key in (("python", "python_loop"),
                              ("scan", "scan_segment"),
                              ("outer", "outer_scan"))]
    npt_rows = [e for e in payload.get("trajectory", [])
                if e.get("ensemble") == "npt_berendsen"]
    # a failed NPT invocation must not surface a PRIOR commit's trajectory
    # entry as this run's timing — report the failure, not stale numbers
    if npt_rows and rc_npt == 0:
        npt = npt_rows[-1]
        for eng in ("scan", "outer"):
            rows.append({"engine": f"{eng}_npt",
                         "us_per_step_atom_min":
                             npt["us_per_step_atom_min"][eng],
                         "host_syncs": -1, "failed": False})
    elif rc_npt != 0:
        rows.append({"engine": "scan_npt", "us_per_step_atom_min": -1.0,
                     "host_syncs": -1, "failed": True})
    return rows


if __name__ == "__main__":
    sys.exit(main())
