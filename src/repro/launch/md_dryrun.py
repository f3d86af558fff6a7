import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod MD dry-run: the paper's own workload on the production mesh.

Cells (copper / water, per DESIGN.md Sec. 5):
  cu_weak   — 122,779 atoms/chip (paper's Summit per-GPU load; weak-scaling
              parity): 31.4M atoms on the 16x16 pod, 62.9M on 2x16x16.
  cu_strong — the 13.5M-atom copper system (the paper's 11.2 ns/day strong-
              scaling headline) on 256 chips.
  h2o_weak  — 41.47M-atom water (paper's Summit strong-scaling system size)
              at 162k atoms/chip.

Per cell x impl in {mlp, quintic, cheb, cheb_pallas}: lower + compile the
shard_map'd distributed MD step scanned over a ``--segment-len``-step
rebuild segment (the fused on-device inner loop of ``md/stepper.py`` — the
program production actually dispatches), then record memory_analysis (the
paper's max-atoms-per-device story: the baseline materializes G_i, the
fused path never does) and the roofline terms.

With ``--outer-segments N`` (N > 0) the lowered program is the
whole-trajectory two-level scan instead (``domain.make_outer_md_program``):
N segments of (scan-safe migration + ``--segment-len`` steps) fused into a
single dispatch — the compile proof that migration + rebuild fold into the
scanned program at paper scale.
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import roofline as rl
from repro.core import dp_model
from repro.core.types import COPPER_DP, WATER_DP, DPConfig
from repro.kernels.dp_fused import ops as fused_ops
from repro.launch import mesh as mesh_mod
from repro.md import api, domain, stepper
from repro.md.topology import Topology


@dataclasses.dataclass(frozen=True)
class MDCell:
    name: str
    cfg: DPConfig
    atoms_per_chip: int
    dt_fs: float
    masses: Tuple[float, ...]
    density: float               # atoms / A^3


CU = MDCell("cu", COPPER_DP, 122_779, 1.0, (63.546,), 4 / 3.634**3)
CU_STRONG = MDCell("cu_strong", COPPER_DP, 52_734, 1.0, (63.546,),
                   4 / 3.634**3)
H2O = MDCell("h2o", WATER_DP, 162_000, 0.5, (15.999, 1.008),
             192 / 12.42**3)

IMPLS = ("mlp", "quintic", "cheb", "cheb_pallas")


def geometry(cell: MDCell, n_slabs: int, n_model: int,
             topology: Optional[Tuple[int, ...]] = None
             ) -> Tuple[domain.DomainSpec, int]:
    """Brick box sized so each chip owns ``atoms_per_chip`` centers.

    ``topology`` picks the N-D brick shape over the spatial ranks (default:
    the 1-D ``(n_slabs,)`` slab column). Decomposed axes get a brick edge
    of at least ``2.2 * rc_halo``; the remaining volume spreads over the
    undecomposed axes (or inflates the brick for a full 3-D topology).
    """
    topo = Topology.parse(topology if topology is not None else (n_slabs,))
    assert topo.n_ranks == n_slabs, (topo.shape, n_slabs)
    cap = cell.atoms_per_chip * n_model
    cap = -(-cap // n_model) * n_model
    brick_volume = cap / cell.density
    rc_halo = cell.cfg.rcut + 2.0
    w_min = max(2.2 * rc_halo, 25.0)
    ndim = topo.ndim
    if ndim == 3:
        w = max(brick_volume ** (1.0 / 3.0), w_min)
        edges = (w, w, w)
    elif ndim == 2:
        rest = brick_volume / (w_min * w_min)
        edges = (w_min, w_min, max(rest, 1.0))
    else:
        yz = float(np.sqrt(brick_volume / w_min))
        edges = (w_min, yz, yz)
    box = tuple(edges[a] * (topo.shape[a] if a < ndim else 1)
                for a in range(3))
    # per-axis halo fraction; later sweeps pack earlier sweeps' ghosts too,
    # so the send capacity grows with the decomposed rank
    halo_frac = max(rc_halo / edges[a] for a in range(ndim))
    halo_cap = int(cap * halo_frac * 1.4 * 1.6 ** (ndim - 1)) + 1024
    spec = domain.DomainSpec(
        box=box, n_slabs=n_slabs,
        atom_capacity=int(cap * 1.08) // n_model * n_model,
        halo_capacity=halo_cap, rcut_halo=rc_halo, topology=topo.shape)
    return spec, cap


def dp_model_flops(cfg: DPConfig, n_atoms: int, impl: str) -> float:
    """Useful FLOPs per MD step (fwd + force backward ~ 3x fwd).

    Embedding (paper Sec. 3.2): mlp = Nm*d1 + 10*Nm*d1^2 per atom;
    tabulated = 56*Nm*d1. Descriptor contraction + fitting added for all.
    """
    nm = cfg.nsel
    d1 = cfg.embed_widths[0]
    m = cfg.m_embed
    if impl == "mlp":
        embed = nm * d1 + 10 * nm * d1 * d1
    else:
        embed = 56 * nm * d1
    contract = 2 * nm * 4 * m + 2 * 4 * m * cfg.axis_neuron
    fit_in = cfg.descriptor_dim
    fit = 2 * (fit_in * cfg.fit_widths[0]
               + cfg.fit_widths[0] * cfg.fit_widths[1]
               + cfg.fit_widths[1] * cfg.fit_widths[2] + cfg.fit_widths[2])
    return 3.0 * n_atoms * (embed + contract + fit)


def lower_md_cell(cell: MDCell, impl: str, mesh, multi_pod: bool,
                  verbose: bool = True, segment_len: int = 4,
                  outer_segments: int = 0, potential_name: str = "dp",
                  ensemble: Optional[Any] = None,
                  barostat: Optional[Any] = None,
                  topology: Optional[str] = None) -> Dict[str, Any]:
    spatial_axis = ("pod", "data") if multi_pod else "data"
    n_slabs = mesh.shape["data"] * (mesh.shape.get("pod", 1))
    n_model = mesh.shape["model"]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    ensemble = ensemble or api.NVE()
    name = f"dpmd_{cell.name}/{impl}/{mesh_name}"
    if potential_name != "dp":
        name = f"{potential_name}_{cell.name}/{mesh_name}"
    if topology:
        name += f"/topo{Topology.parse(topology).label()}"
    if type(ensemble) is not api.NVE:
        name += f"/{type(ensemble).__name__}"
    if barostat is not None:
        name += f"/{type(barostat).__name__}"
    if outer_segments:
        name += f"/outer{outer_segments}"
    try:
        spec, cap = geometry(cell, n_slabs, n_model, topology=topology)
        # the mesh is forced CPU host devices, so the Pallas rung lowers
        # through the kernel interpreter (tests/test_tpu_compile.py
        # compiles it for the TPU)
        cfg = dataclasses.replace(cell.cfg, impl=impl, kernel_interpret=True)
        potential = None                 # make_local_md_step wraps cfg/impl
        if potential_name == "lj":
            potential = api.LJPotential(sel=tuple(cfg.sel), rcut_lj=cfg.rcut)

        key = jax.random.PRNGKey(0)

        def make_params(k):
            if potential_name == "lj":
                return {}
            p = dp_model.init_dp_params(k, cfg)
            if impl in ("quintic", "cheb", "cheb_pallas"):
                kind = "quintic" if impl == "quintic" else "cheb"
                p = dp_model.tabulate_model(p, cfg, kind)
            return p

        params_shapes = jax.eval_shape(make_params, key)
        ens_shapes = jax.eval_shape(lambda: ensemble.init_state(n_slabs))
        baro_shapes = jax.eval_shape(
            lambda: barostat.init_state()) if barostat is not None else ()
        box_shape = jax.ShapeDtypeStruct((3,), jnp.float32)
        if outer_segments:
            # whole-trajectory program: migration + rebuild inside the scan
            program = domain.make_outer_md_program(
                cfg, spec, mesh, cell.masses, cell.dt_fs, impl=impl,
                spatial_axis=spatial_axis, decomp="atoms", neighbor="cells",
                potential=potential, ensemble=ensemble, barostat=barostat)
            seg_fn = program.build(outer_segments, segment_len)
        else:
            step_fn = domain.make_distributed_md_step(
                cfg, spec, mesh, cell.masses, cell.dt_fs, impl=impl,
                spatial_axis=spatial_axis, decomp="atoms", neighbor="cells",
                potential=potential, ensemble=ensemble, barostat=barostat)

            def seg_fn(params, state, ens, box, baro):
                # the production inner loop: one scan per rebuild segment
                # (the dynamic box + barostat state ride in the carry)
                (state, ens, box, baro), th = stepper.scan_segment(
                    lambda c, p: step_fn(p, *c), (state, ens, box, baro),
                    segment_len, params)
                return state, ens, box, baro, th

        sl = spec.atom_capacity
        state_shapes = domain.SlabState(
            pos=jax.ShapeDtypeStruct((n_slabs, sl, 3), jnp.float32),
            vel=jax.ShapeDtypeStruct((n_slabs, sl, 3), jnp.float32),
            typ=jax.ShapeDtypeStruct((n_slabs, sl), jnp.int32),
            mask=jax.ShapeDtypeStruct((n_slabs, sl), jnp.bool_))
        sp = P(spatial_axis) if isinstance(spatial_axis, str) else P(spatial_axis)
        state_sh = domain.SlabState(*(NamedSharding(mesh, sp),) * 4)
        rep_tree = jax.tree.map(lambda _: NamedSharding(mesh, P()), params_shapes)
        ens_sh = jax.tree.map(lambda _: NamedSharding(mesh, sp), ens_shapes)
        rep = NamedSharding(mesh, P())
        baro_sh = jax.tree.map(lambda _: rep, baro_shapes)
        thermo_keys = list(domain.THERMO_KEYS)
        if outer_segments:
            thermo_keys.append("mig_overflow")
        thermo_sh = {k: NamedSharding(mesh, P()) for k in thermo_keys}

        t0 = time.time()
        jitted = jax.jit(seg_fn,
                         in_shardings=(rep_tree, state_sh, ens_sh, rep,
                                       baro_sh),
                         out_shardings=(state_sh, ens_sh, rep, baro_sh,
                                        thermo_sh),
                         donate_argnums=(1,))
        lowered = jitted.lower(params_shapes, state_shapes, ens_shapes,
                               box_shape, baro_shapes)
        compiled = lowered.compile()
        t_compile = time.time() - t0

        n_atoms_global = cap * n_slabs
        mesh_shape = tuple(mesh.shape[a] for a in mesh.axis_names)
        steps_lowered = segment_len * max(outer_segments, 1)
        if potential_name == "lj":
            # ~30 flops per neighbor slot, fwd + force backward ~ 3x
            model_flops = 3.0 * n_atoms_global * cfg.nsel * 30.0
        else:
            model_flops = dp_model_flops(cfg, n_atoms_global, impl)
        report = rl.analyze_compiled(
            name, compiled, n_chips=mesh.size,
            model_flops=steps_lowered * model_flops,
            mesh_shape=mesh_shape)
        if impl == "cheb_pallas":
            # interpret=True lowers the kernel as a scanned XLA program whose
            # per-grid-step slices the HLO byte model counts as HBM traffic;
            # on TPU those tiles are VMEM-resident BY CONSTRUCTION (BlockSpec)
            # and never reach HBM. Replace the memory term with the kernel's
            # block-level dataflow: fwd reads env+s, writes T; bwd reads
            # env+s+dT, writes ds+denv; coeffs resident across the grid.
            a_chip = n_atoms_global // mesh.size
            nm = cfg.nsel
            m = cfg.m_embed
            fwd = a_chip * nm * 5 * 4 + a_chip * 4 * m * 4
            bwd = a_chip * nm * 5 * 4 + a_chip * 4 * m * 4 \
                + a_chip * nm * 5 * 4
            kernel_bytes = float(steps_lowered * (fwd + bwd))
            # non-kernel traffic (neighbor search, env build, fitting net,
            # integration) approximated by the cheb XLA path's non-G share:
            # keep the artifact's bytes for everything outside the kernel by
            # subtracting the interpret-scan inflation (grid-step slices).
            report.hlo_bytes = kernel_bytes \
                + steps_lowered * 6 * 4 * a_chip * nm          # env build
            report.t_memory = report.hlo_bytes / report.hw.hbm_bw
            # Redundancy removal (paper Sec. 3.4.2): the kernel's pl.when
            # skips neighbor tiles past each atom tile's real count; the
            # interpret-mode HLO counts the masked tiles as executed. Correct
            # the compute term by the live-tile fraction from the system
            # geometry (real neighbors = density * 4/3 pi rcut^3).
            block_n = fused_ops.DEFAULT_BLOCK_N
            nbr_real = cell.density * 4.0 / 3.0 * np.pi * cfg.rcut ** 3
            n_tiles = -(-nm // block_n)
            live = min(-(-int(nbr_real) // block_n), n_tiles)
            report.t_compute *= live / n_tiles
            report.hlo_flops *= live / n_tiles
        ma = compiled.memory_analysis()
        row = report.row()
        row.update({
            "cell": name, "status": "ok", "impl": impl,
            "atoms_global": n_atoms_global,
            "atoms_per_chip": n_atoms_global // mesh.size,
            "t_compile_s": round(t_compile, 1),
            "arg_bytes": int(ma.argument_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
        })
        if verbose:
            print(f"[ok] {name}: atoms/chip {row['atoms_per_chip']}, "
                  f"compile {t_compile:.0f}s, mem/chip {row['mem_GiB']:.2f} "
                  f"GiB, dominant={row['dominant']}, "
                  f"t=(c {report.t_compute*1e3:.1f} | m "
                  f"{report.t_memory*1e3:.1f} | coll "
                  f"{report.t_collective*1e3:.2f}) ms useful="
                  f"{row['useful_ratio']:.2f}", flush=True)
        return row
    except Exception as e:
        traceback.print_exc()
        print(f"[FAIL] {name}: {type(e).__name__}: {e}", flush=True)
        return {"cell": name, "status": "failed",
                "error": f"{type(e).__name__}: {e}"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--system", action="append",
                    choices=("cu", "cu_strong", "h2o"), default=None)
    ap.add_argument("--impl", action="append", choices=IMPLS, default=None)
    ap.add_argument("--mesh", choices=("pod", "multipod", "both"),
                    default="pod")
    ap.add_argument("--segment-len", type=int, default=4,
                    help="MD steps fused into the lowered scan segment")
    ap.add_argument("--outer-segments", type=int, default=0,
                    help="if > 0, lower the whole-trajectory two-level scan "
                         "(this many segments of migration + segment-len "
                         "steps) instead of a single inner segment")
    ap.add_argument("--potential", default="dp", choices=("dp", "lj"),
                    help="force model plugged into the lowered program")
    ap.add_argument("--ensemble", default="nve",
                    choices=api.ENSEMBLE_CHOICES,
                    help="integrator/thermostat plugged into the lowered "
                         "program (Langevin adds per-step RNG ops + a key "
                         "in the scan carry; npt_* adds a barostat and the "
                         "dynamic box)")
    ap.add_argument("--topology", default=None,
                    help="N-D brick shape over the spatial ranks, e.g. 4x4 "
                         "on the 16x16 pod (default: the 1-D slab column) — "
                         "the compile proof that the fused outer program "
                         "lowers on multi-axis topologies")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    ensemble, barostat = api.resolve_ensemble(args.ensemble)

    cells = {"cu": CU, "cu_strong": CU_STRONG, "h2o": H2O}
    systems = args.system or ["cu", "cu_strong", "h2o"]
    impls = args.impl or list(IMPLS)
    if args.potential == "lj":
        impls = impls[:1]           # impl ladder is DP-only; one LJ row
    meshes = []
    if args.mesh in ("pod", "both"):
        meshes.append((mesh_mod.make_production_mesh(multi_pod=False), False))
    if args.mesh in ("multipod", "both"):
        meshes.append((mesh_mod.make_production_mesh(multi_pod=True), True))

    rows = []
    fails = 0
    for mesh, multi in meshes:
        for s in systems:
            for impl in impls:
                row = lower_md_cell(cells[s], impl, mesh, multi,
                                    segment_len=args.segment_len,
                                    outer_segments=args.outer_segments,
                                    potential_name=args.potential,
                                    ensemble=ensemble, barostat=barostat,
                                    topology=args.topology)
                rows.append(row)
                fails += row["status"] == "failed"
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1, default=str)
    print(f"{len(rows) - fails} ok, {fails} failed")
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
