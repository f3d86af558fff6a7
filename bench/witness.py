#!/usr/bin/env python3
"""A second witness for the sound program's readings of ``correct``.

    python3 bench/witness.py --workload cu16k_nve --seeds 50,16 \
        --out bench/readings/witness/cu16k_nve.jsonl

For every seed, one row, at the cell's size (or a smaller box of the same
lattice with ``--cells``):

- ``force_rel``: the first frame's forces of the program's rungs (``mlp``,
  ``cheb`` and the cell's own) at the configuration's precision, the cell's
  rung at ``default``, and the reference with bf16 operands, each as
  rms |F - F_ref| / rms |F_ref| against the plain reference at
  ``highest``; ``<rung>@<precision>+host_table`` is the cell's rung with
  its table swapped for one built in float64 on the host (below). The program's model is driven here one block of centers at a
  time, on the same neighbor lists as the reference, so the rungs differ
  only in how they evaluate the model. On a CPU the kernel is interpreted.
- ``table_coeff_err``: the largest gap between the program's Chebyshev
  table coefficients, as built on the backend, and the same table built
  in float64 on the host from the same weights; ``tanh_ulp``: the
  backend's float32 ``tanh`` against float64 over [-12, 12] (rms and
  largest, in units in the last place). The table's nodes come from it.
  ``cheb_eval_err``: the program's ``tabulation.cheb_eval`` of the host
  table, and its derivative, at the configuration's precision on the
  backend, against the same series summed in float64 at 4,096 midpoints
  of the table's domain (rms error over rms value).
- ``f32_state``: the check's ``velocity`` and ``position`` of the
  reference's own run with its positions and velocities kept in float32
  (``reference.integrate(state=np.float32)``) against the float64
  reference: what a float32 program with the reference's own forces reads.

``bench/readings.py`` gives the program's own readings of the same seeds.
"""

import time

T_START = time.perf_counter()

import argparse
import dataclasses
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


@functools.lru_cache(maxsize=None)
def _model_forces(cfg, impl: str, block: int):
    """Forces (N, 3) of the program's model, ``block`` centers at a time."""
    import jax
    import jax.numpy as jnp
    from repro.core import dp_model

    @jax.jit
    def run(params, pos, typ, box, nlist):
        def one(f_acc, b):
            i0 = b * block
            lst = jax.lax.dynamic_slice_in_dim(nlist, i0, block)
            ctr = jax.lax.dynamic_slice_in_dim(pos, i0, block)
            nmask = lst >= 0
            j = jnp.maximum(lst, 0)
            rij = pos[j] - ctr[:, None, :]
            rij = rij - box * jnp.round(rij / box)
            rij = jnp.where(nmask[..., None], rij, 0.0)
            at = jax.lax.dynamic_slice_in_dim(typ, i0, block)

            def e_of(r):
                return jnp.sum(dp_model.dp_atomic_energy(params, cfg, r, nmask,
                                                         at, impl))

            g = jax.grad(e_of)(rij)
            g = jnp.where(nmask[..., None], g, 0.0)
            f_acc = f_acc.at[j.reshape(-1)].add(-g.reshape(-1, 3))
            f_acc = jax.lax.dynamic_update_slice_in_dim(
                f_acc, jax.lax.dynamic_slice_in_dim(f_acc, i0, block)
                + g.sum(axis=1), i0, 0)
            return f_acc, None

        f, _ = jax.lax.scan(one, jnp.zeros_like(pos),
                            jnp.arange(pos.shape[0] // block))
        return f

    return run


@functools.lru_cache(maxsize=None)
def tanh_ulp():
    """{"rms", "max"}: the backend's f32 tanh against float64, in ulp."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    x = np.linspace(-12.0, 12.0, 2_000_001).astype(np.float32)
    got = np.asarray(jax.jit(jnp.tanh)(x), np.float64)
    exact = np.tanh(x.astype(np.float64))
    ulp = np.abs(got - exact) / np.spacing(
        np.abs(exact).astype(np.float32)).astype(np.float64)
    return {"rms": float(np.sqrt(np.mean(ulp * ulp))), "max": float(ulp.max())}


def table_f64(layers, lower: float, upper: float, order: int):
    """The Chebyshev table of one embedding net, in float64 on the host:
    the net (``reference.resnet_tanh``'s shortcuts) at the K Chebyshev
    nodes, then c_j = (2/K) sum_k g(x_k) cos(j theta_k), c_0 halved."""
    import numpy as np
    k = np.arange(order)
    theta = np.pi * (k + 0.5) / order
    h = (0.5 * (lower + upper) + 0.5 * (upper - lower) * np.cos(theta))[:, None]
    for lyr in layers:
        w = np.asarray(lyr["w"], np.float64)
        y = np.tanh(h @ w + np.asarray(lyr["b"], np.float64))
        if w.shape[1] == w.shape[0]:
            h = h + y
        elif w.shape[1] == 2 * w.shape[0]:
            h = np.concatenate([h, h], axis=-1) + y
        else:
            h = y
    c = (2.0 / order) * np.cos(np.outer(k, theta)) @ h
    c[0] *= 0.5
    return c


def cheb_eval_err(coeffs, lower: float, upper: float, precision: str):
    """{"value", "deriv"}: the program's Chebyshev evaluation (float32
    coefficients, ``precision``) and its derivative by ``jax.jvp``, each
    against a float64 sum of the same series: rms error over rms value."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import tabulation
    c32 = np.asarray(coeffs, np.float32)
    # midpoints: clip's derivative at the domain's ends is not the series'
    x = (lower + (np.arange(4096) + 0.5) * (upper - lower) / 4096).astype(
        np.float32)
    table = {"coeffs": jnp.asarray(c32), "lower": lower, "upper": upper}
    with jax.default_matmul_precision(precision):
        g, dg = jax.jit(lambda x: jax.jvp(
            lambda x: tabulation.cheb_eval(table, x), (x,),
            (jnp.ones_like(x),)))(jnp.asarray(x))
    u = (2.0 * x.astype(np.float64) - lower - upper) / (upper - lower)
    cheb = np.polynomial.chebyshev
    c64 = c32.astype(np.float64)
    g64 = cheb.chebval(u, c64).T
    dg64 = cheb.chebval(u, cheb.chebder(c64)).T * 2.0 / (upper - lower)

    def rel(a, b):
        return float(np.sqrt(np.mean((np.asarray(a, np.float64) - b) ** 2)
                             / np.mean(b * b)))

    return {"value": rel(g, g64), "deriv": rel(dg, dg64)}


def witness(config, cell, seed: int, precision: str):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import harness, reference
    model = config["model"]
    setup = harness.prepare(config, cell, seed)
    n = len(setup.pos)
    lists, _ = reference.neighbor_lists(setup.pos, setup.typ, setup.box,
                                        model["rcut"], setup.ks)
    nlist = -np.ones((n, sum(model["sel"])), np.int32)
    nlist[:, :lists[0].shape[1]] = np.asarray(lists[0])
    cfg = harness.dp_config(model, cell["rung"])
    if jax.devices()[0].platform != "tpu":
        cfg = dataclasses.replace(cfg, kernel_interpret=True)
    block = next(b for b in (512, 256, 128, n) if n % b == 0)
    args = (setup.params_run, jnp.asarray(setup.pos, jnp.float32),
            jnp.asarray(setup.typ, jnp.int32),
            jnp.asarray(setup.box, jnp.float32), jnp.asarray(nlist))
    _, f_ref, _ = reference.energy_forces_virial(
        setup.params, model, setup.pos, setup.typ, setup.box, lists)
    _, f_bf16, _ = reference.energy_forces_virial(
        setup.params, model, setup.pos, setup.typ, setup.box, lists, "bf16")
    tables = setup.params_run.get("table", {}).get("nets", {})
    host = {t: table_f64(setup.params["embed"][t], model["table_lower"],
                         model["table_upper"], int(model["cheb_order"]))
            for t in tables}
    forces = {"reference@bf16": f_bf16}
    for impl, prec in dict.fromkeys([("mlp", precision), ("cheb", precision),
                                     (cell["rung"], precision),
                                     (cell["rung"], "default")]):
        with jax.default_matmul_precision(prec):
            forces[f"{impl}@{prec}"] = np.asarray(
                _model_forces(cfg, impl, block)(*args), np.float64)
    if tables:
        swapped = dict(setup.params_run, table={"nets": {
            t: dict(tab, coeffs=jnp.asarray(host[t], jnp.float32))
            for t, tab in tables.items()}})
        with jax.default_matmul_precision(precision):
            forces[f"{cell['rung']}@{precision}+host_table"] = np.asarray(
                _model_forces(cfg, cell["rung"], block)(swapped, *args[1:]),
                np.float64)
    f_rms = float(np.sqrt(np.mean(np.sum(f_ref * f_ref, axis=1))))
    steps = harness.chunk_steps(cell)
    traj = harness.reference_trajectory(setup, steps)
    t32 = reference.integrate(
        setup.params, model, setup.pos, setup.vel0, setup.typ, setup.box,
        setup.masses, cell["protocol"]["dt_fs"], steps, state=np.float32)
    v0 = np.asarray(setup.vel0, np.float64)
    dv = np.asarray(t32["vel"], np.float64) - traj["vel"]
    dx = np.asarray(t32["pos"], np.float64) - traj["pos"]
    dx -= setup.box * np.round(dx / setup.box)
    coeff_err = {t: float(np.max(np.abs(
        np.asarray(tab["coeffs"], np.float64) - host[t])))
        for t, tab in tables.items()}
    return {
        "seed": seed, "atoms": n, "steps": steps, "f_rms": f_rms,
        "table_coeff_err": coeff_err, "tanh_ulp": tanh_ulp(),
        "cheb_eval_err": {t: cheb_eval_err(
            host[t], model["table_lower"], model["table_upper"], precision)
            for t in host},
        "force_rel": {k: float(np.sqrt(np.mean(np.sum((f - f_ref) ** 2, 1))))
                      / f_rms for k, f in forces.items()},
        "f32_state": {
            "velocity": float(np.sqrt(np.sum(dv * dv)
                                      / np.sum((traj["vel"] - v0) ** 2))),
            "position": float(np.sqrt(np.max(np.sum(dx * dx, axis=1))))},
        "v_change_rms": float(np.sqrt(np.mean(np.sum(
            (traj["vel"] - v0) ** 2, axis=1))))}


def main(argv=None) -> int:
    from bench import harness, systems
    from bench.readings import seed_list
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seed_list, required=True)
    ap.add_argument("--cells", type=int, default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    harness.compile_cache()
    _, config, cell = systems.load_cell(args.workload,
                                        systems.load_benchmark(ROOT), ROOT)
    if args.cells:
        cell = dict(cell, system=dict(cell["system"], cells=[args.cells] * 3))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    for seed in args.seeds:
        t0 = time.perf_counter()
        row = witness(config, cell, seed, config["precision"])
        row["backend"] = __import__("jax").devices()[0].platform
        row["run_s"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
    print(json.dumps({"total_s": time.perf_counter() - T_START}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
