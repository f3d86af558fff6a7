"""The operation and byte counts match counts made by hand at toy size."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import flops  # noqa: E402

TOY = {"ntypes": 2, "embed_widths": [2, 4, 8], "axis_neuron": 2,
       "fit_widths": [3, 3], "cheb_order": 5}


def test_model_flops_by_hand():
    # 3 atoms, 10 real neighbors; M = 8, M< = 2; D has 16 entries.
    contraction = 3 * (2 * 4 * 8) * 10             # fwd + 2x bwd
    descriptor = 3 * (2 * 4 * 2 * 8) * 3
    fit_fwd = 2 * (16 * 3 + 3 * 3 + 3 * 1)         # 16->3->3->1
    fitting = 2 * fit_fwd * 3
    assert flops.model_flops_per_step(TOY, 3, 10) == \
        contraction + descriptor + fitting


def kernel_count(k, m, sections, n_atoms, n_neighbors):
    """Operations of ``kernels/dp_fused/dp_fused.py`` counted line by line
    from its shapes: every real neighbor takes each of the K terms of the
    forward's and the backward's loops, every atom each section's
    contractions with the table C (K, M)."""
    fwd_term = 3 + 4 * 2            # _cheb_step; acc[c, k] += env[c] * T_k
    bwd_term = (3                   # _cheb_step
                + 5                 # tp_next = 2 T_k + 2u T'_k - T'_{k-1}
                + 7                 # rk = sum_c env[c] * q[c]
                + 4 * 2             # denv[c] += q[c] * T_k
                + 2)                # ds += T'_k * rk
    contract = 4 * k * 2 * m        # out[c] += P[c, k] * C[k, :]
    q_of_dt = 4 * k * 2 * m         # Q[c, k] = sum(dT[c] * C[k, :])
    return (n_neighbors * k * (fwd_term + bwd_term)
            + n_atoms * sections * (contract + q_of_dt))


def test_dp_fused_cost_by_hand():
    k, m = 5, 8
    cost = flops.dp_fused_cost_per_step(TOY, 3, 10)
    assert cost["flops"] == kernel_count(k, m, 2, 3, 10)
    per_nbr = 20 + 20 + 20                          # s, R~ in twice; ds, dR~ out
    per_atom = 2 * (2 * 4 * m * 4)                  # T, dT per type section
    tables = 2 * 2 * k * m * 4
    assert cost["bytes"] == per_nbr * 10 + per_atom * 3 + tables


def test_dp_fused_cost_per_atom_term_grows_with_the_sections():
    """Two sections double the per-atom term and leave the per-neighbor
    term (the same real neighbors, split between sections) as it is."""
    one, two = ({**TOY, "ntypes": t} for t in (1, 2))
    per_atom = [flops.dp_fused_cost_per_step(cfg, 1, 0)["flops"]
                for cfg in (one, two)]
    assert per_atom[1] == 2 * per_atom[0] > 0
    per_nbr = [flops.dp_fused_cost_per_step(cfg, 0, 7)["flops"]
               for cfg in (one, two)]
    assert per_nbr[0] == per_nbr[1] > 0


def test_dp_fused_cost_of_copper_is_bytes_bound():
    """At copper's widths (K = 32, M = 128, ~180 real neighbors an atom)
    the kernels move more bytes than a v5e streams in the time its peak
    takes for their operations."""
    model = {"ntypes": 1, "embed_widths": [32, 64, 128], "cheb_order": 32}
    cost = flops.dp_fused_cost_per_step(model, 16384, 16384 * 180)
    assert cost["bytes"] / 819e9 > 10 * cost["flops"] / 197e12
