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


def test_dp_fused_cost_by_hand():
    k, m = 5, 8
    fwd = 3 * k + 2 * k * m + 2 * 4 * m            # basis, B@C, R~^T G
    bwd = 8 * k + 4 * k * m + 16 * m + 2 * m       # + derivative, dR~, ds
    cost = flops.dp_fused_cost_per_step(TOY, 3, 10)
    assert cost["flops"] == (fwd + bwd) * 10
    per_nbr = 20 + 20 + 20                          # s, R~ in twice; ds, dR~ out
    per_atom = 2 * (2 * 4 * m * 4)                  # T, dT per type section
    tables = 2 * 2 * k * m * 4
    assert cost["bytes"] == per_nbr * 10 + per_atom * 3 + tables
