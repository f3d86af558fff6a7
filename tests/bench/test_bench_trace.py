"""The reduction from a device trace to idle share, kernel time and
collective time, on small committed traces."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402
from bench.kernels import is_dp_fused  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def load(name):
    with open(os.path.join(FIXTURES, name)) as f:
        return json.load(f)


def test_synthetic_by_hand():
    tr = load("trace_synthetic.json")
    assert trace.window(tr) == (100.0, 600.0)
    # device 0 busy: the while loop spans [100,440] around its body ops;
    # device 1: [100,400] = 300; the op at 900 lies past the window
    assert trace.busy_ns(tr) == pytest.approx((340 + 300) / 2)
    assert trace.op_ns(tr, is_dp_fused) == pytest.approx(140 / 2)
    assert trace.op_ns(tr, lambda n: "collective-permute" in n) == \
        pytest.approx(20 / 2)
    gaps = trace.idle_gaps(tr)
    assert [g[1] for g in gaps] == pytest.approx([160e-9])      # [440,600]
    assert gaps[0][0] == "thermo fetch"
    top = dict(trace.top_ops(tr))
    assert top["fusion"] == pytest.approx((50 + 30 + 300) / 2 * 1e-9)
    assert "while" not in top


def test_recorded_v5e_trace():
    """1.45 s of a cu16k_nve window on one v5e (two MD steps and the end
    of a neighbor build), checked against a brute-force reading."""
    tr = load("trace_v5e_cu16k.json")
    t0, t1 = trace.window(tr)
    evs = tr["devices"]["0"]
    grid = np.zeros(int((t1 - t0) / 1e3) + 1, bool)        # 1 us cells
    for _, s, d in evs:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            grid[int((a - t0) / 1e3):int(np.ceil((b - t0) / 1e3))] = True
    assert trace.busy_ns(tr) == pytest.approx(grid.sum() * 1e3, rel=1e-3)
    kernels = [e for e in evs if is_dp_fused(e[0])]
    assert {trace.op_stem(e[0]) for e in kernels} == {
        "jvp_jit_fused_fwd__", "transpose_jvp_jit_fused_bwd___"}
    want = sum(min(s + d, t1) - max(s, t0) for _, s, d in kernels)
    assert trace.op_ns(tr, is_dp_fused) == pytest.approx(want)
    assert trace.op_ns(tr, lambda n: "collective-permute" in n) == 0.0
    names = [k for k, _ in trace.top_ops(tr)]
    assert "transpose_jvp_jit_fused_bwd___" in names
    assert not any(trace.CONTAINERS.match(k) for k in names)


def test_op_names():
    text = "%jvp_jit_fused_fwd__.8 = f32[16384,4,128]{2,1,0} custom-call(...)"
    assert trace.op_name(text) == "jvp_jit_fused_fwd__.8"
    assert trace.op_stem("fusion.12") == "fusion"
    assert is_dp_fused("jvp_jit_fused_fwd__.8")
    assert is_dp_fused("transpose_jvp_jit_fused_bwd___.26")
    assert not is_dp_fused("fusion.12")
    assert trace.CONTAINERS.match("while.41")
