"""Each metric reader, on a record made by hand."""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def reader(name):
    path = os.path.join(ROOT, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def record(trace=None):
    return {"atoms": 1000, "chips": 1,
            "steps": 20, "force_evals": 21, "window_s": 2.0, "setup_s": 30.0,
            "peak_bytes_per_device": [5_000_000],
            "model_flops_per_eval": 1e9,
            "dp_fused_per_eval": {"flops": 4e9, "bytes": 1e7},
            "peaks": {"flops_per_s": 2e12, "bytes_per_s": 1e11},
            "counters": {}, "trace": trace}


def synthetic_trace():
    with open(os.path.join(FIXTURES, "trace_synthetic.json")) as f:
        return json.load(f)


def test_end_to_end_readers():
    rec = record()
    assert reader("us_per_step_atom")(rec) == pytest.approx(2.0 * 1e6 / 20e3)
    assert reader("setup_s")(rec) == 30.0


def test_step_mfu():
    # 21 evaluations of 1 GFLOP in 2 s on one 2 TFLOP/s chip
    assert reader("step_mfu")(record()) == pytest.approx(100 * 21e9 / 4e12)


def test_trace_readers():
    rec = record(synthetic_trace())
    assert reader("device_idle_share")(rec) == pytest.approx(
        100 * (1 - 320 / 500))
    # kernels: 70 ns per device on average; bound: compute, 21 * 4e9 / 2e12
    assert reader("dp_fused_roofline")(rec) == pytest.approx(
        100 * (21 * 4e9 / 2e12) / 70e-9)
    assert reader("dp_fused_ms_per_step")(rec) == pytest.approx(70e-6 / 20)


@pytest.mark.parametrize("name", ["device_idle_share", "dp_fused_roofline",
                                  "dp_fused_ms_per_step",
                                  "neighbors_ms_per_step", "env_ms_per_step",
                                  "scatter_ms_per_step",
                                  "force_backward_ms_per_step",
                                  "nbr_slot_fill"])
def test_nothing_to_read_gives_nothing(name):
    assert reader(name)(record(None)) is None
    empty = {"devices": {"0": []}, "host": [["bench.window", 0.0, 10.0]]}
    assert reader(name)(record(empty)) is None
