"""The benchmark finds its cells, configurations and metric readers by
name, and its files keep to the shapes the harness reads."""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import systems  # noqa: E402

BENCH = systems.load_benchmark(ROOT)
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]


@pytest.mark.parametrize("name", WORKLOADS)
def test_cell_found_by_name(name):
    work, config, cell = systems.load_cell(name, BENCH, ROOT)
    assert work["config"] == config["name"]
    assert cell["rung"] in ("mlp", "quintic", "cheb", "cheb_pallas")
    assert set(cell["limits"]) == {"velocity", "position", "etot_drift",
                                   "energy", "virial"}
    model = config["model"]
    assert len(model["sel"]) == model["ntypes"] == len(model["type_map"])
    assert config["precision"] == "highest"
    entry = next(c for c in BENCH["configs"] if c["name"] == work["config"])
    assert set(entry["reduced"]) <= set(config["reduced"])


@pytest.mark.parametrize("name", METRICS)
def test_metric_reader_found_by_name(name):
    path = os.path.join(ROOT, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(mod.read)


def test_every_metric_lists_existing_cells():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", [])) <= set(WORKLOADS)


def test_peaks_known_for_the_chip():
    peaks = systems.load_json(ROOT, "bench", "peaks.json")
    assert peaks["TPU v5 lite"]["flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["bytes_per_s"] == 819e9


def test_system_is_the_same_work_for_every_seed():
    _, _, cell = systems.load_cell("cu16k_nve", BENCH, ROOT)
    small = json.loads(json.dumps(cell))
    small["system"]["cells"] = [3, 3, 3]
    a = systems.build_system(small, 1)
    b = systems.build_system(small, 2**33 + 1)
    assert a[0].shape == b[0].shape == (108, 3)
    assert (a[1] == b[1]).all() and (a[2] == b[2]).all()
    assert not (a[0] == b[0]).all()
    assert systems.sim_seed(1) != systems.sim_seed(2**33 + 1)
