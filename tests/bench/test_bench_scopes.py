"""The program's scopes and spans in a device trace: the attribution rule,
the per-layer reduction, device time under a host span, and the reader of
``first_build_ms``, on hand-made traces and a recording from the chip."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import scopes, trace  # noqa: E402
from bench.kernels import is_dp_fused  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
SCOPELESS = ["trace_synthetic.json", "trace_v5e_cu16k.json"]


def load(name):
    with open(os.path.join(FIXTURES, name)) as f:
        return json.load(f)


def reader(name):
    path = os.path.join(ROOT, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("op_name, want", [
    # forward: the innermost scope
    ("jit(run_chunk)/while/body/md.neighbors/sort", ("md.neighbors", False)),
    ("jit(f)/jvp(dp.embed)/jit(fused_fwd)/pallas_call", ("dp.embed", False)),
    # backward: a transpose( around the scope, in its own entry or outside
    ("jit(f)/transpose(jvp(dp.embed))/jit(fused_bwd)/pallas_call",
     ("dp.embed", True)),
    ("jit(f)/transpose(jvp(jit(g)))/dp.env/mul", ("dp.env", True)),
    # nested: the innermost wins
    ("jit(f)/dp.env/md.integrate/mul", ("md.integrate", False)),
    ("jit(f)/transpose(jvp(dp.fitting))/dp.env/mul", ("dp.env", True)),
    # unscoped, and names that only look like a scope
    ("jit(run_chunk)/while/body/dynamic_update_slice", (None, False)),
    ("", (None, False)),
    ("jit(f)/cmd.x/xdp.y/mul", (None, False)),
])
def test_attribution_rule(op_name, want):
    assert scopes.attribute(op_name) == want


def test_layer_ns_by_hand():
    """Device 0: a build before the chunk, a scoped ``while`` that never
    counts, forward, backward and nested ops, an unscoped copy, an op past
    the window; device 1: one scatter. Averaged over the two devices."""
    got = scopes.layer_ns(load("trace_scoped_synthetic.json"))
    assert got == pytest.approx({
        "md.neighbors": (50 + 50) / 2, "dp.embed": 40 / 2,
        "transpose(dp.fitting)": 30 / 2, "md.integrate": 20 / 2,
        "transpose(dp.embed)": 100 / 2, scopes.UNSCOPED: 20 / 2,
        "dp.scatter": 300 / 2})
    assert scopes.backward_ns(got) == pytest.approx(65.0)


def test_span_device_ns_by_hand():
    tr = load("trace_scoped_synthetic.json")
    # md.first_build [0, 90]: fusion.0 on device 0, nothing on device 1
    assert scopes.span_device_ns(tr, "md.first_build") == pytest.approx(25.0)
    assert scopes.span_device_ns(tr, "md.no_such_span") is None
    assert scopes.span_device_ns(None, "md.first_build") is None


@pytest.mark.parametrize("name", SCOPELESS)
def test_scopeless_traces_give_nothing(name):
    tr = load(name)
    assert scopes.layer_ns(tr) is None
    assert scopes.span_device_ns(tr, "md.first_build") is None
    assert reader("first_build_ms")({"trace": tr}) is None


def test_all_unscoped_gives_nothing():
    tr = load("trace_scoped_synthetic.json")
    tr["scopes"] = {k: [""] * len(v) for k, v in tr["devices"].items()}
    assert scopes.layer_ns(tr) is None


def test_first_build_reader():
    rec = {"trace": load("trace_scoped_synthetic.json")}
    assert reader("first_build_ms")(rec) == pytest.approx(25e-6)
    assert reader("first_build_ms")({"trace": None}) is None


def test_layer_metrics_by_hand():
    """The ten per-layer metrics of the synthetic window (2 steps; busy
    device time 400 ns, averaged over the two devices) and its counters."""
    tr = load("trace_scoped_synthetic.json")
    got = scopes.layer_metrics(tr, 2, {"nbr_builds": 2,
                                       "nbr_live_slots": 68,
                                       "nbr_slots": 100})
    busy = trace.busy_ns(tr)
    assert got == pytest.approx({
        "neighbors_ms_per_step": 50e-6 / 2, "env_ms_per_step": 0.0,
        "embed_ms_per_step": 20e-6 / 2, "fitting_ms_per_step": 0.0,
        "scatter_ms_per_step": 150e-6 / 2,
        "integrate_ms_per_step": 10e-6 / 2,
        "force_backward_ms_per_step": 65e-6 / 2,
        "device_unscoped_share": 100 * 10 / busy,
        "nbr_build_ms": 50e-6 / 2, "nbr_slot_fill": 68.0})


@pytest.mark.parametrize("name", SCOPELESS)
def test_layer_metrics_of_scopeless_traces(name):
    """A trace of a program without scopes, and a run without counters,
    give nothing to read."""
    got = scopes.layer_metrics(load(name), 2, {})
    assert got == dict.fromkeys(got)
    assert len(got) == 10


def test_layers_summary_by_hand():
    """``bench/layers.py``'s report: the ten metrics, the per-step time of
    every layer key, and the top operations of each layer."""
    sys.path.insert(0, os.path.join(ROOT, "bench"))
    import layers
    tr = load("trace_scoped_synthetic.json")
    counters = {"nbr_builds": 2, "nbr_live_slots": 68, "nbr_slots": 100}
    rep = layers.summarize(tr, counters, steps=2)
    assert rep["metrics"] == scopes.layer_metrics(tr, 2, counters)
    assert rep["layers_ms_per_step"]["transpose(dp.embed)"] == \
        pytest.approx(25e-6)
    assert rep["layers_over_busy"] == pytest.approx(305 / trace.busy_ns(tr))
    assert rep["first_build_ms"] == pytest.approx(25e-6)
    assert rep["top_ops"]["dp.scatter"] == [("fusion", pytest.approx(150e-9))]


def test_recorded_v5e_scoped_trace():
    """6.97 s of a cu16k_nve window on one v5e, with each operation's
    ``op_name`` (the end of the chunk's neighbor build and two MD steps):
    every dp_fused kernel runs under ``dp.embed``, forward and backward;
    every layer and the backward are present; the layer times add up to
    the busy time, checked against a brute-force reading."""
    tr = load("trace_v5e_cu16k_scoped.json")
    evs, ops = tr["devices"]["0"], tr["scopes"]["0"]
    assert len(evs) == len(ops)
    kernels = [(e[0], op) for e, op in zip(evs, ops) if is_dp_fused(e[0])]
    assert len(kernels) == 4
    for name, op in kernels:
        assert scopes.attribute(op) == ("dp.embed",
                                        trace.op_stem(name) == "fused_bwd")
    got = scopes.layer_ns(tr)
    assert {"md.neighbors", "dp.env", "dp.embed", "dp.fitting",
            "dp.scatter", "md.integrate", "transpose(dp.embed)",
            "transpose(dp.env)", "transpose(dp.fitting)"} <= set(got)
    t0, t1 = trace.window(tr)
    grid = np.zeros(int((t1 - t0) / 1e3) + 1, bool)        # 1 us cells
    for _, s, d in evs:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            grid[int((a - t0) / 1e3):int(np.ceil((b - t0) / 1e3))] = True
    assert sum(got.values()) == pytest.approx(grid.sum() * 1e3, rel=5e-3)
    assert got[scopes.UNSCOPED] < 0.05 * sum(got.values())


LINE_READERS = ["neighbors_ms_per_step", "env_ms_per_step",
                "scatter_ms_per_step", "force_backward_ms_per_step",
                "nbr_slot_fill"]


@pytest.mark.parametrize("name", LINE_READERS)
def test_line_readers_on_the_recorded_v5e_scoped_trace(name):
    """Each per-layer metric of the result line reads what
    ``scopes.layer_metrics`` gives, from the run's record."""
    tr = load("trace_v5e_cu16k_scoped.json")
    counters = {"nbr_builds": 1, "nbr_live_slots": 684, "nbr_slots": 1000}
    rec = {"trace": tr, "steps": 2, "counters": counters}
    want = scopes.layer_metrics(tr, 2, counters)[name]
    assert want is not None and want > 0
    assert reader(name)(rec) == want


def test_scopes_cover_the_recorded_v5e_trace():
    """The program's scopes leave under 1% of the busy time unnamed, so
    the line can go without ``device_unscoped_share``."""
    tr = load("trace_v5e_cu16k_scoped.json")
    assert scopes.layer_metrics(tr, 2, {})["device_unscoped_share"] < 1.0


def _xspace(ops_line: str) -> bytes:
    """A serialized XSpace with one TPU plane (``ops_line`` is its
    ``XLA Ops`` line in protobuf text format) and the benchmark's window
    on a host plane. The operations' ``op_name``s are on the event
    metadata, as the TPU profiler writes them."""
    from jax.profiler import ProfileData
    return ProfileData.text_proto_to_serialized_xspace(f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules"
          events {{ metadata_id: 9 offset_ps: 0 duration_ps: 10000 }} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000 {ops_line} }}
  event_metadata {{ key: 7 value {{ id: 7
    name: "%fusion.1 = f32[8] fusion(x)"
    stats {{ metadata_id: 1 str_value: "jit(f)/dp.env/mul:" }} }} }}
  event_metadata {{ key: 8 value {{ id: 8 name: "%copy.2 = f32[8] copy(y)"
    stats {{ metadata_id: 1
      str_value: "jit(f)/transpose(jvp(dp.env))/add:" }} }} }}
  event_metadata {{ key: 9 value {{ id: 9 name: "jit_f(1)" }} }}
  event_metadata {{ key: 10 value {{ id: 10 name: "%copy.3 = f32[8] copy(z)" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "run_id" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0
          events {{ metadata_id: 1 offset_ps: 0 duration_ps: 200000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
}}""")


def test_load_reads_op_names_from_event_metadata(tmp_path):
    """``trace.load`` on a serialized trace: ``devices`` and ``host``, and
    under ``scopes`` each operation event's ``op_name`` in the same order,
    ``""`` where the metadata has none."""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace("""
      events { metadata_id: 7 offset_ps: 0 duration_ps: 5000 }
      events { metadata_id: 8 offset_ps: 6000 duration_ps: 2000 }
      events { metadata_id: 10 offset_ps: 8000 duration_ps: 1000 }
      events { metadata_id: 7 offset_ps: 9000 duration_ps: 1000
               stats { metadata_id: 2 int64_value: 3 } }"""))
    tr = trace.load(str(path))
    assert tr["devices"] == {"0": [
        ["fusion.1", 1000.0, 5.0], ["copy.2", 1006.0, 2.0],
        ["copy.3", 1008.0, 1.0], ["fusion.1", 1009.0, 1.0]]}
    assert tr["host"] == [["bench.window", 0.0, 200000.0]]
    assert tr["scopes"] == {"0": [
        "jit(f)/dp.env/mul:", "jit(f)/transpose(jvp(dp.env))/add:", "",
        "jit(f)/dp.env/mul:"]}
    assert scopes.layer_ns(tr) == pytest.approx(
        {"dp.env": 6.0, "transpose(dp.env)": 2.0, scopes.UNSCOPED: 1.0})
