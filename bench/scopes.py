"""Which layer of the MD program each device operation belongs to.

The program names its layers with ``jax.named_scope``: ``md.neighbors``
(every neighbor build), ``dp.env``, ``dp.embed``, ``dp.fitting`` and
``dp.scatter`` (the potential's sublayers) and ``md.integrate`` (the rest of
a step). XLA keeps the scope in each operation's metadata, its ``op_name``
(``jit(run_chunk)/while/body/.../jvp(dp.embed)/jit(fused_fwd)/pallas_call``),
and autodiff writes a scope's backward operations as ``transpose(jvp(dp.
...))``. The TPU profiler keeps that ``op_name`` as the stat ``SCOPE_STAT``
(``tf_op``, e.g. ``jit(dp_energy_forces)/jvp(dp.embed)/jit(fused_fwd)/
pallas_call:``) on each operation's event *metadata*, not on the event,
so ``op_scopes`` reads it from the ``.xplane.pb``'s protobuf (confirmed on
a v5e trace of ``cu16k_nve``). The program's host phases are
``jax.profiler.TraceAnnotation`` spans (``md.run``, ``md.first_build``,
``md.chunk``, ...), which ``trace.load`` keeps with the other host spans.

``trace.load`` keeps, under ``scopes``, each device operation event's
``op_name`` as ``op_scopes`` reads it; ``layer_ns`` reduces them by the
attribution rule of ``attribute``, and ``layer_metrics`` to the per-layer
metrics.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Tuple

from bench import trace

SCOPE_STAT = "tf_op"
UNSCOPED = "unscoped"
SCOPE = re.compile(r"(?<![\w.])(?:md|dp)\.[A-Za-z_]+")


# The XSpace protobuf (tsl/profiler/protobuf/xplane.proto), as far as
# ``op_scopes`` reads it: field numbers of each message.
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 3, 4, 5
_LINE_NAME, _LINE_EVENTS = 2, 4
_EVENT_MD_ID = 1
_MD_NAME, _EVENT_MD_STATS = 2, 5
_STAT_MD_ID, _STAT_STR = 1, 5
_MAP_KEY, _MAP_VALUE = 1, 2


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one serialized message: an
    int for a varint, a memoryview for a length-delimited field."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unknown protobuf wire type {wire}")
        yield key >> 3, value


def _map_entries(entries):
    """{key: value bytes} of a protobuf map<int64, message> field."""
    out = {}
    for entry in entries:
        kv = dict(_fields(entry))
        out[kv.get(_MAP_KEY, 0)] = kv.get(_MAP_VALUE, b"")
    return out


def op_scopes(xspace: bytes) -> Dict[str, Tuple[List[str], List[str]]]:
    """For each TPU device plane of a serialized ``XSpace``, the names and
    the ``SCOPE_STAT`` of its ``XLA Ops`` events, in the order the events
    are stored (``""`` where an event has none). The profiler keeps an
    operation's ``op_name`` on the event's metadata, which
    ``jax.profiler.ProfileData`` does not expose, so this reads the
    protobuf's wire format itself."""
    out = {}
    for field, plane in _fields(xspace):
        if field != _SPACE_PLANES:
            continue
        parts: Dict[int, list] = {}
        for f, v in _fields(plane):
            parts.setdefault(f, []).append(v)
        name = bytes(parts.get(_PLANE_NAME, [b""])[0]).decode()
        m = trace.DEVICE_PLANE.match(name)
        if not m:
            continue
        stat_names = {k: bytes(dict(_fields(v)).get(_MD_NAME, b"")).decode()
                      for k, v in _map_entries(
                          parts.get(_PLANE_STAT_MD, [])).items()}
        md_name, md_scope = {}, {}
        for k, v in _map_entries(parts.get(_PLANE_EVENT_MD, [])).items():
            scope = ""
            for f, stat in _fields(v):
                if f == _MD_NAME:
                    md_name[k] = bytes(stat).decode()
                elif f == _EVENT_MD_STATS:
                    st = dict(_fields(stat))
                    if stat_names.get(st.get(_STAT_MD_ID)) == SCOPE_STAT:
                        scope = bytes(st.get(_STAT_STR, b"")).decode()
            md_scope[k] = scope
        names, ops = [], []
        for line in parts.get(_PLANE_LINES, []):
            fields = list(_fields(line))
            if dict(fields).get(_LINE_NAME) != trace.OPS_LINE.encode():
                continue
            for f, ev in fields:
                if f == _LINE_EVENTS:
                    k = dict(_fields(ev)).get(_EVENT_MD_ID, 0)
                    names.append(md_name.get(k, ""))
                    ops.append(md_scope.get(k, ""))
        out[m.group(1)] = (names, ops)
    return out


def attribute(op_name: str) -> Tuple[Optional[str], bool]:
    """(layer, backward) of an operation from its ``op_name``: the layer is
    the innermost ``md.*`` / ``dp.*`` scope (None where there is none), and
    the operation is backward where a ``transpose(`` encloses that scope,
    in its own path entry or an outer one."""
    found = None
    for found in SCOPE.finditer(op_name):
        pass
    if found is None:
        return None, False
    return found.group(0), "transpose(" in op_name[:found.start()]


def layer_key(op_name: str) -> str:
    """The key ``layer_ns`` pools an operation under: its layer, as
    ``transpose(<layer>)`` for a backward one, or ``UNSCOPED``."""
    layer, backward = attribute(op_name)
    if layer is None:
        return UNSCOPED
    return f"transpose({layer})" if backward else layer


def layer_ns(tr: Optional[Dict[str, Any]]) -> Optional[Dict[str, float]]:
    """Device time (ns) of the window's operations by ``layer_key``,
    averaged over the devices. Control-flow containers (``while``,
    ``conditional``, ``call``) never count: they may carry a scope and span
    the operations of their bodies. None where no operation in the window
    carries a program scope (a trace of a program without them)."""
    if tr is None or not tr.get("scopes"):
        return None
    t0, t1 = trace.window(tr)
    devs = [k for k, evs in tr["devices"].items() if evs]
    tot: Dict[str, float] = {}
    for k in devs:
        for (name, s, d), op in zip(tr["devices"][k], tr["scopes"][k]):
            a, b = max(s, t0), min(s + d, t1)
            if b > a and not trace.CONTAINERS.match(name):
                key = layer_key(op)
                tot[key] = tot.get(key, 0.0) + (b - a)
    if not devs or set(tot) <= {UNSCOPED}:
        return None
    return {key: v / len(devs) for key, v in tot.items()}


def backward_ns(layers: Dict[str, float]) -> float:
    """Device time of the potential's backward: every ``transpose(dp.*)``."""
    return sum(v for k, v in layers.items() if k.startswith("transpose(dp."))


#: The per-layer metrics of a scoped window, and the layer each of the
#: ``<layer>_ms_per_step`` ones reads (forward operations only).
LAYER_METRICS = {"neighbors_ms_per_step": "md.neighbors",
                 "env_ms_per_step": "dp.env",
                 "embed_ms_per_step": "dp.embed",
                 "fitting_ms_per_step": "dp.fitting",
                 "scatter_ms_per_step": "dp.scatter",
                 "integrate_ms_per_step": "md.integrate"}


def layer_metrics(tr: Optional[Dict[str, Any]], steps: int,
                  counters: Dict[str, Optional[int]]
                  ) -> Dict[str, Optional[float]]:
    """The ten per-layer metrics of a window of ``steps`` MD steps, from
    its scoped trace ``tr`` and the program's counters for the call
    (``MDResult``'s ``nbr_builds``, ``nbr_live_slots``, ``nbr_slots``):
    each ``LAYER_METRICS`` layer's forward device time per step (ms),
    ``force_backward_ms_per_step``, ``device_unscoped_share`` (% of busy
    device time), ``nbr_build_ms`` (``md.neighbors`` time over
    ``nbr_builds``) and ``nbr_slot_fill`` (live over total slots, %).
    None for each one with nothing to read."""
    layers = layer_ns(tr)
    out: Dict[str, Optional[float]] = dict.fromkeys(
        [*LAYER_METRICS, "force_backward_ms_per_step",
         "device_unscoped_share", "nbr_build_ms"])
    if layers is not None:
        for name, layer in LAYER_METRICS.items():
            out[name] = layers.get(layer, 0.0) * 1e-6 / steps
        out["force_backward_ms_per_step"] = backward_ns(layers) * 1e-6 / steps
        busy = trace.busy_ns(tr)
        out["device_unscoped_share"] = (
            100.0 * layers.get(UNSCOPED, 0.0) / busy if busy else None)
        builds = counters.get("nbr_builds")
        out["nbr_build_ms"] = (layers.get("md.neighbors", 0.0) * 1e-6 / builds
                               if builds else None)
    live, slots = counters.get("nbr_live_slots"), counters.get("nbr_slots")
    out["nbr_slot_fill"] = 100.0 * live / slots if slots else None
    return out


def span_device_ns(tr: Optional[Dict[str, Any]], span: str
                   ) -> Optional[float]:
    """Busy device time (ns, the union of operation intervals, averaged over
    the devices) inside the longest host span named ``span`` in the
    window. None where the window has no such span."""
    if tr is None:
        return None
    t0, t1 = trace.window(tr)
    spans = [e for e in tr["host"] if e[0] == span
             and e[1] < t1 and e[1] + e[2] > t0]
    devs = [evs for evs in tr["devices"].values() if evs]
    if not spans or not devs:
        return None
    _, s, d = max(spans, key=lambda e: e[2])
    a, b = max(s, t0), min(s + d, t1)
    busy = [sum(y - x for x, y in trace.union(
        [(max(es, a), min(es + ed, b)) for _, es, ed in evs
         if min(es + ed, b) > max(es, a)])) for evs in devs]
    return sum(busy) / len(busy)
