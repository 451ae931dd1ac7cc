"""Split a profiler trace by the program's layers.

The program labels its work twice over (``src/repro``):

* device ops carry the ``jax.named_scope`` they were traced under --
  ``fed.gather``, ``fed.client``, ``fed.aggregate``, ``fed.server`` -- in
  their ``op_name`` metadata, which the TPU trace keeps per op as the
  ``tf_op`` stat of the op's event metadata;
* host spans (``jax.profiler.TraceAnnotation``) around the chunk loop's
  steps: ``fed.stage``, ``fed.upload``, ``fed.dispatch``, ``fed.seal``,
  ``fed.drain``.

``load`` reads both from an ``.xplane.pb``.  ``jax.profiler.ProfileData``
gives an event's own stats but not those of its metadata, so the device
planes are parsed here with a schema of the few ``XSpace`` fields read
(``tsl/profiler/protobuf/xplane.proto``); host spans come from
``ProfileData`` as in ``chipbench.trace``.  ``reduce`` hands the same ops,
scopes dropped, to ``trace.reduce``, so ``busy_s``, ``op_s`` and today's
gap labels read what they read there, and adds:

* ``scope_s``: self seconds per scope (an op's self time as in
  ``op_s``: clipped to the window, less that of the ops nested in it),
  mean over devices; ops under no ``fed.*`` scope -- among them the
  round loop's own ``while`` -- count under ``unscoped``;
* ``gaps``: idle stretches labelled by the innermost span of either
  prefix, ``bench.`` or ``fed.``; where no ``fed.`` span covers a gap its
  label is the one ``trace.reduce`` gives.
"""
from __future__ import annotations

import functools
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from chipbench import trace

SCOPE_PREFIX = "fed."
SPAN_PREFIXES = (trace.SPAN_PREFIX, SCOPE_PREFIX)
UNSCOPED = "unscoped"
_SCOPE = re.compile(r"(?<![\w.])fed\.\w+")

# (message, [(field, number, type, repeated, message type)]): the subset of
# xplane.proto read here; a map<int64, M> is its wire form, a repeated
# {key = 1, value = 2} message.  Oneof members are plain fields on the wire.
_I64, _U64, _F64, _STR, _BYTES, _MSG = 3, 4, 1, 9, 12, 11
_SCHEMA = [
    ("XSpace", [("planes", 1, _MSG, True, "XPlane")]),
    ("XPlane", [("id", 1, _I64, False, None), ("name", 2, _STR, False, None),
                ("lines", 3, _MSG, True, "XLine"),
                ("event_metadata", 4, _MSG, True, "EventMetadataEntry"),
                ("stat_metadata", 5, _MSG, True, "StatMetadataEntry")]),
    ("EventMetadataEntry", [("key", 1, _I64, False, None),
                            ("value", 2, _MSG, False, "XEventMetadata")]),
    ("StatMetadataEntry", [("key", 1, _I64, False, None),
                           ("value", 2, _MSG, False, "XStatMetadata")]),
    ("XEventMetadata", [("id", 1, _I64, False, None),
                        ("name", 2, _STR, False, None),
                        ("stats", 5, _MSG, True, "XStat")]),
    ("XStatMetadata", [("id", 1, _I64, False, None),
                       ("name", 2, _STR, False, None)]),
    ("XLine", [("id", 1, _I64, False, None), ("name", 2, _STR, False, None),
               ("timestamp_ns", 3, _I64, False, None),
               ("events", 4, _MSG, True, "XEvent")]),
    ("XEvent", [("metadata_id", 1, _I64, False, None),
                ("offset_ps", 2, _I64, False, None),
                ("duration_ps", 3, _I64, False, None)]),
    ("XStat", [("metadata_id", 1, _I64, False, None),
               ("double_value", 2, _F64, False, None),
               ("uint64_value", 3, _U64, False, None),
               ("int64_value", 4, _I64, False, None),
               ("str_value", 5, _STR, False, None),
               ("bytes_value", 6, _BYTES, False, None),
               ("ref_value", 7, _U64, False, None)]),
]


@functools.lru_cache(maxsize=1)
def _xspace_class():
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    package = "chipbench.xplane"
    f = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package=package, syntax="proto3")
    for msg, fields in _SCHEMA:
        m = f.message_type.add(name=msg)
        for name, number, typ, repeated, ref in fields:
            fd = m.field.add(name=name, number=number, type=typ,
                             label=3 if repeated else 1)
            if ref:
                fd.type_name = f".{package}.{ref}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{package}.XSpace"))


def scope_of(op_name: str) -> str:
    """The innermost ``fed.*`` component of an op's name path, else
    ``unscoped``."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else UNSCOPED


def _device_ops(plane) -> List[tuple]:
    """(name, start ns, duration ns, scope, program) per ``XLA Ops`` event
    of one device plane; start and duration as ``ProfileData`` gives
    them."""
    stat_name = {e.key: e.value.name for e in plane.stat_metadata}
    meta = {}
    for e in plane.event_metadata:
        stats = {stat_name.get(s.metadata_id): s for s in e.value.stats}
        tf_op = stats["tf_op"].str_value if "tf_op" in stats else ""
        program = (stats["program_id"].uint64_value
                   if "program_id" in stats else 0)
        meta[e.key] = (e.value.name, scope_of(tf_op), program)
    out = []
    for line in plane.lines:
        if line.name != trace.OPS_LINE:
            continue
        for ev in line.events:
            name, scope, program = meta[ev.metadata_id]
            # whole nanoseconds, as ProfileData rounds them
            out.append((name, float(line.timestamp_ns + ev.offset_ps // 1000),
                        float(ev.duration_ps // 1000), scope, program))
    return out


def load(path: str) -> dict:
    """``{"devices": {id: [(name, start ns, duration ns, scope,
    program)]}, "spans": [(name, start ns, duration ns)]}``: the ops of
    ``trace.load`` with their scope and program, and the host spans of
    both prefixes."""
    from jax.profiler import ProfileData

    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    devices = {}
    for plane in space.planes:
        if plane.name.startswith(trace.DEVICE_PREFIX):
            devices[int(plane.name[len(trace.DEVICE_PREFIX):])] = \
                _device_ops(plane)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIXES)]
    return {"devices": devices, "spans": spans}


@dataclass
class Layers:
    base: trace.Reduction            # what trace.reduce reads
    rounds: int                      # rounds the traced stretch ran
    scope_s: Dict[str, float]        # self seconds per scope
    op_scope_s: Dict[Tuple[str, str], float]   # (scope, op) -> seconds
    gaps: List[Tuple[str, float]]    # labelled by either prefix

    def ms_per_round(self) -> Dict[str, float]:
        return {k: v / self.rounds * 1e3 for k, v in self.scope_s.items()}


def _window(spans) -> Tuple[float, float]:
    marks = [(s, s + d) for name, s, d in spans if name == trace.WINDOW_SPAN]
    if len(marks) != 1:
        raise ValueError(f"want one {trace.WINDOW_SPAN} span, found "
                         f"{len(marks)}")
    return marks[0]


def reduce(data: dict, rounds: int,
           window: Optional[Tuple[float, float]] = None,
           devices: Optional[List[int]] = None, n_gaps: int = 10) -> Layers:
    """Reduce ``load``'s lists over ``window`` (default: the
    ``bench.window`` span) for a stretch of ``rounds`` rounds."""
    lo, hi = _window(data["spans"]) if window is None else window
    plain = {dev: [e[:3] for e in evs]
             for dev, evs in data["devices"].items()}
    bench_spans = [s for s in data["spans"]
                   if s[0].startswith(trace.SPAN_PREFIX)]
    base = trace.reduce({"devices": plain, "spans": bench_spans},
                        window=(lo, hi), devices=devices, n_gaps=n_gaps)
    ids = sorted(data["devices"]) if devices is None else list(devices)
    keyed_ns: Dict[Tuple[str, str], float] = {}
    first = []
    for dev in ids:
        evs = []
        for name, s, d, scope, program in data["devices"].get(dev, []):
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                # one key per (scope, program, op): the same short name in
                # two programs never shares a scope
                evs.append((f"{scope}\t{program}\t{trace.short_name(name)}",
                            a, b - a))
        if dev == ids[0]:
            first = evs
        for key, ns in trace._self_ns(evs).items():
            scope, _, op = key.split("\t")
            keyed_ns[scope, op] = keyed_ns.get((scope, op), 0.0) + ns
    op_scope_s = {k: v * 1e-9 / len(ids) for k, v in keyed_ns.items()}
    scope_s: Dict[str, float] = {}
    for (scope, _), s in op_scope_s.items():
        scope_s[scope] = scope_s.get(scope, 0.0) + s
    gaps = trace._gaps([(k.rsplit("\t", 1)[1], a, d) for k, a, d in first],
                       data["spans"], lo, hi, n_gaps)
    return Layers(base=base, rounds=rounds, scope_s=scope_s,
                  op_scope_s=op_scope_s, gaps=gaps)
