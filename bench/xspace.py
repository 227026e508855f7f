"""A profiler trace (``.xplane.pb``) read with the metadata that
``jax.profiler.ProfileData`` leaves out, and two reductions of it that
split what ``bench/trace.py`` sums whole: each program's device time by
the named scope its operations ran under, and the device's idle gaps by
the phase of the host round around them.

The trace is an XSpace protobuf message.  Its messages are declared
here by the field numbers of the profiler's ``xplane.proto``, into a
private descriptor pool, so that reading needs protobuf alone: no
TensorFlow import, which would load a second runtime beside the chip's.
On a TPU's plane each operation's event metadata carries the stats
``program_id`` (the program that ran it) and ``tf_op`` (its name path,
``jit(<program>)/<scope>/.../<op>:``, in which every
``jax.named_scope`` around the op is one component).

Times follow ``bench/trace.py``: the window runs from the start of the
first traversal span to the end of the last, on the host thread that
ran them; device times are clipped to it and averaged over the chips of
the run; idle gaps are read on the first chip.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import re

import numpy as np

from bench import trace as trace_mod

# the edge passes' named scopes (``repro.core.balancer``)
SCOPES = ("edges", "sources", "combine", "enumerate")
# prefix of the host round's spans (``repro.core`` round loop)
ROUND_PREFIX = "graph."
ROUND_SPAN = "graph.round"
_PROGRAM = re.compile(r"^(.*)\((\d+)\)$")

# xplane.proto, the fields read here: (name, number, type, message type);
# the two maps are their wire form, repeated key/value entries, and an
# XStat's value fields its oneof ``value``
_INT64, _UINT64, _DOUBLE, _STRING, _BYTES, _MESSAGE = 3, 4, 1, 9, 12, 11
_MESSAGES = {
    "XSpace": [("planes", 1, _MESSAGE, "XPlane")],
    "XPlane": [("id", 1, _INT64, None), ("name", 2, _STRING, None),
               ("lines", 3, _MESSAGE, "XLine"),
               ("event_metadata", 4, _MESSAGE, "EventMetadataEntry"),
               ("stat_metadata", 5, _MESSAGE, "StatMetadataEntry")],
    "EventMetadataEntry": [("key", 1, _INT64, None),
                           ("value", 2, _MESSAGE, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, _INT64, None),
                          ("value", 2, _MESSAGE, "XStatMetadata")],
    "XLine": [("id", 1, _INT64, None), ("name", 2, _STRING, None),
              ("timestamp_ns", 3, _INT64, None),
              ("events", 4, _MESSAGE, "XEvent")],
    "XEvent": [("metadata_id", 1, _INT64, None),
               ("offset_ps", 2, _INT64, None),
               ("duration_ps", 3, _INT64, None),
               ("stats", 4, _MESSAGE, "XStat")],
    "XStat": [("metadata_id", 1, _INT64, None),
              ("double_value", 2, _DOUBLE, None),
              ("uint64_value", 3, _UINT64, None),
              ("int64_value", 4, _INT64, None),
              ("str_value", 5, _STRING, None),
              ("bytes_value", 6, _BYTES, None),
              ("ref_value", 7, _UINT64, None)],
    "XEventMetadata": [("id", 1, _INT64, None), ("name", 2, _STRING, None),
                       ("display_name", 4, _STRING, None),
                       ("stats", 5, _MESSAGE, "XStat")],
    "XStatMetadata": [("id", 1, _INT64, None), ("name", 2, _STRING, None)],
}
_REPEATED = {("XSpace", "planes"), ("XPlane", "lines"),
             ("XPlane", "event_metadata"), ("XPlane", "stat_metadata"),
             ("XLine", "events"), ("XEvent", "stats"),
             ("XEventMetadata", "stats")}
_ONEOF = {"XStat": ("value", 2)}     # message: (oneof, first field number)
_PACKAGE = "bench.xspace"


@functools.cache
def _space_class():
    """The XSpace message class, built once per process."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xspace.proto", package=_PACKAGE, syntax="proto3")
    for msg, fields in _MESSAGES.items():
        m = fd.message_type.add(name=msg)
        oneof = _ONEOF.get(msg)
        if oneof:
            m.oneof_decl.add(name=oneof[0])
        for name, number, ftype, type_name in fields:
            f = m.field.add(name=name, number=number, type=ftype)
            f.label = (f.LABEL_REPEATED if (msg, name) in _REPEATED
                       else f.LABEL_OPTIONAL)
            if oneof and number >= oneof[1]:
                f.oneof_index = 0
            if type_name:
                f.type_name = f".{_PACKAGE}.{type_name}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.XSpace"))


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    end_ns: float
    stats: dict


@dataclasses.dataclass
class Plane:
    name: str
    lines: dict          # line name -> [Event]


def _stat_value(stat, stat_names: dict):
    """An XStat's value; a ``ref_value`` names a stat metadata entry
    whose name is the (interned) string."""
    kind = stat.WhichOneof("value")
    if kind is None:
        return None
    v = getattr(stat, kind)
    return stat_names.get(v, "") if kind == "ref_value" else v


def _stats(stats, stat_names: dict) -> dict:
    return {stat_names.get(s.metadata_id, str(s.metadata_id)):
            _stat_value(s, stat_names) for s in stats}


def read_planes(path: str, names=None) -> list:
    """The planes of the trace at ``path`` (those ``names`` matches,
    a callable on the plane's name, or all), each event with its name,
    start and end on the profiler's clock (ns), and its stats and its
    metadata's stats merged by stat name."""
    space = _space_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = []
    for plane in space.planes:
        if names is not None and not names(plane.name):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: (e.value.name, _stats(e.value.stats, stat_names))
                for e in plane.event_metadata}
        lines = {}
        for line in plane.lines:
            events = []
            for ev in line.events:
                name, mstats = meta.get(ev.metadata_id, ("", {}))
                start = line.timestamp_ns + ev.offset_ps * 1e-3
                stats = dict(mstats)
                stats.update(_stats(ev.stats, stat_names))
                events.append(Event(name, start,
                                    start + ev.duration_ps * 1e-3, stats))
            lines[line.name] = events
        out.append(Plane(plane.name, lines))
    return out


def op_scope(tf_op: str) -> str:
    """The first of :data:`SCOPES` on an op's name path, "" if none:
    ``jit(_bin_pass_impl)/combine/scatter-min:`` -> ``combine``."""
    path = tf_op.rsplit(":", 1)[0].split("/")[:-1]
    return next((c for c in path if c in SCOPES), "")


def span_name(event_name: str) -> str:
    """A host span's name without the metadata a ``TraceAnnotation``
    may encode into it (``graph.round#round=3#`` -> ``graph.round``)."""
    return event_name.split("#", 1)[0]


@dataclasses.dataclass
class Scoped:
    window_s: float
    # per program name, per scope ("" outside every scope): device
    # seconds of its operations, averaged over the chips
    scopes: dict
    # per host round phase (the innermost ``graph.*`` span around the
    # gap's midpoint, "other" outside every one): seconds of device
    # idle gaps, chip 0
    idle_by_span: dict
    # ``graph.round`` spans in the window
    round_spans: int


def reduce_file(path: str, span: str, chips: int) -> Scoped:
    """The trace at ``path`` reduced by scope and by round phase;
    ``span`` is the traversal span that bounds the window."""
    planes = read_planes(path, lambda n: bool(
        trace_mod._DEVICE_PLANE.match(n)) or n == trace_mod._HOST_PLANE)
    host = []
    devices = {}
    for plane in planes:
        m = trace_mod._DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = plane
            continue
        for events in plane.lines.values():
            if any(e.name == span for e in events):
                host.extend(events)
    spans = [e for e in host if e.name == span]
    if not spans:
        raise ValueError(f"no {span!r} span in {path}")
    lo = min(e.start_ns for e in spans)
    hi = max(e.end_ns for e in spans)
    if len(devices) < chips:
        raise ValueError(f"{path}: {len(devices)} TPU planes, the run "
                         f"used {chips} chips")

    by_scope = collections.defaultdict(collections.Counter)
    idle = {}
    for k, dev_id in enumerate(sorted(devices)[:chips]):
        lines = devices[dev_id].lines
        programs = {}
        for e in lines.get(trace_mod._MODULES_LINE, []):
            pm = _PROGRAM.match(e.name)
            if pm:
                programs[int(pm.group(2))] = trace_mod.program_name(
                    pm.group(1))
        ops = lines.get(trace_mod._OPS_LINE, [])
        for e in ops:
            d = min(e.end_ns, hi) - max(e.start_ns, lo)
            if d <= 0:
                continue
            program = programs.get(e.stats.get("program_id"), "")
            by_scope[program][op_scope(e.stats.get("tf_op") or "")] += d
        if k == 0:
            iv = trace_mod._clip(np.array(
                [(e.start_ns, e.end_ns) for e in ops],
                float).reshape(-1, 2), lo, hi)
            # the innermost round span around each gap's midpoint
            idle = trace_mod._attribute(
                trace_mod.gaps(iv, lo, hi),
                [(span_name(e.name), e.start_ns, e.end_ns) for e in host
                 if e.name.startswith(ROUND_PREFIX)])
    scale = 1e-9 / chips
    rounds = sum(1 for e in host if span_name(e.name) == ROUND_SPAN
                 and lo <= e.start_ns and e.end_ns <= hi)
    return Scoped(
        window_s=(hi - lo) * 1e-9,
        scopes={p: {s: t * scale for s, t in c.items()}
                for p, c in by_scope.items()},
        idle_by_span={n: t * 1e-9 for n, t in idle.items()},
        round_spans=rounds)


def seconds_under(scopes: dict, programs, scope_names):
    """Device seconds of ``programs``' operations under ``scope_names``
    (a :attr:`Scoped.scopes` table); None where none ran under them."""
    hit = [scopes[p][s] for p in programs if p in scopes
           for s in scope_names if s in scopes[p]]
    return sum(hit) if hit else None
