"""The program's own names in a traced window: each device op's named
scope, and the DiT scheduler's host spans.

The program puts a `jax.named_scope` around each layer of the DiT step
(DESIGN.md "Tracing"). XLA keeps the scope path in each instruction's
`op_name` metadata ("jit(tick)/while/body/.../dit.mlp/dot_general"),
and a TPU trace carries it as the stat `OP_NAME_STAT` on the op's event
metadata, which `jax.profiler.ProfileData` does not show: this module
reads it from the trace file's wire format. The scheduler's spans
(`dit.sched.*`, `jax.profiler.TraceAnnotation`) sit on the host plane,
on the clock of the device ops.

`of(ctx)` is what a per-layer reader calls. The first call of a traced
run reads the trace file again for the op_name of each op of the run's
reduction (`ctx.trace.ops`, same ops, same order, same self times),
prints one `trace scope <name> <s> s` line per top-level scope on
standard error, and adds the scheduler's spans to the reduction's host
spans, so that the run's idle gaps are labelled by the innermost of the
harness's and the scheduler's spans. Later calls share that reading.
"""
from __future__ import annotations

import collections
import pathlib
import re
import sys
from typing import Dict, List, Tuple

from bench import trace_reduce

OP_NAME_STAT = "tf_op"
SPAN_PREFIX = "dit.sched."
DEVICE_PLANE = "/device:"
# a named scope in an op_name path ("dit.mlp", "sla.plan"); the other
# components are programs ("jit(tick)"), loops and primitives
SCOPE = re.compile(r"[A-Za-z_]\w*(?:\.\w+)+")
UNSCOPED = "unscoped"


def top_scope(op_name: str) -> str:
    """The outermost named scope of an op_name path, or UNSCOPED."""
    return next((c for c in op_name.split("/") if SCOPE.fullmatch(c)),
                UNSCOPED)


class Scopes:
    """The op_name of each op of a reduction (`names[i]` for `ops[i]`,
    "" where the trace has none) and the scheduler's host spans."""

    def __init__(self, ops: List[trace_reduce.Op], names: List[str],
                 spans: List[Tuple[str, int, int]] = ()):
        if len(ops) != len(names):
            raise ValueError(f"{len(names)} op names for {len(ops)} ops")
        self.ops, self.names, self.spans = ops, names, list(spans)

    def seconds(self, name: str) -> float:
        """Self time of the ops whose op_name path has `name` as one of
        its components ("sla.plan", or an inner scope such as "drift")."""
        return sum(op.self_ns for op, n in zip(self.ops, self.names)
                   if name in n.split("/")) * 1e-9

    def summary(self) -> List[Tuple[str, float]]:
        """(top-level scope, self seconds) over every op, UNSCOPED for
        ops under no named scope, longest first."""
        out = collections.Counter()
        for op, n in zip(self.ops, self.names):
            out[top_scope(n)] += op.self_ns * 1e-9
        return sorted(out.items(), key=lambda kv: -kv[1])


def of(ctx) -> Scopes:
    """The scopes of the run's traced window (see the module's doc)."""
    red = ctx.trace
    if getattr(red, "scopes", None) is None:
        from bench.harness import TRACE_DIR

        red.scopes = read(trace_reduce.find_xplane(TRACE_DIR / ctx.cell.name),
                          red)
        red.spans.extend(red.scopes.spans)
        for name, secs in red.scopes.summary():
            print(f"trace scope {name} {secs!r} s", file=sys.stderr)
    return red.scopes


def read(path, red: trace_reduce.Reduction) -> Scopes:
    """The op_names of `red.ops`, which `trace_reduce.reduce_file(path)`
    made: device 0's "XLA Ops" events that overlap the window, in the
    trace's order. Raises where the two disagree."""
    from jax.profiler import ProfileData

    data = pathlib.Path(path).read_bytes()
    meta = metadata_stats(data)
    lo, hi = red.window
    spans, devices = [], []
    for plane in ProfileData.from_serialized_xspace(data).planes:
        if plane.name.startswith("/host:"):
            spans += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                      for line in plane.lines for ev in line.events
                      if ev.name.startswith(SPAN_PREFIX)]
        elif plane.name.startswith(("/device:TPU:", "/device:CPU:")):
            lines = {line.name: line for line in plane.lines}
            if trace_reduce.OPS_LINE in lines:
                devices.append((plane.name, lines[trace_reduce.OPS_LINE]))
    names = []
    if devices:
        plane, line = min(devices, key=lambda dv: dv[0])
        stats = meta.get(plane, {})
        names = [stats.get(ev.name, {}).get(OP_NAME_STAT, "")
                 for ev in line.events
                 if ev.start_ns + ev.duration_ns > lo and ev.start_ns < hi]
        short = [trace_reduce.short_name(ev.name) for ev in line.events
                 if ev.start_ns + ev.duration_ns > lo and ev.start_ns < hi]
        if short != [op.name for op in red.ops]:
            raise ValueError(f"{path} does not hold the reduction's ops")
    return Scopes(red.ops, names, spans)


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo, hi):
    """(field number, value) of one protobuf message in buf[lo:hi]: an
    int for a varint, (start, end) for a length-delimited field, None
    for a fixed-width one."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif kind in (1, 5):
            v, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def metadata_stats(data: bytes) -> Dict[str, Dict[str, Dict[str, str]]]:
    """{plane name: {event metadata name: {stat name: str value}}} for
    the device planes of a serialized XSpace: the stats XLA attaches to
    an instruction once, on its event metadata. A minimal reader of the
    XSpace wire format (XSpace .planes 1; XPlane .name 2,
    .event_metadata 4, .stat_metadata 5; map entries key 1, value 2;
    XEventMetadata .name 2, .stats 5; XStat .metadata_id 1, .str_value
    5, .ref_value 7; XStatMetadata .name 2; a plane's name precedes its
    maps on the wire); the event lines are skipped unread."""
    buf = memoryview(data)
    out = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(buf, *plane):
            if pf == 2:
                name = _text(buf, v)
            elif pf in (4, 5) and name.startswith(DEVICE_PLANE):
                entry = dict(_fields(buf, *v))
                if 2 not in entry:
                    continue
                if pf == 4:
                    events.append(entry[2])
                else:
                    stat_names[entry.get(1, 0)] = next(
                        (_text(buf, sv) for sf, sv in _fields(buf, *entry[2])
                         if sf == 2), "")
        if not name.startswith(DEVICE_PLANE):
            continue
        table = out.setdefault(name, {})
        for span in events:
            ev_name, stats = "", {}
            for ef, ev in _fields(buf, *span):
                if ef == 2:
                    ev_name = _text(buf, ev)
                elif ef == 5:
                    st = dict(_fields(buf, *ev))
                    value = (_text(buf, st[5]) if 5 in st else
                             stat_names.get(st[7]) if 7 in st else None)
                    if value is not None:
                        stats[stat_names.get(st.get(1, 0), "")] = value
            if stats:
                table[ev_name] = stats
    return out
