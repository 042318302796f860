"""From a profiler trace (`.xplane.pb`, read with jax.profiler.ProfileData)
to the quantities the per-layer metrics read.

  * the traced window: the host span `bench.window` the harness records;
  * device busy time: the union of the op intervals on each TPU's
    "XLA Ops" line inside the window, averaged over the chips;
  * each device op's duration, with the XLA module (jitted program) that
    ran it, from the "XLA Modules" line;
  * idle gaps inside the window, each labelled by the innermost
    harness span (`bench.*`) that covers its midpoint.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Op:
    name: str       # short HLO name ("sla_fwd.9", "fusion.287", ...)
    module: str
    start: int      # ns
    dur: int        # ns
    self_ns: int = 0  # dur less the ops nested inside it (a while loop's body)


def short_name(hlo_text: str) -> str:
    """'%sla_fwd.9 = (f32[...]) custom-call(...)' -> 'sla_fwd.9'."""
    head = hlo_text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


@dataclasses.dataclass
class Reduction:
    window: Tuple[int, int]
    ops: List[Op]                       # device 0's ops inside the window
    modules: List[Op]                   # device 0's program executions
    spans: List[Tuple[str, int, int]]   # host (name, start, end)
    busy_ns_per_device: List[int]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        b = self.busy_ns_per_device
        return sum(b) / len(b) * 1e-9

    def op_seconds(self) -> Dict[str, float]:
        """Self time per op, summed over calls, keyed by program/op with
        the op's numeric suffix dropped ("jit_tick/sla_fwd")."""
        out = collections.Counter()
        for op in self.ops:
            mod = op.module.split("(", 1)[0]
            out[f"{mod}/{op.name.rsplit('.', 1)[0]}"] += op.self_ns * 1e-9
        return dict(out)

    def kernel_seconds(self, prefix: str) -> float:
        """Summed device time of the ops whose short name starts with
        `prefix` (a kernel's custom call)."""
        return sum(op.dur for op in self.ops
                   if op.name.startswith(prefix)) * 1e-9

    def module_durations(self, needle: str) -> List[float]:
        """Durations (s) of each execution of the programs whose module
        name contains `needle`."""
        return [m.dur * 1e-9 for m in self.modules if needle in m.name]

    def module_busy_seconds(self, needle: str) -> float:
        """Device time of the ops of the programs whose module name
        contains `needle`: self times, so that an op nested in another
        (a while loop's body) is counted once."""
        return sum(op.self_ns for op in self.ops
                   if needle in op.module) * 1e-9

    def module_summary(self) -> List[Tuple[str, float, int]]:
        """(module, seconds, calls) of every program run in the window,
        longest first."""
        secs, calls = collections.Counter(), collections.Counter()
        for m in self.modules:
            secs[m.name] += m.dur * 1e-9
            calls[m.name] += 1
        return sorted(((n, s, calls[n]) for n, s in secs.items()),
                      key=lambda r: -r[1])

    def idle_gaps(self) -> List[Tuple[str, float]]:
        lo, hi = self.window
        gaps, cur = [], lo
        for s, e in _union((op.start, op.start + op.dur) for op in self.ops):
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if hi > cur:
            gaps.append((cur, hi))
        out = []
        for s, e in gaps:
            mid = (s + e) // 2
            cover = [sp for sp in self.spans if sp[1] <= mid <= sp[2]
                     and sp[0] != "bench.window"]
            label = (min(cover, key=lambda sp: sp[2] - sp[1])[0] if cover
                     else "outside harness spans")
            out.append((label, (e - s) * 1e-9))
        return out

    def breakdown(self) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.idle_gaps(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def _self_times(ops):
    """Each op's duration less the ops nested directly inside it."""
    stack = []
    for op in sorted(ops, key=lambda o: (o.start, -o.dur)):
        op.self_ns = op.dur
        while stack and stack[-1].start + stack[-1].dur <= op.start:
            stack.pop()
        if stack:
            stack[-1].self_ns -= op.dur
        stack.append(op)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def find_xplane(trace_dir) -> str:
    files = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_file(path) -> Reduction:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/device:TPU:") or \
                plane.name.startswith("/device:CPU:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                devices.append((plane.name, lines))
    windows = [sp for sp in spans if sp[0] == "bench.window"]
    if not windows:
        raise ValueError("trace has no bench.window span")
    lo, hi = windows[0][1], windows[0][2]
    devices.sort(key=lambda dv: dv[0])
    busy, ops0, mods0 = [], [], []
    for k, (_, lines) in enumerate(devices):
        mods = [Op(ev.name, ev.name, ev.start_ns, ev.duration_ns)
                for ev in (lines[MODULES_LINE].events
                           if MODULES_LINE in lines else [])]
        mods.sort(key=lambda m: m.start)
        ops = []
        starts = [m.start for m in mods]
        import bisect
        for ev in lines[OPS_LINE].events:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e <= lo or s >= hi:
                continue
            i = bisect.bisect_right(starts, s) - 1
            module = (mods[i].name if i >= 0
                      and mods[i].start + mods[i].dur >= s else "")
            ops.append(Op(short_name(ev.name), module, s, ev.duration_ns))
        _self_times(ops)
        busy.append(sum(e - s for s, e in _union(
            _clip(((o.start, o.start + o.dur) for o in ops), lo, hi))))
        if k == 0:
            ops0 = ops
            mods0 = [m for m in mods if m.start < hi and m.start + m.dur > lo]
    if not devices:
        raise ValueError("trace has no device plane with an XLA Ops line")
    return Reduction(window=(lo, hi), ops=ops0, modules=mods0, spans=spans,
                     busy_ns_per_device=busy)


def reduce_dir(trace_dir) -> Reduction:
    return reduce_file(find_xplane(trace_dir))
