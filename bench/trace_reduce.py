"""Reduce a ``jax.profiler`` trace to the numbers the per-layer metrics
read: device busy and idle time over the traced window, time per device
operation and per compiled program, and the longest idle gaps, each put
down to what the host was doing then.

The harness wraps its own calls in ``jax.profiler.TraceAnnotation``
spans named ``bench:<phase>``, and the whole traced window in
``bench:window``.  A gap between device operations is attributed to the
innermost ``bench:`` span open on the host at the gap's midpoint
(``idle`` when none is).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench:window"
PHASE_PREFIX = "bench:"


@dataclasses.dataclass
class DeviceOp:
    name: str            # HLO instruction and opcode: "%qmm_2d.82 custom-call"
    program: str         # the compiled program it ran in: "jit__decode_impl"
    start_ns: float
    end_ns: float

    @property
    def control_flow(self) -> bool:
        """A while, conditional or call: its time is that of the ops it
        runs, which the trace lists on their own."""
        return self.name.rsplit(" ", 1)[-1] in ("while", "conditional",
                                                 "call")


_OPCODE = re.compile(r"\s([a-z][a-z0-9_.-]*)\(")


def op_name(text: str) -> str:
    """``"%fusion.3 = f32[8]{0} fusion(...), ..."`` -> ``"%fusion.3
    fusion"``: the instruction's name and opcode, without its shapes."""
    lhs, _, rhs = text.partition(" = ")
    m = _OPCODE.search(" " + rhs)
    return f"{lhs} {m.group(1)}" if m else lhs


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                 # union of op intervals, mean over devices
    devices: int
    ops: List[DeviceOp]           # ops inside the window (clipped to it)
    gaps: List[Tuple[str, float]]  # (host phase, seconds), longest first
    idle_by_phase: Dict[str, float]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, pattern: str) -> Optional[float]:
        """Device seconds of ops whose name matches ``pattern`` (regex),
        summed and divided by the device count; None when none match."""
        rx = re.compile(pattern)
        hit = [o.end_ns - o.start_ns for o in self.ops if rx.search(o.name)]
        return sum(hit) / 1e9 / self.devices if hit else None

    def program_seconds(self, pattern: str) -> Optional[float]:
        """Device seconds of ops that belong to programs (jitted
        functions) whose name matches ``pattern``."""
        rx = re.compile(pattern)
        hit = [o.end_ns - o.start_ns for o in self.ops
               if rx.search(o.program)]
        return sum(hit) / 1e9 / self.devices if hit else None

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        tot: Dict[str, float] = {}
        for o in self.ops:
            if o.control_flow:
                continue
            key = f"{o.program}/{o.name}" if o.program else o.name
            tot[key] = tot.get(key, 0.0) + (o.end_ns - o.start_ns) / 1e9
        return sorted(((k, v / self.devices) for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:n]

    def breakdown(self) -> dict:
        return {"device_ops": [[k, v] for k, v in self.top_ops(10)],
                "idle_gaps": [[k, v] for k, v in self.gaps[:10]]}


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def _union(intervals):
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


_DEVICE_PLANE = re.compile(r"/device:(TPU|GPU):\d+")


def is_device_plane(name: str) -> bool:
    """A chip's own plane.  The TPU runtime also writes planes such as
    ``/device:CUSTOM:Megascale Trace`` that hold no operations: counted
    as a device, each would halve the busy time."""
    return bool(_DEVICE_PLANE.fullmatch(name))


def reduce_planes(planes) -> TraceSummary:
    """Reduce planes given as ``[(name, [(line_name, [event...])])]``
    where an event has ``name``, ``start_ns`` and ``duration_ns``, as
    ``jax.profiler.ProfileData`` yields them.  A device plane's ``XLA
    Ops`` line gives the operations (named by their HLO text) and its
    ``XLA Modules`` line the program launches they ran in."""
    spans = []                       # host bench: spans
    dev_ops: Dict[str, List[DeviceOp]] = {}
    for pname, lines in planes:
        device = is_device_plane(pname)
        lines = dict(lines)
        if device:
            # each op runs inside one program launch of the modules line
            mods = sorted((float(e.start_ns),
                           float(e.start_ns) + float(e.duration_ns),
                           e.name.split("(")[0])
                          for e in lines.get("XLA Modules", ()))
            mstart = [m[0] for m in mods]
            ops = dev_ops.setdefault(pname, [])
            for ev in lines.get("XLA Ops", ()):
                s, d = float(ev.start_ns), float(ev.duration_ns)
                i = bisect.bisect_right(mstart, s) - 1
                prog = mods[i][2] if i >= 0 and mods[i][1] >= s else ""
                ops.append(DeviceOp(op_name(ev.name), prog, s, s + d))
            continue
        for events in lines.values():
            for ev in events:
                if ev.name.startswith(PHASE_PREFIX):
                    s, d = float(ev.start_ns), float(ev.duration_ns)
                    spans.append((ev.name, s, s + d))
    if not dev_ops:
        raise ValueError("the trace holds no device operations")
    win = [sp for sp in spans if sp[0] == WINDOW]
    if win:
        w0, w1 = win[0][1], win[0][2]
    else:
        w0 = min(o.start_ns for ops in dev_ops.values() for o in ops)
        w1 = max(o.end_ns for ops in dev_ops.values() for o in ops)
    phases = sorted((sp for sp in spans if sp[0] != WINDOW),
                    key=lambda sp: sp[1])
    starts = [sp[1] for sp in phases]

    def phase_at(t):
        # the latest-starting span that still covers t is the innermost;
        # spans nest or follow each other, so it lies a few places back
        i = bisect.bisect_right(starts, t)
        for sp in reversed(phases[max(0, i - 64):i]):
            if sp[2] >= t:
                return sp[0][len(PHASE_PREFIX):]
        return "idle"

    busy_total, gaps, idle_by = 0.0, [], {}
    kept: List[DeviceOp] = []
    for ops in dev_ops.values():
        inside = [DeviceOp(o.name, o.program, max(o.start_ns, w0),
                           min(o.end_ns, w1))
                  for o in ops if o.end_ns > w0 and o.start_ns < w1]
        kept += inside
        merged = _union((o.start_ns, o.end_ns) for o in inside)
        busy_total += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                ph = phase_at((a + b) / 2)
                gaps.append((ph, (b - a) / 1e9))
                idle_by[ph] = idle_by.get(ph, 0.0) + (b - a) / 1e9
    n = len(dev_ops)
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(window_s=(w1 - w0) / 1e9, busy_s=busy_total / 1e9 / n,
                        devices=n, ops=kept, gaps=gaps,
                        idle_by_phase={k: v / n for k, v in idle_by.items()})


def reduce(logdir: str) -> TraceSummary:
    """Reduce the newest trace under ``logdir``."""
    import jax
    pd = jax.profiler.ProfileData.from_file(find_xplane(logdir))
    planes = [(p.name, [(l.name, l.events) for l in p.lines])
              for p in pd.planes]
    return reduce_planes(planes)
