"""Reduce the program's own marks in a ``jax.profiler`` trace: the
``repro:<phase>`` spans its supervisor and engine open on the host
(``repro.obs.span``), and the ``jax.named_scope`` of each device
operation (``repro.train.step``).

It reads the same trace as :mod:`bench.trace_reduce` and leaves that
reduction as it is: the window is the ``bench:window`` span, device busy
time the union of the chips' ``XLA Ops``.  Device idle time is put down
to the innermost ``repro:`` span open on the host at each moment
(``outside`` where none is), each gap split by its overlap with the
spans, so the phases' idle sums to the window's idle.

A TPU's ``XLA Ops`` events carry no ``op_name``, so an operation's scope
comes from the HLO its program was compiled to, which the profiler keeps
in its ``/host:metadata`` plane (a ``Hlo Proto`` stat per program): the
first of :data:`SCOPES` found as a component of the instruction's
``op_name`` (``jit(step)/dfxp_store/...``).  ``ProfileData`` does not
expose that plane's event metadata, so :func:`hlo_scopes` reads the
protobuf wire format itself.

A trace from a program without these marks reduces to empty fields, and
every reading of it is None.

    python -m bench.program_trace <trace dir>    # the fields, as JSON
"""
from __future__ import annotations

import bisect
import dataclasses
import json
import pathlib
import re
import sys
from typing import Dict, List, Optional, Tuple

from bench.trace_reduce import (WINDOW, DeviceOp, _union, find_xplane,
                                is_device_plane, op_name)

PREFIX = "repro:"
OUTSIDE = "outside"
SCOPES = ("fwd_bwd", "grad_quant", "optimizer", "dfxp_store", "controller",
          "sentinels")
_SCOPE = re.compile(r"/(%s)/" % "|".join(SCOPES))
# host phases in which the device waits on serial host work
STALL_PHASES = ("train.batch", "train.launch", "train.record")


@dataclasses.dataclass
class ProgramTrace:
    window_s: float
    devices: int
    spans: Dict[str, Tuple[int, float]]   # name: (count, seconds in window)
    idle_by_program_phase: Dict[str, float]
    scope_seconds: Dict[str, float]       # device seconds by named scope
    steps: int                            # train.step spans ending inside
    step_fetches: List[int]               # their noted host fetches

    def program_spans(self) -> Dict[str, dict]:
        return {k: {"count": n, "seconds": s}
                for k, (n, s) in sorted(self.spans.items())}

    def stall_ms_per_step(self) -> Optional[float]:
        """Device idle inside the supervisor's serial host phases, per
        step that ended in the window."""
        if not self.steps:
            return None
        idle = sum(self.idle_by_program_phase.get(p, 0.0)
                   for p in STALL_PHASES)
        return 1e3 * idle / self.steps

    def fetches_per_step(self) -> Optional[float]:
        if not self.step_fetches:
            return None
        return sum(self.step_fetches) / len(self.step_fetches)

    def scope_ms_per_step(self, scope: str, steps: int) -> Optional[float]:
        s = self.scope_seconds.get(scope)
        if s is None or not steps:
            return None
        return 1e3 * s / steps


def _stat(ev, key):
    for k, v in getattr(ev, "stats", ()):
        if k == key:
            return v
    return None


def _fields(buf):
    """``(field number, value)`` of one protobuf message: varints as
    ints, length-delimited fields as memoryviews."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            v, i = buf[i:i + width], i + width
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def _varint(buf, i):
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _field(msg, number):
    return next((v for f, v in _fields(msg) if f == number), None)


def hlo_scopes(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """``{program: {instruction: scope}}`` from the HLO of each program
    in a serialized ``XSpace``.  Field numbers: XSpace.planes 1;
    XPlane.name 2, event_metadata 4, stat_metadata 5 (map entries: key
    1, value 2); X*Metadata.id 1, name 2, XEventMetadata.stats 5;
    XStat.metadata_id 1, bytes_value 6; HloProto.hlo_module 1;
    HloModuleProto.computations 3; HloComputationProto.instructions 2;
    HloInstructionProto.name 1, metadata 7; OpMetadata.op_name 2."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1 or bytes(_field(plane, 2) or b"") != b"/host:metadata":
            continue
        hlo_stat = {_field(_field(e, 2), 1) for k, e in _fields(plane)
                    if k == 5 and bytes(_field(_field(e, 2), 2) or b"")
                    == b"Hlo Proto"}
        for k, entry in _fields(plane):
            if k != 4:
                continue
            meta = _field(entry, 2)
            program = bytes(_field(meta, 2) or b"").decode()
            for sf, stat in _fields(meta):
                if sf != 5 or _field(stat, 1) not in hlo_stat:
                    continue
                scopes = out.setdefault(program, {})
                module = _field(_field(stat, 6), 1)
                for cf, comp in _fields(module):
                    if cf != 3:
                        continue
                    for jf, ins in _fields(comp):
                        md = _field(ins, 7) if jf == 2 else None
                        m = md is not None and _SCOPE.search(
                            bytes(_field(md, 2) or b"").decode())
                        if m:
                            name = bytes(_field(ins, 1)).decode()
                            scopes[name] = m.group(1)
    return out


def _innermost(spans, w0, w1):
    """Segments ``(start, end, name)`` covering ``[w0, w1]``, each named
    for the innermost span open there (the latest-starting), else
    :data:`OUTSIDE`."""
    cuts = sorted({w0, w1} | {t for s, e, _ in spans for t in (s, e)
                              if w0 < t < w1})
    by_start = sorted(spans, key=lambda sp: (sp[0], -sp[1]))  # outer first
    starts = [s for s, _, _ in by_start]
    out = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        i = bisect.bisect_right(starts, mid)
        name = OUTSIDE
        # spans nest or follow each other: the innermost lies a few back
        for s, e, n in reversed(by_start[max(0, i - 64):i]):
            if e >= mid:
                name = n
                break
        out.append((a, b, name))
    return out


def reduce_planes(planes, scopes: Optional[Dict[str, Dict[str, str]]] = None
                  ) -> Optional[ProgramTrace]:
    """Planes as :func:`bench.trace_reduce.reduce_planes` takes them
    (host events may carry ``stats``), ``scopes`` as :func:`hlo_scopes`
    gives them; None where no chip ran an operation."""
    scopes = scopes or {}
    # a launch whose full name the metadata plane lacks takes the one
    # program of its base name, where there is one
    bases: Dict[str, List[str]] = {}
    for prog in scopes:
        bases.setdefault(prog.split("(")[0], []).append(prog)
    spans, win = [], None
    dev_ops: Dict[str, List[Tuple[DeviceOp, Optional[str]]]] = {}
    step_ends: List[Tuple[float, int]] = []
    for pname, lines in planes:
        lines = dict(lines)
        if is_device_plane(pname):
            # a launch's full name ("jit_step(12)") names its program
            mods = sorted((float(e.start_ns),
                           float(e.start_ns) + float(e.duration_ns), e.name)
                          for e in lines.get("XLA Modules", ()))
            mstart = [m[0] for m in mods]
            ops = dev_ops.setdefault(pname, [])
            for ev in lines.get("XLA Ops", ()):
                s, d = float(ev.start_ns), float(ev.duration_ns)
                i = bisect.bisect_right(mstart, s) - 1
                prog = mods[i][2] if i >= 0 and mods[i][1] >= s else ""
                op = DeviceOp(op_name(ev.name), prog.split("(")[0], s, s + d)
                instr = op.name.split(" ")[0].lstrip("%")
                same = bases.get(op.program, [])
                table = scopes.get(prog) or (
                    scopes[same[0]] if len(same) == 1 else {})
                ops.append((op, table.get(instr)))
            continue
        for events in lines.values():
            for ev in events:
                s = float(ev.start_ns)
                e = s + float(ev.duration_ns)
                if ev.name == WINDOW and win is None:
                    win = (s, e)
                elif ev.name.startswith(PREFIX):
                    name = ev.name[len(PREFIX):]
                    spans.append((s, e, name))
                    if name == "train.step":
                        step_ends.append((e, _stat(ev, "fetches")))
    if not dev_ops:
        return None
    if win is None:
        win = (min(o.start_ns for ops in dev_ops.values() for o, _ in ops),
               max(o.end_ns for ops in dev_ops.values() for o, _ in ops))
    w0, w1 = win
    n = len(dev_ops)

    counts: Dict[str, Tuple[int, float]] = {}
    for s, e, name in spans:
        if e > w0 and s < w1:
            c, t = counts.get(name, (0, 0.0))
            counts[name] = (c + 1, t + (min(e, w1) - max(s, w0)) / 1e9)

    segs = _innermost(spans, w0, w1) if spans else []
    seg_start = [a for a, _, _ in segs]
    idle: Dict[str, float] = {}
    by_scope: Dict[str, float] = {}
    for ops in dev_ops.values():
        inside = [(max(o.start_ns, w0), min(o.end_ns, w1), o, sc)
                  for o, sc in ops if o.end_ns > w0 and o.start_ns < w1]
        for s, e, o, sc in inside:
            if sc is not None and not o.control_flow:
                by_scope[sc] = by_scope.get(sc, 0.0) + (e - s) / 1e9
        if not segs:
            continue
        merged = _union((s, e) for s, e, _, _ in inside)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            j = max(0, bisect.bisect_right(seg_start, a) - 1)
            while j < len(segs) and segs[j][0] < b:
                sa, sb, name = segs[j]
                over = min(b, sb) - max(a, sa)
                if over > 0:
                    idle[name] = idle.get(name, 0.0) + over / 1e9
                j += 1
    fetches = [f for e, f in step_ends if w0 < e <= w1 and f is not None]
    return ProgramTrace(
        window_s=(w1 - w0) / 1e9, devices=n, spans=counts,
        idle_by_program_phase={k: v / n for k, v in idle.items()},
        scope_seconds={k: v / n for k, v in by_scope.items()},
        steps=sum(1 for e, _ in step_ends if w0 < e <= w1),
        step_fetches=[int(f) for f in fetches])


def planes_of(path: str):
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    return [(p.name, [(l.name, l.events) for l in p.lines])
            for p in pd.planes]


_cache: Dict[Tuple[str, float], Optional[ProgramTrace]] = {}


def reduce(logdir: str) -> Optional[ProgramTrace]:
    """Reduce the newest trace under ``logdir`` (kept for the readers of
    one run, which share it)."""
    path = find_xplane(logdir)
    key = (path, pathlib.Path(path).stat().st_mtime)
    if key not in _cache:
        _cache.clear()
        _cache[key] = reduce_planes(
            planes_of(path), hlo_scopes(pathlib.Path(path).read_bytes()))
    return _cache[key]


def trace_dir(root: pathlib.Path, ctx: dict) -> Optional[pathlib.Path]:
    """The harness's trace directory of the run ``ctx`` describes: the
    cell of ``BENCHMARK.json`` whose configuration and traffic files
    hold what ``ctx`` carries."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for cell in bench["workloads"]:
        conf = root / files[cell["config"]]
        mix = root / "bench" / "traffic" / f"{cell['traffic']}.json"
        if (conf.exists() and mix.exists()
                and json.loads(conf.read_text()) == ctx.get("config")
                and json.loads(mix.read_text()) == ctx.get("mix")):
            d = root / ".cache" / "bench" / "trace" / cell["name"]
            return d if d.is_dir() else None
    return None


def for_run(root: pathlib.Path, ctx: dict) -> Optional[ProgramTrace]:
    """The reduced trace of the traced run ``ctx`` describes; None
    without one."""
    d = trace_dir(root, ctx)
    if d is None:
        return None
    try:
        return reduce(str(d))
    except FileNotFoundError:
        return None


def main(argv) -> int:
    pt = reduce(argv[0])
    if pt is None:
        print("the trace holds no device operations", file=sys.stderr)
        return 1
    out = dataclasses.asdict(pt)
    out["spans"] = pt.program_spans()
    out["step_fetches"] = sorted(set(pt.step_fetches))
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
