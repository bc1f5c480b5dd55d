"""Reduction of a JAX profiler trace to the benchmark's device numbers.

`traced_answer` runs one answer under `jax.profiler.trace`, reads the
`.xplane.pb` it wrote with `jax.profiler.ProfileData` and reduces it:

* device busy time: the union of the intervals in which an operation ran
  on a chip, inside the traced answer's host span, averaged over chips;
* the device operations that took the most time;
* the idle time, each stretch of it named by the innermost harness span
  (`harness.Spans`, written as `TraceAnnotation`s) open during it;
* device time and executions of the operations under a named scope (a
  kernel's `jax.named_scope`), for its roofline share.  A TPU trace's op
  events name only the HLO instruction, so the scopes come from the HLO
  of each program that the profiler records beside them (`op_tags`),
  read from the protobuf by hand: JAX's `ProfileData` does not expose it.

The reduction works on plain event records (`extract`), so the tests
check it on a small recorded fixture without a chip.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Sequence, Tuple

WINDOW = "traced_answer"
# the harness spans an idle gap can be attributed to (innermost wins)
SPANS = ("answer", "graph", "routing", "paths", "solve", "workload", "scan",
         "readback")


# ---- the raw trace: protobuf wire format, read without other packages ----
# XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 (map entry:
# value = 2); XEventMetadata: name = 2, stats = 5; XStat: bytes = 6.
# HloProto.hlo_module = 1; HloModuleProto.computations = 3;
# HloComputationProto: instructions = 2, id = 5; HloInstructionProto:
# name = 1, opcode = 2, metadata = 7 (OpMetadata.op_name = 2),
# called_computation_ids = 38.

def _varint(b, i: int):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b, start: int = 0, end: int = None):
    """(field, value) of one message; a length-delimited value is its
    (start, end) in `b`."""
    i, end = start, len(b) if end is None else end
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(b, span) -> str:
    return bytes(b[span[0]:span[1]]).decode("utf-8", "replace")


def hlo_protos(raw: bytes) -> Dict[str, bytes]:
    """{program name: serialized HloProto}, from the profiler's
    ``/host:metadata`` plane of an XSpace."""
    b = memoryview(raw)
    out = {}
    for f, plane in _fields(b):
        if f != 1:
            continue
        parts = list(_fields(b, *plane))
        if not any(pf == 2 and _text(b, v) == "/host:metadata"
                   for pf, v in parts):
            continue
        for pf, entry in parts:
            if pf != 4:
                continue
            for ef, meta in _fields(b, *entry):
                if ef != 2:
                    continue
                name, proto = None, None
                for mf, mv in _fields(b, *meta):
                    if mf == 2:
                        name = _text(b, mv)
                    elif mf == 5:
                        for sf, sv in _fields(b, *mv):
                            if sf == 6:
                                proto = bytes(b[sv[0]:sv[1]])
                if name and proto:
                    out[name] = proto
    return out


def op_tags(hlo: bytes) -> Dict[str, Tuple[str, str, str]]:
    """{instruction name: (opcode, op_name, tags)} of one HloProto.
    `tags` joins ``op_name@opcode`` of the instruction and, for a fusion,
    of every instruction it fuses: the source scopes of the work the op
    does."""
    b = memoryview(hlo)
    comps: Dict[int, list] = {}
    for f, module in _fields(b):
        if f != 1:
            continue
        for mf, comp in _fields(b, *module):
            if mf != 3:
                continue
            cid, ins = None, []
            for cf, cv in _fields(b, *comp):
                if cf == 5:
                    cid = cv
                elif cf == 2:
                    name, opcode, op_name, called = "", "", "", []
                    for inf, iv in _fields(b, *cv):
                        if inf == 1:
                            name = _text(b, iv)
                        elif inf == 2:
                            opcode = _text(b, iv)
                        elif inf == 7:
                            for of, ov in _fields(b, *iv):
                                if of == 2:
                                    op_name = _text(b, ov)
                        elif inf == 38:
                            if isinstance(iv, tuple):  # packed
                                j = iv[0]
                                while j < iv[1]:
                                    c, j = _varint(b, j)
                                    called.append(c)
                            else:
                                called.append(iv)
                    ins.append((name, opcode, op_name, called))
            comps[cid] = ins
    fused: Dict[int, List[str]] = {}

    def inside(cid: int) -> List[str]:
        if cid not in fused:
            fused[cid] = []
            for _, opcode, op_name, called in comps.get(cid, ()):
                fused[cid].append(f"{op_name}@{opcode}")
                if opcode == "fusion":
                    for c in called:
                        fused[cid].extend(inside(c))
        return fused[cid]

    out = {}
    for ins in comps.values():
        for name, opcode, op_name, called in ins:
            tags = [f"{op_name}@{opcode}"]
            if opcode == "fusion":
                for c in called:
                    tags.extend(inside(c))
            out[name] = (opcode, op_name, " ".join(sorted(set(tags))))
    return out


def op_name(event_name: str) -> str:
    """An op event's instruction name: a TPU trace names an op by its HLO
    text (``%fusion.410 = f32[...] fusion(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def extract(path: str) -> dict:
    """{"device": {plane: [[op, start_ns, dur_ns, opcode, tags], ...]},
    "host": [[name, start_ns, dur_ns], ...]} from one `.xplane.pb`.
    Device events come from each TPU plane's "XLA Ops" line, whose events
    carry no source scope: each op is looked up, by its instruction name,
    in the HLO that the profiler records of the program it runs in (the
    "XLA Modules" event around it), for its opcode, its op_name (`op` is
    the instruction name and that op_name) and its tags (`op_tags`).
    Host events are the harness spans and the traced window."""
    import bisect

    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    tags_of = {name: op_tags(p) for name, p in hlo_protos(raw).items()}
    data = ProfileData.from_serialized_xspace(raw)
    device: Dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU"):
            lines = {line.name: line for line in plane.lines}
            mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           tags_of.get(ev.name, {}))
                          for ev in (lines["XLA Modules"].events
                                     if "XLA Modules" in lines else ()))
            starts = [m[0] for m in mods]
            rows = device.setdefault(plane.name, [])
            for ev in (lines["XLA Ops"].events if "XLA Ops" in lines
                       else ()):
                name = op_name(ev.name)
                k = bisect.bisect_right(starts, ev.start_ns) - 1
                tags = mods[k][2] if k >= 0 and ev.start_ns < mods[k][1] \
                    else {}
                opcode, label, tag = tags.get(name, ("", "", ""))
                rows.append([f"{name} {label}".strip(), float(ev.start_ns),
                             float(ev.duration_ns), opcode, tag])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW or ev.name in SPANS:
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
    return {"device": device, "host": host}


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    """Merge [start, end) intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def window_of(events: dict) -> Tuple[float, float]:
    w = [h for h in events["host"] if h[0] == WINDOW]
    if not w:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    return w[0][1], w[0][1] + w[0][2]


def busy_intervals(rows, lo: float, hi: float):
    clipped = [(max(r[1], lo), min(r[1] + r[2], hi)) for r in rows]
    return union([(s, e) for s, e in clipped if e > s])


# ops whose interval holds other ops' (a loop, a branch, a call): not
# counted as work of their own
CONTROL = ("while", "conditional", "call")


def in_scope(tags: str, scope: str, anchor: str = "") -> bool:
    """Whether one of an op's tags (``op_name@opcode``) lies under the
    named scope, and, with `anchor`, is an instruction of that opcode."""
    return any(scope in t and (not anchor or t.endswith("@" + anchor))
               for t in tags.split())


def reduce(events: dict, scopes: Dict[str, str] = None) -> dict:
    """busy_s, window_s (seconds), the breakdown, and per scope its
    device seconds (the ops that do work under it) and executions (the
    runs of its anchor instruction: `scopes` maps a scope to the opcode
    that one call of it runs once, e.g. a kernel's one gather)."""
    lo, hi = window_of(events)
    window_s = (hi - lo) * 1e-9
    planes = events["device"]
    busy = {p: busy_intervals(rows, lo, hi) for p, rows in planes.items()}
    busy_s = (sum(sum(e - s for s, e in iv) for iv in busy.values())
              / max(1, len(busy)) * 1e-9)

    per_op: Dict[str, float] = {}
    for rows in planes.values():
        for name, s, d, opcode, _ in rows:
            if lo <= s < hi and opcode not in CONTROL:
                per_op[name] = per_op.get(name, 0.0) + d * 1e-9
    n_planes = max(1, len(planes))
    top_ops = sorted(([k, v / n_planes] for k, v in per_op.items()),
                     key=lambda kv: -kv[1])[:10]

    # idle gaps of the first chip, named by the innermost open span
    first = busy[sorted(busy)[0]] if busy else []
    gaps, t = [], lo
    for s, e in first:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    spans = [h for h in events["host"] if h[0] in SPANS]
    edges = sorted({t for h in spans for t in (h[1], h[1] + h[2])})
    idle: Dict[str, float] = {}
    for s, e in gaps:
        # a gap that outlasts a span is split where spans open and close
        cuts = [s] + [t for t in edges if s < t < e] + [e]
        for a, b in zip(cuts, cuts[1:]):
            open_ = [h for h in spans if h[1] <= a < h[1] + h[2]]
            name = min(open_, key=lambda h: h[2])[0] if open_ else "outside"
            idle[name] = idle.get(name, 0.0) + (b - a) * 1e-9
    top_idle = sorted(([k, v] for k, v in idle.items()),
                      key=lambda kv: -kv[1])[:10]

    scope_out = {}
    for scope, anchor in (scopes or {}).items():
        secs, calls = 0.0, 0
        for rows in planes.values():
            for _, s, d, opcode, tags in rows:
                if (lo <= s < hi and opcode not in CONTROL
                        and in_scope(tags, scope)):
                    secs += d * 1e-9
                    calls += in_scope(tags, scope, anchor)
        scope_out[scope] = {"seconds": secs / n_planes,
                            "executions": calls // n_planes}
    return {"busy_s": busy_s, "window_s": window_s,
            "breakdown": {"device_ops": top_ops, "idle_gaps": top_idle},
            "scopes": scope_out}


def traced_answer(tdir: str, fn, scopes: Dict[str, str] = None) -> dict:
    """Run `fn` (one answer) under the profiler and reduce its trace;
    `scopes` as `reduce` takes them (by default the `path_costs`
    kernel's scope, whose one call gathers once)."""
    import jax

    with jax.profiler.trace(tdir):
        with jax.profiler.TraceAnnotation(WINDOW):
            fn()
    paths = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"the profiler wrote no trace under {tdir}")
    return reduce(extract(paths[0]),
                  scopes or {"minplus.path_costs": "gather"})
