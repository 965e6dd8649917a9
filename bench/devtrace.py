"""From a profiler trace to numbers.

``load`` reads the newest ``*.xplane.pb`` under a directory with
``jax.profiler.ProfileData`` and keeps, as plain tuples, the device planes'
events, each with its scope path, and the host spans the loop wrote
(``serve_loop.run_window``'s ``TraceAnnotation``s). ``reduce`` turns those
into the device's busy time (the union of the intervals in which an
operation ran, per chip, averaged over chips), the time per XLA program and
per operation, and the idle gaps between operations attributed to what the
host was doing then. Both work on plain data, so a small recorded trace
checks them (``tests/data/trace_events.json.gz``).

A TPU trace's operations carry no stat with their ``op_name``: the trace
keeps it in the HLO of each program, a ``Hlo Proto`` stat on its
``/host:metadata`` plane, which ``ProfileData`` does not show. ``op_names``
reads it from the file with a small protobuf reader: the ``op_name`` of
each instruction, the path of ``jax.named_scope``s and jitted functions
that made it (``jit(decode_step)/jit(decode_step)/while/body/...``).
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
from typing import Dict, Iterable, Iterator, List, Sequence, Tuple

HOST_SPANS = ("generator", "step", "idle")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"

# marks of a Pallas kernel in an operation's HLO instruction or stats: the
# custom call's target, or the ``pallas_call`` at the end of its op_name
KERNEL_MARKS = ("tpu_custom_call", "pallas_call")

# (plane, line, name, start_ns, duration_ns, hlo_module, is_pallas_kernel,
#  scope); traces recorded before the scope was kept have no last field
Event = Tuple[str, str, str, float, float, str, bool, str]


def short_name(name: str) -> str:
    """An operation's name without its operands: a TPU trace names each
    operation by its whole HLO instruction (``%closed_call.74 =
    f32[16,1,2048]{...} custom-call(...), ...``); this keeps the
    instruction's name and the type of its result."""
    head, _, rest = name.partition(" = ")
    return f"{head} {rest.split('{')[0].split(' ')[0]}".strip()[:80]


def is_kernel(name: str, stats: dict) -> bool:
    """Whether an operation is a Pallas kernel: its HLO instruction (the
    name a TPU trace gives it) or one of its stats names a mark."""
    return any(m in name for m in KERNEL_MARKS) or any(
        isinstance(v, str) and m in v for v in stats.values()
        for m in KERNEL_MARKS)


def scope(event) -> List[str]:
    """The components of an event's scope path (``jit(decode_step)``,
    ``while``, a named scope, ...), or none where it has no scope."""
    path = event[7] if len(event) > 7 else ""
    return path.split("/") if path else []


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of a protobuf message's fields, in order: an
    integer, or a ``memoryview`` of a length-delimited or fixed-width
    value."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _field(buf, number: int):
    return next((v for f, v in _fields(buf) if f == number), None)


def _text(value) -> str:
    return bytes(value).decode() if value is not None else ""


def _instruction_op_names(hlo_proto, names: Dict[str, str]):
    # HloProto.hlo_module (1) -> HloModuleProto.computations (3) ->
    # HloComputationProto.instructions (2) -> HloInstructionProto: name (1),
    # metadata (7) -> OpMetadata.op_name (2)
    module = _field(hlo_proto, 1)
    for f, comp in _fields(module if module is not None else b""):
        if f != 3:
            continue
        for g, inst in _fields(comp):
            if g != 2:
                continue
            name = meta = None
            for h, v in _fields(inst):
                if h == 1:
                    name = v
                elif h == 7:
                    meta = v
            if name is not None:
                names[_text(name)] = _text(
                    _field(meta, 2) if meta is not None else None)


def op_names(path: str, programs=None) -> Dict[str, Dict[str, str]]:
    """Per program, as the XLA Modules line names its runs
    (``jit_decode_step(<id>)``), each HLO instruction's ``op_name``, read
    from the ``Hlo Proto`` stats of the trace's ``/host:metadata`` plane
    (``XSpace.planes`` 1; ``XPlane``: ``name`` 2, ``event_metadata`` 4,
    ``stat_metadata`` 5; ``XEventMetadata``: ``name`` 2, ``stats`` 5;
    ``XStat``: ``metadata_id`` 1, ``bytes_value`` 6). Only the programs in
    ``programs`` are read, every one where it is None: the plane holds
    every program the process had loaded, most of which did not run in the
    trace. A trace without that plane gives none."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(buf):
        if f != 1 or _text(_field(plane, 2)) != METADATA_PLANE:
            continue
        hlo_ids = set()
        for g, entry in _fields(plane):      # map entries: key 1, value 2
            if g == 5:
                stat = _field(entry, 2)
                if stat is not None and _text(_field(stat, 2)) == HLO_STAT:
                    hlo_ids.add(_field(stat, 1) or 0)
        for g, entry in _fields(plane):
            meta = _field(entry, 2) if g == 4 else None
            if meta is None:
                continue
            program = _text(_field(meta, 2))
            if programs is not None and program not in programs:
                continue
            for h, stat in _fields(meta):
                if h == 5 and (_field(stat, 1) or 0) in hlo_ids:
                    _instruction_op_names(_field(stat, 6) or b"",
                                          out.setdefault(program, {}))
    return out


def load(directory: str) -> Dict[str, list]:
    """Device events and host spans of the newest trace under ``directory``:
    ``{"device": [Event...], "host": [(name, start_ns, duration_ns)...]}``.
    Operation names are shortened (``short_name``) once the kernel mark has
    been read from them. An operation's scope is the ``op_name`` of its
    instruction in the program whose run holds it (``op_names``), or empty
    where the trace has none."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {directory}")
    path = max(files, key=os.path.getmtime)
    data = ProfileData.from_file(path)
    kept = {plane.name: [line for line in plane.lines
                         if line.name in (OPS_LINE, MODULES_LINE)]
            for plane in data.planes
            if plane.name.startswith("/device:") and "CPU" not in plane.name}
    names = op_names(path, {ev.name for lines in kept.values()
                            for line in lines if line.name == MODULES_LINE
                            for ev in line.events})
    device, host = [], []
    for plane in data.planes:
        if plane.name in kept:
            lines = kept[plane.name]
            runs = sorted(((ev.start_ns, ev.start_ns + ev.duration_ns,
                            names.get(ev.name, {}))
                           for line in lines if line.name == MODULES_LINE
                           for ev in line.events), key=lambda r: r[0])
            starts = [r[0] for r in runs]
            for line in lines:
                for ev in line.events:
                    stats = dict(ev.stats)
                    scope_path = ""
                    if line.name == OPS_LINE:
                        i = bisect.bisect_right(starts, ev.start_ns) - 1
                        if i >= 0 and ev.start_ns < runs[i][1]:
                            scope_path = runs[i][2].get(
                                ev.name.partition(" = ")[0].lstrip("%"), "")
                    device.append((plane.name, line.name,
                                   short_name(ev.name), ev.start_ns,
                                   ev.duration_ns,
                                   str(stats.get("hlo_module", "")),
                                   is_kernel(ev.name, stats), scope_path))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append((ev.name, ev.start_ns, ev.duration_ns))
    return {"device": device, "host": host}


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _span_at(host: Sequence[tuple], starts: Sequence[float], t: float) -> str:
    """The host span (sorted by start; the loop's spans do not nest)
    running at time ``t``, or ``none``."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t <= host[i][1] + host[i][2]:
        return host[i][0]
    return "none"


def reduce(events: Dict[str, list], top: int = 10) -> dict:
    """Busy time, per-program and per-operation times, and idle gaps.

    Busy time is the union of operation intervals on each device plane
    (programs' own intervals where a plane records no operations), averaged
    over planes. Gaps are the spaces between merged busy intervals on the
    first plane, each attributed to the host span running at its middle;
    ``idle_by_span`` sums them per span name."""
    dev = events["device"]
    planes = sorted({e[0] for e in dev})
    busy_ns = []
    merged0: List[Tuple[float, float]] = []
    for i, plane in enumerate(planes):
        # an operation of no duration (a buffer allocation) keeps nothing
        # busy and splits no idle gap
        ops = [(e[3], e[3] + e[4]) for e in dev
               if e[0] == plane and e[1] == OPS_LINE and e[4] > 0]
        if not ops:
            ops = [(e[3], e[3] + e[4]) for e in dev
                   if e[0] == plane and e[4] > 0]
        merged = union(ops)
        busy_ns.append(sum(e - s for s, e in merged))
        if i == 0:
            merged0 = merged
    modules: Dict[str, list] = collections.defaultdict(list)
    for plane, line, name, start, dur, *_ in dev:
        if plane == planes[0] and line == MODULES_LINE:
            modules[name].append((start, dur))
    runs = sorted((s, s + d, name.split("(")[0])
                  for name, rs in modules.items() for s, d in rs)
    run_starts = [r[0] for r in runs]
    ops_by_name: Dict[str, float] = collections.defaultdict(float)
    for plane, line, name, start, dur, module, *_ in dev:
        if plane != planes[0] or line != OPS_LINE:
            continue
        if not module:          # the program whose run holds the operation
            i = bisect.bisect_right(run_starts, start) - 1
            if i >= 0 and start < runs[i][1]:
                module = runs[i][2]
        ops_by_name[f"{module}/{name}" if module else name] += dur
    gaps = [(s2 - e1, (e1 + s2) / 2) for (_, e1), (s2, _)
            in zip(merged0, merged0[1:])]
    gaps.sort(reverse=True)
    host = sorted(events["host"], key=lambda h: h[1])
    starts = [h[1] for h in host]
    idle_by_span: Dict[str, list] = collections.defaultdict(lambda: [0, 0.0])
    for length, mid in gaps:
        entry = idle_by_span[_span_at(host, starts, mid)]
        entry[0] += 1
        entry[1] += length
    top_ops = sorted(ops_by_name.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": (sum(busy_ns) / len(busy_ns) / 1e9) if busy_ns else 0.0,
        "span_s": ((merged0[-1][1] - merged0[0][0]) / 1e9) if merged0 else 0.0,
        "modules": {k: sorted(v) for k, v in modules.items()},
        "device_ops": [[k, v / 1e9] for k, v in top_ops],
        "gaps": [[_span_at(host, starts, mid), length / 1e9]
                 for length, mid in gaps[:top]],
        "idle_by_span": {k: (n, s / 1e9) for k, (n, s) in idle_by_span.items()},
    }


def ops_within(events: Dict[str, list], match,
               within: Sequence[tuple]) -> List[Tuple[float, float]]:
    """``(start_ns, duration_ns)`` of the operations on the first device
    plane that satisfy ``match`` (called with the event), counting only
    those inside one of the ``within`` ``(start_ns, duration_ns)``
    intervals (a program's runs)."""
    dev = events["device"]
    if not dev:
        return []
    plane = sorted({e[0] for e in dev})[0]
    spans = sorted(within)
    starts = [s for s, _ in spans]
    out = []
    for ev in dev:
        p, line, _, start, dur = ev[:5]
        if p != plane or line != OPS_LINE or not match(ev):
            continue
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start + dur <= spans[i][0] + spans[i][1] + 1:
            out.append((start, dur))
    return out


def op_seconds(events: Dict[str, list], match, within: Sequence[tuple]) -> tuple:
    """``(count, seconds)`` of the operations ``ops_within`` finds."""
    ops = ops_within(events, match, within)
    return len(ops), sum(d for _, d in ops) / 1e9
