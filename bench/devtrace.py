"""From a profiler trace to numbers.

``load`` reads the newest ``*.xplane.pb`` under a directory with
``jax.profiler.ProfileData`` and keeps, as plain tuples, the device planes'
events and the host spans the loop wrote (``serve_loop.run_window``'s
``TraceAnnotation``s). ``reduce`` turns those into the device's busy time
(the union of the intervals in which an operation ran, per chip, averaged
over chips), the time per XLA program and per operation, and the idle
gaps between operations attributed to what the host was doing then. Both
work on plain data, so a small recorded trace checks them
(``tests/data/trace_events.json.gz``).
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

HOST_SPANS = ("generator", "step", "idle")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

# marks of a Pallas kernel in an operation's HLO instruction or stats: the
# custom call's target, or the ``pallas_call`` at the end of its op_name
KERNEL_MARKS = ("tpu_custom_call", "pallas_call")

# (plane, line, name, start_ns, duration_ns, hlo_module, is_pallas_kernel)
Event = Tuple[str, str, str, float, float, str, bool]


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


def load(directory: str) -> Dict[str, list]:
    """Device events and host spans of the newest trace under ``directory``:
    ``{"device": [Event...], "host": [(name, start_ns, duration_ns)...]}``.
    Operation names are shortened (``short_name``) once the kernel mark has
    been read from them."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {directory}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    device, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for ev in line.events:
                    stats = dict(ev.stats)
                    device.append((plane.name, line.name,
                                   short_name(ev.name), ev.start_ns,
                                   ev.duration_ns,
                                   str(stats.get("hlo_module", "")),
                                   is_kernel(ev.name, stats)))
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
    for plane, line, name, start, dur, _, _ in dev:
        if plane == planes[0] and line == MODULES_LINE:
            modules[name].append((start, dur))
    runs = sorted((s, s + d, name.split("(")[0])
                  for name, rs in modules.items() for s, d in rs)
    run_starts = [r[0] for r in runs]
    ops_by_name: Dict[str, float] = collections.defaultdict(float)
    for plane, line, name, start, dur, module, _ in dev:
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


def op_seconds(events: Dict[str, list], match, within: Sequence[tuple]) -> tuple:
    """``(count, seconds)`` of operations on the first device plane that
    satisfy ``match`` (called with the event), counting only those inside
    one of the ``within`` ``(start_ns, duration_ns)`` intervals (a
    program's runs)."""
    dev = events["device"]
    if not dev:
        return 0, 0.0
    plane = sorted({e[0] for e in dev})[0]
    spans = sorted(within)
    starts = [s for s, _ in spans]
    n, total = 0, 0.0
    for ev in dev:
        p, line, _, start, dur = ev[:5]
        if p != plane or line != OPS_LINE or not match(ev):
            continue
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start + dur <= spans[i][0] + spans[i][1] + 1:
            n += 1
            total += dur
    return n, total / 1e9
