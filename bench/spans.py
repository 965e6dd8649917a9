"""The program's own spans in a profiler trace, and the device's idle time
put down to them.

The serving program opens a ``jax.profiler.TraceAnnotation`` at each layer
boundary of its step: ``engine.step`` (a step annotation, with the counts
``step_num``, ``rows``, ``admitted`` and ``pending``), the spans nested in
it (``engine.sweep``, ``engine.admit``, ``engine.prefill``,
``engine.decode``, ...) and the adapter memory's ``memory.acquire``,
``memory.swap_in`` and ``memory.prefetch``. ``load`` reads them, with their
counts, from the host plane of the newest ``*.xplane.pb`` under a
directory. ``split`` puts each idle gap of the device down to the innermost
span running in it, by overlap: a program span where one runs, else the
benchmark loop's own span (``step`` is then the loop's step outside the
program's spans, ``generator``, ``idle``), else ``none``.

A program that writes no such span gives an empty list, and every reader
here then returns None: the metrics that read these spans are silent on
it, and nothing raises.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import sys
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import devtrace

PREFIXES = ("engine.", "memory.")
TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "out", "trace")

# (name, start_ns, duration_ns, counts)
Span = Tuple[str, float, float, dict]
Interval = Tuple[float, float]


def load(directory: str) -> List[Span]:
    """The program's spans on the host planes of the newest trace under
    ``directory``, by start; empty where there is no trace or no span."""
    files = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        return []
    from jax.profiler import ProfileData

    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    out.append((ev.name, ev.start_ns, ev.duration_ns,
                                dict(ev.stats)))
    out.sort(key=lambda s: (s[1], -s[2]))
    return out


def busy(events: Dict[str, list]) -> List[Interval]:
    """Merged intervals in which an operation ran on the first device plane
    (programs' own intervals where it records no operations), as
    ``devtrace.reduce`` takes them."""
    dev = events["device"]
    if not dev:
        return []
    plane = min(e[0] for e in dev)
    ops = [(e[3], e[3] + e[4]) for e in dev
           if e[0] == plane and e[1] == devtrace.OPS_LINE and e[4] > 0]
    if not ops:
        ops = [(e[3], e[3] + e[4]) for e in dev if e[0] == plane and e[4] > 0]
    return devtrace.union(ops)


def gaps(merged: Sequence[Interval]) -> List[Interval]:
    """The idle intervals between merged busy intervals."""
    return [(e1, s2) for (_, e1), (s2, _) in zip(merged, merged[1:])]


def innermost(spans: Sequence[Tuple[float, float, str]]
              ) -> List[Tuple[float, float, str]]:
    """Cut the time that nested ``(start, end, name)`` spans cover into
    disjoint ``(start, end, name)`` pieces, each named for the innermost
    span open in it (spans of one thread nest or are disjoint)."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, float, str]] = []
    cursor = 0.0

    def emit(a, b, name):
        if b > a:
            out.append((a, b, name))

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            emit(cursor, top[1], top[2])
            cursor = max(cursor, top[1])
        if stack:
            emit(cursor, s, stack[-1][2])
        cursor = max(cursor, s)
        stack.append((s, e, name))
    while stack:
        top = stack.pop()
        emit(cursor, top[1], top[2])
        cursor = max(cursor, top[1])
    return out


def split(idle: Sequence[Interval], pieces: Sequence[Tuple[float, float, str]]
          ) -> Dict[str, float]:
    """Nanoseconds of the ``idle`` intervals under each name of the disjoint
    sorted ``pieces``, by overlap; what no piece covers is ``none``."""
    out: Dict[str, float] = collections.defaultdict(float)
    for gs, ge in idle:
        covered = 0.0
        k = bisect.bisect_right(pieces, gs, key=lambda p: p[1])
        while k < len(pieces) and pieces[k][0] < ge:
            a, b = max(pieces[k][0], gs), min(pieces[k][1], ge)
            if b > a:
                out[pieces[k][2]] += b - a
                covered += b - a
            k += 1
        if ge - gs > covered:
            out["none"] += ge - gs - covered
    return dict(out)


def idle_within(idle: Sequence[Interval], s: float, e: float) -> float:
    """Nanoseconds of the disjoint sorted ``idle`` intervals inside
    ``[s, e]``."""
    total = 0.0
    k = bisect.bisect_right(idle, s, key=lambda g: g[1])
    while k < len(idle) and idle[k][0] < e:
        total += max(0.0, min(idle[k][1], e) - max(idle[k][0], s))
        k += 1
    return total


def analyse(events: Dict[str, list], spans: Sequence[Span]) -> Optional[dict]:
    """The idle gaps of a trace and their split across the innermost spans,
    or None where the trace holds no device operation or no program span.
    ``window`` is the traced device span (first to last operation): the
    readers count only spans that lie inside it."""
    merged = busy(events)
    if not merged or not spans:
        return None
    idle = gaps(merged)
    loop = [(s, s + d, name) for name, s, d in events["host"]]
    pieces = innermost([(s, s + d, name) for name, s, d, _ in spans] + loop)
    by_name = split(idle, pieces)
    in_step = sum(idle_within(idle, s, e) for s, e, name in loop
                  if name == "step")
    return {"spans": list(spans), "idle": idle, "by_name": by_name,
            "in_step_idle": in_step,
            "window": (merged[0][0], merged[-1][1])}


def mean_idle_ms(rep: Optional[dict], name: str,
                 keep: Callable[[dict], bool] = lambda counts: True
                 ) -> Optional[float]:
    """Device-idle milliseconds inside the spans called ``name`` whose
    counts pass ``keep``, mean per span, over the spans inside the traced
    device window; None where there is none."""
    if rep is None:
        return None
    lo, hi = rep["window"]
    chosen = [(s, s + d) for n, s, d, counts in rep["spans"]
              if n == name and keep(counts) and lo <= s and s + d <= hi]
    if not chosen:
        return None
    total = sum(idle_within(rep["idle"], s, e) for s, e in chosen)
    return total / len(chosen) / 1e6


def report(ctx) -> Optional[dict]:
    """``analyse`` of this run's trace, read once per run and shared by the
    readers (kept on ``ctx``), with one stderr line: the device's idle
    seconds by innermost span, and the share of the idle time inside the
    loop's ``step`` spans that a program span names. None in a run that was
    not traced, or where the program wrote no span."""
    if not hasattr(ctx, "program_spans"):
        ctx.program_spans = None
        if ctx.events is not None:
            t = time.perf_counter()
            spans = load(TRACE_DIR)
            ctx.program_spans = analyse(ctx.events, spans)
            rep = ctx.program_spans
            line = (f"[bench] spans: {len(spans)} program spans read in "
                    f"{time.perf_counter() - t:.1f}s")
            if rep is not None:
                by = sorted(rep["by_name"].items(), key=lambda kv: -kv[1])
                step_self = rep["by_name"].get("step", 0.0)
                named = (1.0 - step_self / rep["in_step_idle"]
                         if rep["in_step_idle"] else 0.0)
                line += ("; idle s by innermost span {"
                         + ", ".join(f"{k}: {v / 1e9:.6f}" for k, v in by)
                         + f"}}; named share of the idle in step "
                         f"{100.0 * named:.2f}%")
            print(line, file=sys.stderr, flush=True)
    return ctx.program_spans
