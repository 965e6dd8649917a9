"""The measured window: the harness's own loop over ``engine.submit`` and
``engine.step()``, with an open-loop or a backlog feed, recording every
request's and every step's times on ``time.perf_counter`` (the engine's
clock).

Token times. A request's first token is its ``Request.t_first`` (stamped by
the engine after the admission prefill's result reached the host). Every
later token is stamped when the ``step()`` that produced it returned: the
engine decodes every live row once per step, a newly admitted row included,
so a live request gains exactly one token per step. At retirement the
count of stamps must equal the output's length; a request where it does not
is counted as bad.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

import loadgen


@dataclasses.dataclass
class Rec:
    """What the loop saw of one request."""

    planned: loadgen.Planned
    req: object                       # the program's Request
    due: float                        # perf_counter seconds
    submitted: float
    admit: Optional[float] = None     # start of the step that admitted it
    row: Optional[int] = None         # the batch row that decoded it
    tokens: List[float] = dataclasses.field(default_factory=list)
    status: Optional[str] = None
    output: Optional[np.ndarray] = None


@dataclasses.dataclass
class Step:
    start: float
    end: float
    active: int            # rows decoded in this step
    keys: int              # keys attended over those rows (sum of contexts)
    adapters: int          # distinct adapters the step's SGMV calls read
    admitted_tokens: int   # padded prompt tokens prefilled in this step


@dataclasses.dataclass
class Window:
    t0: float
    end: float
    recs: Dict[int, Rec]
    steps: List[Step]
    lateness: List[float]             # submit − due per open-loop request
    bad: int                          # requests whose token count disagreed
    trace_span: Optional[tuple] = None   # (start, stop) perf_counter


class _Tracer:
    """Profiles the end of the window: starts the profiler at the first
    step boundary past ``start`` seconds and stops it once the window has
    closed, so collecting the trace stalls nothing that is measured. The
    traced span ends where the window does."""

    def __init__(self, directory, start):
        self.directory, self.start = directory, start
        self.t_on = self.t_off = None

    def boundary(self, elapsed: float):
        if self.t_on is None and elapsed >= self.start:
            jax.profiler.start_trace(str(self.directory))
            self.t_on = time.perf_counter()

    def close(self, end: float):
        if self.t_on is not None:
            self.t_off = end
            jax.profiler.stop_trace()


def run_window(engine, make_request: Callable, traffic: dict, seed: int,
               vocab: int, seconds: float, trace: Optional[dict] = None
               ) -> Window:
    """Drive ``engine`` for ``seconds`` under the mix's arrivals
    (``open_loop`` or ``backlog``). ``trace`` (``{"dir", "length"}``)
    profiles the window's last ``length`` seconds and marks the loop's
    phases with ``TraceAnnotation`` spans (``generator``, ``step``,
    ``idle``)."""
    span = ((lambda name: jax.profiler.TraceAnnotation(name)) if trace
            else (lambda name: contextlib.nullcontext()))
    tracer = (_Tracer(trace["dir"], seconds - trace["length"]) if trace
              else None)
    seg = engine.seg_tile
    recs: Dict[int, Rec] = {}
    waiting: List[Rec] = []
    running: Dict[int, Rec] = {}
    steps: List[Step] = []
    lateness: List[float] = []
    bad = 0
    open_loop = traffic["arrivals"] == "open_loop"
    if open_loop:
        plan = loadgen.schedule(traffic, seed, vocab, seconds)
    elif traffic["arrivals"] == "backlog":
        feed = loadgen.stream(traffic, seed, vocab)
    else:
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    nxt = 0
    t0 = time.perf_counter()

    def submit(p: loadgen.Planned, due: float, now: float):
        req = make_request(p, due)
        rec = Rec(planned=p, req=req, due=due, submitted=now)
        recs[p.index] = rec
        waiting.append(rec)
        engine.submit(req)

    while True:
        now = time.perf_counter()
        if now - t0 >= seconds:
            break
        with span("generator"):
            if open_loop:
                while nxt < len(plan) and t0 + plan[nxt].due <= now:
                    lateness.append(now - (t0 + plan[nxt].due))
                    submit(plan[nxt], t0 + plan[nxt].due, now)
                    nxt += 1
            else:
                while len(engine.pending) < traffic["backlog"]:
                    submit(next(feed), now, now)
        if not engine.pending and not engine.active_rows:
            wake = (t0 + plan[nxt].due if open_loop and nxt < len(plan)
                    else t0 + seconds)
            with span("idle"):
                time.sleep(max(0.0, min(wake, t0 + seconds)
                               - time.perf_counter()))
            continue
        if tracer is not None:
            tracer.boundary(now - t0)
        t_s = time.perf_counter()
        with span("step"):
            finished = engine.step()
        t_e = time.perf_counter()
        admitted = 0
        new = [r for r in waiting if r.req.t_first is not None]
        # the engine's batch rows, for the check's sample; an engine
        # without ``_rows`` leaves every request's row unknown
        rows = ({r.req.request_id: i
                 for i, r in enumerate(getattr(engine, "_rows", ()))
                 if r is not None} if new else {})
        for rec in new:
            waiting.remove(rec)
            rec.admit = t_s
            rec.row = rows.get(rec.planned.index)
            rec.tokens.append(rec.req.t_first)
            running[rec.planned.index] = rec
            n = len(rec.planned.prompt)
            admitted += max(seg, -(-n // seg) * seg)
        keys = sum(len(r.planned.prompt) + len(r.tokens)
                   for r in running.values())
        adapters = {r.planned.adapter for r in running.values()}
        reads = len(adapters)
        if len(running) < engine.max_rows and not any(
                engine.memory.slot_of(r.req.adapter_id) == 0
                for r in running.values()):
            reads += 1           # idle rows read slot 0's page
        steps.append(Step(t_s, t_e, len(running), keys, reads, admitted))
        for rec in running.values():
            rec.tokens.append(t_e)
        for req in finished:
            rec = running.pop(req.request_id, None)
            if rec is None:          # ended without a row (not admitted)
                rec = recs[req.request_id]
                waiting.remove(rec)
            rec.status = req.status.name
            rec.output = np.asarray(req.output)
            if rec.status == "DONE" and len(rec.output) != len(rec.tokens):
                bad += 1
    end = time.perf_counter()
    if tracer is not None:
        tracer.close(end)
    return Window(t0=t0, end=end, recs=recs, steps=steps, lateness=lateness,
                  bad=bad, trace_span=(tracer.t_on, tracer.t_off)
                  if tracer is not None and tracer.t_on else None)
