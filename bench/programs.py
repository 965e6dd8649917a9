"""How the program's work shows in a device trace, and the steps the
profiled sub-window covered. The metric readers share these.

Programs are matched by the XLA module names JAX gives the engine's jitted
calls, as a TPU trace names their runs: ``jit_decode_step(<id>)`` (decode),
``jit__lambda(<id>)`` (the admission prefill and the cache-row scatter),
``jit__page_write(<id>)`` (a swap-in). An operation's scope path is the
``op_name`` of its HLO instruction (``devtrace.load``): the jitted
functions, ``jax.named_scope``s and control flow that made it, as
``jit(decode_step)/jit(decode_step)/while/body/closed_call/dot_general``
for an operation of the decode program's layer loop. The fused SGMV kernel
has no name of its own in the trace: it is the custom call
``closed_call.<n>`` (target ``tpu_custom_call``, 112 a decode step), whose
scope path ends in the ``pallas_call`` of every Pallas kernel. So it is
any Pallas kernel inside the decode program (the only kernel there), or an
operation named after it once it is named.
"""

from __future__ import annotations

import devtrace

DECODE_MODULE = "jit_decode_step"
PREFILL_MODULE = "jit__lambda"
SGMV_OP = "sgmv_fused"
PREFILL_MIN_OPS = 32     # the cache-row scatter runs a handful of ops


def _runs(ctx, match):
    if ctx.trace is None:
        return []
    return [run for name, runs in ctx.trace["modules"].items()
            if match(name) for run in runs]


def decode_runs(ctx) -> list:
    """``(start_ns, duration_ns)`` of every decode-step program run."""
    return _runs(ctx, lambda n: n.startswith(DECODE_MODULE))


def is_sgmv(event) -> bool:
    """Whether a trace event is the fused SGMV kernel (within the decode
    program, where it is the only kernel)."""
    return event[6] or SGMV_OP in event[2]


def scope_seconds(ctx, runs, name: str) -> tuple:
    """``(count, seconds)`` of the device operations inside ``runs`` whose
    scope path has a component equal to ``name``: a ``jax.named_scope``'s
    name, or a jitted function's as the trace writes it
    (``jit(decode_step)``). An operation that runs inside another one that
    matches (the body of a loop under the scope, inside the loop's own
    operation) is counted with it and not again."""
    if ctx.trace is None:
        return 0, 0.0
    ops = devtrace.ops_within(ctx.events,
                              lambda e: name in devtrace.scope(e), runs)
    n, total, end = 0, 0.0, float("-inf")
    for start, dur in sorted(ops, key=lambda o: (o[0], -o[1])):
        if start + dur > end:
            n, total, end = n + 1, total + dur, start + dur
    return n, total / 1e9


def prefill_runs(ctx) -> list:
    """Runs of the admission prefill program. The engine's other jitted
    lambda, the cache-row scatter, runs a handful of operations where a
    prefill runs every layer's, so a lambda run counts as a prefill when
    ``PREFILL_MIN_OPS`` or more operations ran inside it."""
    runs = _runs(ctx, lambda n: n.startswith(PREFILL_MODULE))
    return [r for r in runs if devtrace.op_seconds(
        ctx.events, lambda e: True, [r])[0] >= PREFILL_MIN_OPS]


def traced_steps(ctx) -> list:
    """The loop's steps that began and ended inside the profiled span."""
    span = ctx.window.trace_span
    if span is None:
        return []
    return [s for s in ctx.window.steps
            if s.start >= span[0] and s.end <= span[1]]


def prefill_ms_per_ktok(ctx):
    """Device milliseconds of the admission prefill programs per thousand
    padded prompt tokens the traced steps prefilled."""
    runs = prefill_runs(ctx)
    ktok = sum(s.admitted_tokens for s in traced_steps(ctx)) / 1e3
    if not runs or not ktok:
        return None
    return sum(d for _, d in runs) / 1e6 / ktok
