"""Roofline share of the fused SGMV kernel at decode (``tile_t = 1``), in
percent: the least time the chip could take for the kernel calls of the
traced decode steps (``counts.sgmv_decode_step``: the larger of FLOPs over
peak FLOP/s and bytes over HBM bandwidth) over the kernel's device time in
the decode programs. The traced steps' calls are memory-bound at these
shapes; the stderr line names the bound."""

import sys

import counts
import programs
import devtrace


def read(ctx):
    runs = programs.decode_runs(ctx)
    steps = programs.traced_steps(ctx)
    if ctx.peak is None or not runs or not steps:
        return None
    n_ops, kernel_s = devtrace.op_seconds(
        ctx.events, programs.is_sgmv, runs)
    if not n_ops:
        return None
    recipe = ctx.traffic["fleet"]["recipe"]
    least, bound = 0.0, set()
    for s in steps:
        f, b = counts.sgmv_decode_step(ctx.mc, ctx.max_rows, s.adapters,
                                       recipe["bits_high"],
                                       recipe["group_size"])
        t, which = counts.roofline_seconds(f, b, ctx.peak)
        least += t
        bound.add(which)
    least *= len(runs) / len(steps)
    print(f"[bench] sgmv_decode_roofline: {n_ops} kernel runs, "
          f"{kernel_s:.6f}s; bound: {'/'.join(sorted(bound))}",
          file=sys.stderr)
    return 100.0 * least / kernel_s
