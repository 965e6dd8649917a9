"""Model FLOPs of the profiled decode steps over what the chip's bf16 peak
could do in the time they spanned, in percent: mean FLOPs per traced step
(``counts.decode_step_flops`` over the rows it decoded) over the mean
interval between decode-program starts times the peak."""

import counts
import programs


def read(ctx):
    runs = sorted(programs.decode_runs(ctx))
    steps = programs.traced_steps(ctx)
    if ctx.peak is None or len(runs) < 2 or not steps:
        return None
    interval = (runs[-1][0] - runs[0][0]) / (len(runs) - 1) / 1e9
    flops = sum(counts.decode_step_flops(ctx.mc, s.active, s.keys)
                for s in steps) / len(steps)
    return 100.0 * flops / (interval * ctx.peak["bf16_flops_per_s"])
