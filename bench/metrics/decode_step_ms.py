"""Device time of one decode-step program, mean over the profiled runs."""

import programs


def read(ctx):
    runs = programs.decode_runs(ctx)
    if not runs:
        return None
    return sum(d for _, d in runs) / len(runs) / 1e6
