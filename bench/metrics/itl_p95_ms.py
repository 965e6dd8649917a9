"""95th percentile of every gap between consecutive output tokens of every
request, over the gaps that end in the window."""

import numpy as np


def read(ctx):
    w = ctx.window
    gaps = [b - a for r in w.recs.values()
            for a, b in zip(r.tokens, r.tokens[1:]) if w.t0 <= b <= w.end]
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
