"""95th percentile, over requests due in the window, of the time from due
to the start of the step that admitted them (still queued at the close:
their age then)."""

import numpy as np


def read(ctx):
    w = ctx.window
    xs = [(min(r.admit, w.end) if r.admit is not None else w.end) - r.due
          for r in w.recs.values() if r.due < w.end]
    return float(np.percentile(xs, 95)) * 1e3 if xs else None
