"""95th percentile, over every request due in the window, of the time from
its due time to its first token; a request still unanswered when the
window closes counts at its age then. The admission path's tail as a chat
user sees it: queueing for a row, the swap-in of a missing page and the
prefill, which also lengthen the steps that carry them."""

import numpy as np


def read(ctx):
    w = ctx.window
    xs = [(min(r.req.t_first, w.end) if r.req.t_first is not None else w.end)
          - r.due for r in w.recs.values() if r.due < w.end]
    return float(np.percentile(xs, 95)) * 1e3 if xs else None
