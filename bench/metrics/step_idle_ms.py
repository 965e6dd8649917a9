"""Device-idle milliseconds inside the program's ``engine.step`` spans of
the steps that admitted nothing, mean per such step: the host's own work
around a plain decode step (``spans.py``)."""

import spans


def read(ctx):
    return spans.mean_idle_ms(spans.report(ctx), "engine.step",
                              lambda counts: counts.get("admitted") == 0)
