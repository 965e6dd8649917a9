"""Device-idle milliseconds inside the program's ``engine.admit`` spans
that admitted a group, mean per admission group: selection, swap-ins,
prefill dispatch and read-back, and the cache-row scatter (``spans.py``)."""

import spans


def read(ctx):
    return spans.mean_idle_ms(spans.report(ctx), "engine.admit",
                              lambda counts: counts.get("rows", 0) > 0)
