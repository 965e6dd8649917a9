"""Device-idle milliseconds inside the adapter memory's ``memory.swap_in``
spans, mean per swap-in, at admission and in prefetch: the part of a page's
host read and copy that the device does not hide (``spans.py``)."""

import spans


def read(ctx):
    return spans.mean_idle_ms(spans.report(ctx), "memory.swap_in")
