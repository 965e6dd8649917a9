"""Share of the program's ``engine.decode`` spans in the traced window that
were dispatched while the previous step's tokens were still on the device
(count ``overlapped`` 1), in percent (``spans.py``). Silent on a program
whose decode spans carry no such count."""

import spans


def read(ctx):
    rep = spans.report(ctx)
    if rep is None:
        return None
    lo, hi = rep["window"]
    flags = [counts["overlapped"] for name, s, d, counts in rep["spans"]
             if name == "engine.decode" and "overlapped" in counts
             and lo <= s and s + d <= hi]
    if not flags:
        return None
    return 100.0 * sum(int(f) for f in flags) / len(flags)
