#!/usr/bin/env python3
"""Find an open-loop cell's knee: run its mix at several fixed rates in one
process (one set-up) and print, per rate, the offered and completed rates,
TTFT and inter-token p95 and the requests still queued at the close.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> \
        --rates 4,6,8,10 [--seeds <more seeds>] [--orders shuffled,fixed]

The knee is the highest rate at which completions keep up with arrivals
and the queue does not grow through the window. The cell's traffic file
then fixes its rate at about four fifths of it."""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(HERE), "src"))

import numpy as np  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seeds", default="",
                   help="more window seeds per rate, after --seed")
    p.add_argument("--orders", default="",
                   help="arrival orders to run (shuffled,fixed), "
                        "overriding the mix's")
    args = p.parse_args(argv)
    import jax

    import serve_loop
    import harness

    rehearsal = os.environ.get("BENCH_REHEARSAL") == "1"
    cell = harness.load_cell(args.workload, rehearsal)
    if not rehearsal and jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 3
    engine, _ = harness.setup(cell, args.seed, harness.compile_setup(jax))

    seeds = [args.seed] + [int(s) for s in args.seeds.split(",") if s]
    orders = [o for o in args.orders.split(",") if o] or [None]
    runs = [(o, float(r), s) for o in orders for r in args.rates.split(",")
            for s in seeds]
    for order, rate, seed in runs:
        traffic = dict(cell.traffic, rate_per_s=rate)
        if order:
            traffic["arrival_order"] = order
        t = time.perf_counter()
        w = serve_loop.run_window(engine, harness.request, traffic,
                                  seed, cell.mc["vocab"], args.seconds)
        recs = [r for r in w.recs.values() if r.due < w.end]
        ttft = [(r.req.t_first or w.end) - r.due for r in recs]
        itl = [b - a for r in recs for a, b in zip(r.tokens, r.tokens[1:])]
        done = sum(r.status == "DONE" for r in recs)
        queued = sum(r.admit is None for r in recs)
        print(json.dumps({
            "rate": rate, "seed": seed,
            "order": traffic.get("arrival_order", "shuffled"),
            "offered": len(recs) / (w.end - w.t0),
            "completed_per_s": done / (w.end - w.t0),
            "ttft_p95_ms": float(np.percentile(ttft, 95)) * 1e3,
            "itl_p95_ms": float(np.percentile(itl, 95)) * 1e3,
            "tokens_per_s": sum(len(r.tokens) for r in recs)
            / (w.end - w.t0),
            "queued_at_close": queued, "requests": len(recs)}), flush=True)
        engine.run()                 # drain before the next rate
        print(f"[sweep] rate {rate} took {time.perf_counter() - t:.1f}s",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
