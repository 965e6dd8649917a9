#!/usr/bin/env python3
"""Readings for a cell's ``logit_gap`` limit, in one process, at the cell's
own size and load. For each seed: build the cell, serve its mix for a short
window, and put through the harness's ``correct`` decision (1) the sampled
served tokens against the float32 reference, the program's reading, and
(2) the tokens the float8 control ranks first at the same positions, the
control's reading. On the seeds in ``--fault-seeds`` it then serves a
window with each fault of ``faults.py`` planted under the engine and puts
those served tokens through the same decision.

    python3 bench/calibrate.py --workload <name> --seconds 12 \
        --seeds 101,102,... --fault-seeds 101,102,103

One JSON line per reading. The limit goes between the largest program
reading and the smallest control reading (``PERF.md`` gives both and the
limit)."""

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(HERE), "src"))

import numpy as np  # noqa: E402

import harness  # noqa: E402
import layout  # noqa: E402


def _seqs(window, seed, cell):
    return [(r.planned.adapter, r.planned.prompt, r.output)
            for r in harness._sample(window, seed, cell.traffic["check"])]


def _warm(engine, cell):
    """One short request through the engine, so a program swapped in under
    it compiles before the window opens."""
    from repro.serving.engine import Request

    engine.submit(Request(
        request_id=-1, adapter_id="a0",
        prompt=np.zeros(cell.traffic["prompt_tokens"][0], np.int32),
        max_new_tokens=harness.SETUP_PROMPT_NEW))
    engine.run()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--fault-seeds", default="")
    args = p.parse_args(argv)
    import jax

    import faults
    import serve_loop

    rehearsal = os.environ.get("BENCH_REHEARSAL") == "1"
    cell = harness.load_cell(args.workload, rehearsal)
    if not rehearsal and jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 3
    watch = harness.compile_setup(jax)
    ref = layout.load(harness.CODE / "references" /
                          f"{cell.mc['reference']}.py")
    recipe = cell.traffic["fleet"]["recipe"]
    shape = harness.reference_shape(cell.traffic)
    fault_seeds = {int(s) for s in args.fault_seeds.split(",") if s}

    def report(seed, what, res, bad, t):
        _, correct = harness.decide(res, cell.limits, bad)
        line = {"seed": seed, "run": what, "correct": correct, "bad": bad,
                "seconds": round(time.perf_counter() - t, 1)}
        line.update(res or {})
        print(json.dumps(line), flush=True)

    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        windows = [seed] + ([seed + 1 + i for i in range(len(faults.NAMES))]
                            if seed in fault_seeds else [])
        engine, _ = harness.setup(
            cell, seed, watch,
            harness.paging_order(cell, windows, args.seconds))
        w = serve_loop.run_window(engine, harness.request, cell.traffic,
                                  seed, cell.mc["vocab"], args.seconds)
        seqs, bad = _seqs(w, seed, cell), w.bad
        fault_runs = []
        if seed in fault_seeds:
            for i, name in enumerate(faults.NAMES):
                engine.run()              # drain before the next window
                with faults.planted(engine, name):
                    _warm(engine, cell)       # the faulty program compiles
                    fw = serve_loop.run_window(
                        engine, harness.request, cell.traffic,
                        seed + 1 + i, cell.mc["vocab"], args.seconds)
                fault_runs.append((name, seed + 1 + i,
                                   _seqs(fw, seed + 1 + i, cell), fw.bad))
        del engine, w
        gc.unfreeze()
        gc.collect()
        res = ref.logit_gaps(cell.mc, recipe, seed, seqs, control=True,
                             **shape) if seqs else None
        report(seed, "program", res, bad, t)
        if res is not None:
            report(seed, "control", {"gap": res["control_gap"],
                                     "tokens": res["tokens"]}, 0, t)
        for name, s, fseqs, fbad in fault_runs:
            t = time.perf_counter()
            fres = (ref.logit_gaps(cell.mc, recipe, seed, fseqs, **shape)
                    if fseqs else None)
            report(s, name, fres, fbad, t)
    return 0


if __name__ == "__main__":
    sys.exit(main())
