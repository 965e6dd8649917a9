#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout holding ``BENCHMARK.json``, ``bench/`` and the
program under ``src/``. Set-up (weights, fleet, host pages, warm-up of every
shape the cell's traffic uses) is timed as ``setup_s`` from the start of
this process; then the window runs for ``--seconds``; then the served
tokens are checked against the float32 reference. ``--trace 1`` profiles a
sub-window and reports the cell's per-layer metrics instead of its
end-to-end ones. The last line of standard output is one JSON object; a
run that finds no TPU, fewer chips than the cell needs, or no program
exits non-zero and prints none.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
# the TPU runtime's logs stay inside the checkout, not at a fixed /tmp path
if "TPU_LOG_DIR" not in os.environ:
    os.environ["TPU_LOG_DIR"] = os.path.join(HERE, "out", "tpu_logs")
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(HERE), "src"))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if args.seed < 0:
        p.error("--seed must be a non-negative whole number")
    import harness

    try:
        import repro.serving.engine  # noqa: F401
    except ImportError as e:
        print(f"bench: the program is not here ({e})", file=sys.stderr)
        return 2
    try:
        return harness.run(args, T_START)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3


if __name__ == "__main__":
    sys.exit(main())
