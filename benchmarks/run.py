"""Benchmark driver — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only table1,fig2] [--json out.json]

Prints ``name,metric=value,...`` CSV lines; ``*.check`` lines assert the
paper's qualitative claims (PASS/FAIL). ``--json`` additionally writes the
parsed metrics + check outcomes to a file, so successive PRs can diff a
perf trajectory. The smoke targets used by CI are:

    PYTHONPATH=src python -m benchmarks.run --only kernels --json BENCH_kernels.json
    PYTHONPATH=src python -m benchmarks.run --only serving --json BENCH_serving.json
"""

from __future__ import annotations

import argparse
import inspect
import json
import sys
import time


def _parse_line(line: str):
    """``suite.name,label,k=v,...`` → (key, {metric: value}) best-effort."""
    parts = line.split(",")
    key = ",".join(parts[:2]) if len(parts) >= 2 else line
    metrics = {}
    for p in parts[2:]:
        if "=" in p:
            k, v = p.split("=", 1)
            try:
                metrics[k] = float(v.rstrip("x%").split("(")[0])
            except ValueError:
                metrics[k] = v
        else:
            metrics[p] = True
    return key, metrics


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None,
                   help="comma-separated subset (table1,table2,fig2,fig3,"
                        "fig4,fig6,kernels,recipes,serving,chaos)")
    p.add_argument("--json", default=None, metavar="PATH",
                   help="write parsed metrics + checks to this JSON file")
    p.add_argument("--metrics-out", default=None, metavar="PATH",
                   help="write the shared telemetry registry as Prometheus "
                        "text exposition after the suites finish")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="write the shared telemetry Chrome-trace JSON "
                        "(load in Perfetto / chrome://tracing)")
    args = p.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import (
        bench_chaos,
        bench_kernels,
        bench_recipes,
        bench_serving,
        fig2_split_strategy,
        fig3_ablation,
        fig4_h_selection,
        fig6_memory,
        table1_quality,
        table2_avgbits,
    )

    suites = {
        "kernels": bench_kernels.run,
        "recipes": bench_recipes.run,
        "serving": bench_serving.run,
        "chaos": bench_chaos.run,
        "table2": table2_avgbits.run,
        "fig6": fig6_memory.run,
        "table1": table1_quality.run,
        "fig2": fig2_split_strategy.run,
        "fig3": fig3_ablation.run,
        "fig4": fig4_h_selection.run,
    }
    wanted = args.only.split(",") if args.only else list(suites)

    # one shared registry across every suite that opts in (accepts a
    # ``telemetry`` kwarg) — its histograms feed the exports below
    from repro.serving.telemetry import Telemetry
    telemetry = Telemetry()

    lines = []

    def report(line: str):
        print(line)
        sys.stdout.flush()
        lines.append(line)

    for name in wanted:
        t0 = time.perf_counter()
        print(f"# --- {name} ---")
        fn = suites[name]
        if "telemetry" in inspect.signature(fn).parameters:
            fn(report, telemetry=telemetry)
        else:
            fn(report)
        print(f"# {name} done in {time.perf_counter() - t0:.1f}s")

    fails = [l for l in lines if l.endswith("FAIL")]
    passes = sum(1 for l in lines if l.endswith("PASS"))
    print(f"# checks: {passes} pass, {len(fails)} fail")
    for f in fails:
        print(f"# FAILED: {f}")

    if args.json:
        payload = {
            "suites": wanted,
            "metrics": {},
            "checks": {},
            "raw_lines": lines,
            "pass": passes,
            "fail": len(fails),
        }
        for line in lines:
            key, metrics = _parse_line(line)
            if ".check" in key.split(",")[0]:
                payload["checks"][",".join(line.split(",")[:2])] = (
                    line.endswith("PASS"))
            else:
                payload["metrics"][key] = metrics
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"# wrote {args.json}")
    if args.metrics_out:
        telemetry.write_prometheus(args.metrics_out)
        print(f"# wrote {args.metrics_out}")
    if args.trace_out:
        telemetry.write_chrome_trace(args.trace_out)
        print(f"# wrote {args.trace_out}")
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
