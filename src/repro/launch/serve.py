"""Multi-LoRA serving driver: register N quantized adapters, run batched
heterogeneous requests through the continuous-batching scheduler (or the
static reference modes), report quality/memory/throughput.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b \
        --adapters 8 --requests 32 --variant 2@0.9
"""

from __future__ import annotations

import argparse
import dataclasses
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import LoRAQuantConfig
from repro.kernels.quant_matmul.kernel import interpret_mode
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serving.engine import AdapterStore, MultiLoRAEngine, Request
from repro.serving.faults import RequestStatus, named_plan
from repro.serving.telemetry import Telemetry


def _fmt_ms(v) -> str:
    return "-" if v is None else f"{v * 1e3:.1f}ms"


def print_latency_summary(telemetry: Telemetry, prefix: str = "[serve]"):
    """Per-terminal-status p50/p95/p99 TTFT and E2E lines from the
    telemetry histograms (one line per status seen)."""
    reg = telemetry.registry
    statuses = sorted({dict(m.labels).get("status", "")
                       for m in reg.series("serving_e2e_seconds")})
    for status in statuses:
        parts = []
        for title, name in (("ttft", "serving_ttft_seconds"),
                            ("e2e", "serving_e2e_seconds")):
            hs = [m for m in reg.series(name)
                  if dict(m.labels).get("status") == status]
            if not hs or not any(h.count for h in hs):
                continue
            h = hs[0]
            parts.append(f"{title} p50={_fmt_ms(h.percentile(50))} "
                         f"p95={_fmt_ms(h.percentile(95))} "
                         f"p99={_fmt_ms(h.percentile(99))} (n={h.count})")
        if parts:
            print(f"{prefix} latency[{status}]: {' | '.join(parts)}")


def parse_variant(s: str) -> LoRAQuantConfig:
    m = re.match(r"^(\d)@(0?\.\d+)$", s)
    if not m:
        raise ValueError(f"variant must look like 2@0.9, got {s!r}")
    return LoRAQuantConfig(bits_high=int(m.group(1)), rho=float(m.group(2)))


def parse_recipe_override(s: str):
    """``id=2@0.9`` → (id, recipe): a per-upload recipe override."""
    if "=" not in s:
        raise ValueError(f"--recipe must look like user_0=4@0.95, got {s!r}")
    adapter_id, variant = s.split("=", 1)
    return adapter_id, parse_variant(variant)


def random_trained_lora(template, key, scale=0.02, spectrum_decay=0.3):
    """Synthesize a 'trained' adapter: rank components with a decaying
    spectrum (what SGD produces on real tasks), not flat iid noise — this is
    the regime where LoRAQuant's variance-based split has signal."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(template)
    keys = jax.random.split(key, len(paths))
    out = []
    for (path, leaf), k in zip(paths, keys):
        arr = jax.random.normal(k, leaf.shape, jnp.float32) * scale
        name = jax.tree_util.keystr(path)
        if "'a'" in name and leaf.ndim >= 2:         # (..., r, in)
            r = leaf.shape[-2]
            decay = jnp.exp(-spectrum_decay * jnp.arange(r))
            arr = arr * decay[..., :, None]
        elif "'b'" in name and leaf.ndim >= 2:       # (..., out, r)
            r = leaf.shape[-1]
            decay = jnp.exp(-spectrum_decay * jnp.arange(r))
            arr = arr * decay[None, :]
        out.append(arr.astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="llama3.2-3b")
    p.add_argument("--preset", default="smoke")
    p.add_argument("--adapters", type=int, default=4)
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--max-new", type=int, default=8)
    p.add_argument("--variant", default="2@0.9",
                   help="default recipe (bits_high@rho) for every upload "
                        "without a --recipe override")
    p.add_argument("--recipe", action="append", default=[],
                   metavar="ID=BITS@RHO",
                   help="per-upload recipe override, repeatable (e.g. "
                        "--recipe user_0=4@0.95 --recipe user_1=3@0.9): the "
                        "named adapter quantizes under its own recipe and "
                        "serves in the same batch as the rest "
                        "(docs/recipes.md)")
    p.add_argument("--target-bits", type=float, default=None,
                   help="fit the DEFAULT recipe to this average-bits budget "
                        "per upload (LoRAQuantConfig.for_budget) instead of "
                        "using --variant; --recipe overrides still win")
    p.add_argument("--mode", default="continuous",
                   choices=("continuous", "packed", "materialize"),
                   help="continuous: step-based scheduler (mid-decode "
                        "admission, per-row positions) straight from packed "
                        "codes; packed: one static heterogeneous batch; "
                        "materialize: per-adapter segment loop over "
                        "dequantized fp trees")
    p.add_argument("--max-rows", type=int, default=8,
                   help="decode batch rows owned by the continuous scheduler")
    p.add_argument("--slots", type=int, default=None,
                   help="HBM slot-pool size of the paged adapter memory "
                        "(continuous mode): at most this many adapters' "
                        "packed pages are device-resident; the rest page in "
                        "from the host tier on demand. Default: unbounded "
                        "(pool grows to every registered adapter)")
    p.add_argument("--hbm-budget", type=float, default=None, metavar="MB",
                   help="alternative to --slots: packed-adapter HBM budget "
                        "in MB; the slot count is derived as "
                        "budget // page_bytes (--slots wins if both given)")
    p.add_argument("--no-quant", action="store_true")
    p.add_argument("--deadline-ms", type=float, default=None,
                   help="per-request total wall-clock deadline; requests "
                        "still running past it retire TIMED_OUT with their "
                        "partial output (docs/robustness.md)")
    p.add_argument("--queue-limit", type=int, default=None,
                   help="bounded pending queue: submits past this depth hit "
                        "backpressure (--queue-policy)")
    p.add_argument("--queue-policy", default="reject",
                   choices=("reject", "shed_oldest"),
                   help="what a full queue does: reject the NEW request, or "
                        "shed the oldest pending one to make room")
    p.add_argument("--inject", default=None,
                   metavar="PLAN",
                   help="named fault plan (none|latency|transient|poison|"
                        "storm) injected into host reads and uploads — the "
                        "chaos harness of docs/robustness.md")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--metrics-out", default=None, metavar="PATH",
                   help="write the final Prometheus-style metrics "
                        "exposition here (docs/observability.md)")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="write a Chrome-trace JSON of request/scheduler "
                        "spans here (open in Perfetto / chrome://tracing)")
    p.add_argument("--events-out", default=None, metavar="PATH",
                   help="write the JSONL lifecycle event log here")
    p.add_argument("--stats-every", type=int, default=0, metavar="N",
                   help="continuous mode: print a one-line stats snapshot "
                        "every N scheduler steps (0 = off)")
    args = p.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, args.preset)
    if args.preset == "smoke":
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))

    qcfg = parse_variant(args.variant)
    if args.no_quant:
        qcfg = dataclasses.replace(qcfg, bits_high=16)
    budget = (int(args.hbm_budget * 1e6)
              if args.hbm_budget is not None else None)
    plan = named_plan(args.inject) if args.inject else None
    store = AdapterStore(qcfg, hbm_budget_bytes=budget, faults=plan)

    rng = jax.random.PRNGKey(args.seed + 1)
    recipes = dict(parse_recipe_override(s) for s in args.recipe)
    upload_ids = {f"user_{i}" for i in range(args.adapters)}
    unknown = sorted(set(recipes) - upload_ids)
    if unknown:
        raise ValueError(f"--recipe overrides for unknown uploads: {unknown} "
                         f"(uploads are user_0..user_{args.adapters - 1})")
    print(f"[serve] registering {args.adapters} adapters "
          f"(default LoRAQuant {qcfg.bits_high}@{qcfg.rho:g}, "
          f"{len(recipes)} per-upload overrides)...")
    t0 = time.perf_counter()
    uploads = {}
    for i in range(args.adapters):
        rng, k = jax.random.split(rng)
        uploads[f"user_{i}"] = random_trained_lora(params["lora"], k)
    if args.target_bits is not None:
        qcfg = LoRAQuantConfig.for_budget(
            next(iter(uploads.values())), args.target_bits,
            ste_steps=qcfg.ste_steps, refine=qcfg.refine)
        store.default_recipe = qcfg
        print(f"[serve] fitted default recipe for {args.target_bits} avg "
              f"bits: {qcfg.variant_name}")
    # one bucketed dispatch per (recipe, leaf shape)
    store.register_many(uploads, recipes=recipes,
                        on_error="skip" if plan else "raise")
    if store.onboard_errors:
        print(f"[serve] rejected uploads: {store.onboard_errors}")
    print(f"[serve] quantized in {time.perf_counter()-t0:.1f}s; "
          f"store stats: {store.stats()}")

    telemetry = Telemetry()
    engine = MultiLoRAEngine(model, params, store, cache_capacity=128,
                             mode=args.mode, max_rows=args.max_rows,
                             hbm_slots=args.slots,
                             queue_limit=args.queue_limit,
                             queue_policy=args.queue_policy,
                             default_deadline_ms=args.deadline_ms,
                             faults=plan, telemetry=telemetry)
    drng = np.random.default_rng(args.seed)
    for rid in range(args.requests):
        engine.submit(Request(
            request_id=rid,
            adapter_id=f"user_{rid % args.adapters}",
            prompt=drng.integers(0, cfg.vocab, size=args.prompt_len).astype(np.int32),
            max_new_tokens=args.max_new,
        ))
    t0 = time.perf_counter()
    if args.mode == "continuous" and args.stats_every > 0:
        done = []
        while engine.pending or engine.active_rows or engine._terminated:
            done.extend(engine.step())
            if engine._step_count % args.stats_every == 0:
                st = engine.stats()
                mem = engine.memory_stats()
                print(f"[serve] step {st['decode_steps']}: "
                      f"active={st['active_rows']}/{args.max_rows} "
                      f"pending={st['pending']} "
                      f"finished={sum(st.get('finished', {}).values())} "
                      f"tokens={st.get('tokens', 0)} "
                      f"mem hits/misses={mem.get('hits', 0)}/"
                      f"{mem.get('misses', 0)}")
    else:
        done = engine.run()
    dt = time.perf_counter() - t0
    ok = [r for r in done if r.status is RequestStatus.DONE]
    total_tokens = sum(len(r.output) for r in ok)
    by_status = {}
    for r in done:
        by_status[r.status.value] = by_status.get(r.status.value, 0) + 1
    print(f"[serve] mode={args.mode}: {len(done)} requests "
          f"({', '.join(f'{k}={v}' for k, v in sorted(by_status.items()))}), "
          f"{total_tokens} tokens in {dt:.2f}s ({total_tokens/dt:.1f} tok/s"
          f"{', interpret' if interpret_mode() else ''}); "
          f"fp-resident LoRA bytes: {store.fp_resident_bytes()}")
    bad = [r for r in done if r.status is not RequestStatus.DONE]
    for r in bad[:8]:
        print(f"[serve]   request {r.request_id} ({r.adapter_id}): "
              f"{r.status.value} — {r.error}")
    if engine.quarantined:
        print(f"[serve] quarantined adapters: {sorted(engine.quarantined)}")
    print_latency_summary(telemetry)
    mem = engine.memory_stats()
    if mem:
        # hit_rate is None until the first acquire — an idle pool must not
        # print as a perfect one
        rate = ("n/a (0 lookups)" if mem["hit_rate"] is None
                else f"{mem['hit_rate']:.2f} ({mem['lookups']} lookups)")
        print(f"[serve] adapter memory: {mem['slots']} slots in "
              f"{mem['pools']:.0f} pool(s) "
              f"({mem['hbm_slot_mb']:.3f} MB HBM) over "
              f"{store.stats()['adapters']:.0f} adapters "
              f"({mem['host_tier_mb']:.3f} MB host tier); "
              f"hit rate {rate}, "
              f"swap-ins {mem['swap_ins']:.0f}, "
              f"evictions {mem['evictions']:.0f}")
        for label, pool in sorted(mem["per_pool"].items()):
            prate = ("n/a" if pool["hit_rate"] is None
                     else f"{pool['hit_rate']:.2f}")
            print(f"[serve]   pool {label}: {pool['resident']}/"
                  f"{pool['capacity']} resident, hit rate {prate}, "
                  f"swap-ins {pool['swap_ins']} "
                  f"({pool['swap_in_bytes'] / 1e6:.3f} MB), "
                  f"evictions {pool['evictions']}")
    per = store.adapter_stats()
    col = " ".join(f"{aid}={st['avg_bits']:.2f}"
                   for aid, st in sorted(per.items()))
    print(f"[serve] per-adapter avg_bits: {col}")
    if ok:
        print(f"[serve] sample output (req {ok[0].request_id}): "
              f"{ok[0].output.tolist()}")
    if args.metrics_out:
        telemetry.write_prometheus(args.metrics_out)
        print(f"[serve] wrote metrics exposition to {args.metrics_out}")
    if args.trace_out:
        telemetry.write_chrome_trace(args.trace_out)
        print(f"[serve] wrote Chrome trace to {args.trace_out} "
              f"(open in Perfetto / chrome://tracing)")
    if args.events_out:
        telemetry.write_jsonl(args.events_out)
        print(f"[serve] wrote {len(telemetry.events)} lifecycle events "
              f"to {args.events_out}")
    return done


if __name__ == "__main__":
    main()
