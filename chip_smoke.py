#!/usr/bin/env python3
"""Smoke run of the multi-LoRA serving path on one TPU chip.

    python chip_smoke.py                 # llama3.2-3b at full width, TPU only
    python chip_smoke.py --preset smoke  # reduced widths, any backend (the
                                         # Pallas interpreter on a CPU)

Drives the main path once, in this one process, through the user-facing
entry points: random ``llama3.2-3b`` base weights from ``--seed`` (28
layers, d_model 3072, GQA 24/8, d_ff 8192, vocab 128256, bf16) → four
synthetic trained-looking adapters registered into an ``AdapterStore`` and
LoRAQuant-quantized (three at ``2@0.9``, one at ``4@0.95``, so a
mixed-recipe bucket runs) → a ``MultiLoRAEngine`` in continuous mode with
8 batch rows and 3 HBM adapter slots (fewer than the adapters, so pages
swap in and evict) → 8 requests of 32 prompt tokens and 8 new tokens each,
prefilled and decoded through the fused SGMV Pallas kernel.

Checks, each of which makes the exit code non-zero when it fails:
  * the default run finds a TPU (it never falls back to the CPU);
  * every request ends DONE with its full token count;
  * the compiled decode step holds a native Pallas kernel
    (``tpu_custom_call``) — on a TPU only, the interpreter has none;
  * at one full-width layer, the kernel's output for every LoRA path of
    both recipe buckets matches the dequantized reference math of
    ``repro.kernels.quant_matmul.ref`` within ``REL_ERR_BOUND`` (relative
    Frobenius error).
It also prints the token agreement of continuous serving against the
``materialize`` reference (dequantized fp adapters), compile seconds, the
serve phase's wall seconds (a smoke timing, not a benchmark), the device's
``peak_bytes_in_use`` and the adapter memory's hit and swap-in counts. The
last line of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "llama3.2-3b"
ADAPTERS = 4
DEFAULT_RECIPE = "2@0.9"
OVERRIDES = {"user_3": "4@0.95"}
REQUESTS = 8
PROMPT_LEN = 32
MAX_NEW = 8
MAX_ROWS = 8
HBM_SLOTS = 3
CACHE_CAPACITY = 128
REL_ERR_BOUND = 1e-2     # kernel vs dequantized reference, relative Frobenius


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileClock:
    """Sums JAX's backend-compile durations while installed."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


def requests(vocab: int, seed: int):
    import numpy as np

    from repro.serving.engine import Request

    rng = np.random.default_rng(seed)
    return [Request(request_id=i, adapter_id=f"user_{i % ADAPTERS}",
                    prompt=rng.integers(0, vocab, PROMPT_LEN).astype(np.int32),
                    max_new_tokens=MAX_NEW)
            for i in range(REQUESTS)]


def kernel_check(store, aids, tile_t: int, seed: int) -> float:
    """Worst relative error of ``sgmv_apply_packed`` against the
    dequantized reference over every LoRA path at layer 0, per bucket of
    adapters that share a packed layout."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import sgmv_apply_packed, stack_packed_adapters
    from repro.kernels.quant_matmul.ref import ref_lora_apply

    worst = 0.0
    rng = np.random.default_rng(seed)
    buckets = {}
    for aid in aids:
        buckets.setdefault(store.signature_of(aid), []).append(aid)
    for members in buckets.values():
        entries = [store.packed_entries(a) for a in members]
        for path in entries[0]:
            pb = stack_packed_adapters([e[path] for e in entries],
                                       tile_t=tile_t)
            pb = jax.tree_util.tree_map(lambda a: a[0], pb)   # layer 0
            rows = 2 * tile_t * len(members)
            seg = np.repeat(np.arange(rows // tile_t) % len(members), tile_t)
            x = jnp.asarray(rng.normal(size=(rows, pb.k)), jnp.bfloat16)
            got = sgmv_apply_packed(
                x, dataclasses.replace(pb, seg=jnp.asarray(seg, jnp.int32)))
            want = np.zeros(got.shape, np.float64)
            with jax.default_matmul_precision("highest"):
                for i, aid in enumerate(members):
                    q = store.quantized[aid].entries[path][0]
                    rsel = np.nonzero(seg == i)[0]
                    y = ref_lora_apply(x[rsel], q.a_high, q.b_high)
                    if q.a_low is not None:
                        y = y + ref_lora_apply(x[rsel], q.a_low, q.b_low)
                    want[rsel] = np.asarray(y, np.float64)
            got = np.asarray(got.astype(jnp.float32), np.float64)
            err = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)
            log(f"kernel vs ref: {path} K={pb.k} M={pb.m} tile_t={tile_t} "
                f"adapters={len(members)} bits_hi={pb.bits_hi} "
                f"rel_err={err:.3e}")
            worst = max(worst, float(err))
    return worst


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--preset", default="full", choices=("full", "smoke"),
                   help="full: published llama3.2-3b widths, TPU required; "
                        "smoke: reduced widths on any backend (rehearsal)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    try:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from repro.configs import get_config
        from repro.kernels import retile_packed
        from repro.launch.compile_cache import enable_compile_cache
        from repro.launch.serve import (
            parse_recipe_override,
            parse_variant,
            random_trained_lora,
        )
        from repro.models import build_model
        from repro.serving.engine import AdapterStore, MultiLoRAEngine
        from repro.serving.faults import RequestStatus
    except ImportError as e:
        print(f"chip_smoke: cannot import the program ({e}); run this "
              f"script from the root of its repository", file=sys.stderr)
        return 2

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device: {device}")
    if args.preset == "full" and dev.platform != "tpu":
        print(f"chip_smoke: the full preset needs a TPU, JAX found "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock(jax)
    failures = []

    # ---- base model: random weights from the seed ----
    t0 = time.perf_counter()
    cfg = get_config(ARCH, args.preset)
    if args.preset == "smoke":
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    model = build_model(cfg)
    params = jax.block_until_ready(
        jax.jit(model.init)(jax.random.PRNGKey(args.seed)))
    n_base = sum(a.size for a in jax.tree_util.tree_leaves(params["base"]))
    log(f"{ARCH} preset={args.preset}: {cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, d_ff {cfg.d_ff}, vocab {cfg.vocab}, "
        f"{n_base / 1e9:.3f} B base params; "
        f"init {time.perf_counter() - t0:.1f}s")

    # ---- register + LoRAQuant-quantize the adapters ----
    t0 = time.perf_counter()
    store = AdapterStore(parse_variant(DEFAULT_RECIPE))
    key = jax.random.PRNGKey(args.seed + 1)
    uploads = {}
    for i in range(ADAPTERS):
        key, k = jax.random.split(key)
        uploads[f"user_{i}"] = random_trained_lora(params["lora"], k)
    recipes = dict(parse_recipe_override(f"{a}={v}")
                   for a, v in OVERRIDES.items())
    store.register_many(uploads, recipes=recipes)
    del uploads
    per = store.adapter_stats()
    log(f"registered {ADAPTERS} adapters in {time.perf_counter() - t0:.1f}s: "
        + " ".join(f"{a}={s['recipe']}/{s['avg_bits']:.3f}b"
                   for a, s in sorted(per.items())))

    # ---- continuous serving from packed codes through paged HBM slots ----
    engine = MultiLoRAEngine(model, params, store,
                             cache_capacity=CACHE_CAPACITY, mode="continuous",
                             max_rows=MAX_ROWS, hbm_slots=HBM_SLOTS)
    for r in requests(cfg.vocab, args.seed):
        engine.submit(r)
    c0 = clock.seconds
    t0 = time.perf_counter()
    done = engine.run()
    serve_s = time.perf_counter() - t0
    serve_compile_s = clock.seconds - c0
    n_tok = sum(len(r.output) for r in done)
    log(f"serve phase (smoke timing, not a benchmark): {len(done)} requests, "
        f"{n_tok} tokens in {serve_s:.2f}s wall, of which "
        f"{serve_compile_s:.2f}s compiling")
    bad = [r for r in done if r.status is not RequestStatus.DONE
           or len(r.output) != MAX_NEW]
    if len(done) != REQUESTS or bad:
        failures.append(f"{len(bad)} of {len(done)} requests not DONE with "
                        f"{MAX_NEW} tokens (of {REQUESTS} submitted): "
                        + "; ".join(f"{r.request_id}:{r.status.value}:"
                                    f"{len(r.output)}:{r.error}"
                                    for r in bad[:4]))
    mem = engine.memory_stats()
    log(f"adapter memory: {mem['slots']} slots in {mem['pools']:.0f} pool(s) "
        f"over {ADAPTERS} adapters; hits {mem['hits']:.0f}, misses "
        f"{mem['misses']:.0f}, swap-ins {mem['swap_ins']:.0f}, evictions "
        f"{mem['evictions']:.0f}")
    if mem["swap_ins"] <= HBM_SLOTS or mem["evictions"] < 1:
        failures.append("the HBM slot pool never evicted and swapped in")

    # ---- the decode step as compiled: native kernel, not the interpreter ----
    dec = {"base": params["base"],
           "lora": {"groups": retile_packed(engine.memory.serving_tree(),
                                            1)["groups"],
                    "seg": jnp.zeros((MAX_ROWS,), jnp.int32)}}
    rows = jnp.zeros((MAX_ROWS,), jnp.int32)
    hlo = jax.jit(model.decode_step).lower(
        dec, jnp.zeros((MAX_ROWS, 1), jnp.int32),
        model.init_cache(MAX_ROWS, CACHE_CAPACITY), rows, rows,
    ).compile().as_text()
    n_kernels = hlo.count("tpu_custom_call")
    log(f"compiled decode step: {n_kernels} tpu_custom_call sites")
    if dev.platform == "tpu" and n_kernels == 0:
        failures.append("the compiled decode step holds no native kernel")

    # ---- the kernel against the dequantized reference at full width ----
    aids = sorted(store.quantized)
    worst = max(kernel_check(store, aids, 1, args.seed),
                kernel_check(store, aids, 8, args.seed))
    log(f"kernel vs dequantized reference: worst rel_err {worst:.3e} "
        f"(bound {REL_ERR_BOUND:g})")
    if not worst <= REL_ERR_BOUND:
        failures.append(f"kernel rel_err {worst:.3e} > {REL_ERR_BOUND:g}")

    # ---- token agreement with the dequantized-fp reference path ----
    ref_engine = MultiLoRAEngine(model, params, store,
                                 cache_capacity=CACHE_CAPACITY,
                                 mode="materialize")
    for r in requests(cfg.vocab, args.seed):
        ref_engine.submit(r)
    ref = {r.request_id: r.output for r in ref_engine.run()}
    same = sum(int(np.sum(r.output == ref[r.request_id][:len(r.output)]))
               for r in done if r.request_id in ref
               and len(ref[r.request_id]) == len(r.output))
    log(f"token agreement, continuous vs materialize: {same}/{n_tok}")

    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")
    log(f"compile seconds: {clock.seconds:.1f} over {clock.count} compiles")
    for f in failures:
        print(f"chip_smoke: FAILED: {f}", file=sys.stderr)
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
