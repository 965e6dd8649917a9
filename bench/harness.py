"""One run of one cell: set-up, the measured window, metrics, the check.

The entry the window drives is ``MultiLoRAEngine.step()`` in continuous
mode, fed by ``MultiLoRAEngine.submit`` from ``serve_loop.run_window``.
Everything a cell is made of is found by name from ``BENCHMARK.json``:
its configuration file, ``traffic/<mix>.json``, ``limits/<cell>.json``,
``references/<reference>.py``, ``metrics/<metric>.py`` (one reader per
metric, ``read(run) -> float | None``) and, for each kind of layer the
configuration's ``blocks`` name, ``blocks/<kind>.py`` (``layout.py``).

``BENCH_REHEARSAL=1`` in the environment is the CPU rehearsal of the
benchmark's own tests: it applies each file's ``rehearsal`` sizes, skips
the look for a chip, runs the kernels in the Pallas interpreter, and prints
no metric (only which readers returned a number). ``BENCH_SPEC`` points the
tests at a ``BENCHMARK.json`` of their own.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import json
import os
import shutil
import sys
import time
import types
import typing
from pathlib import Path

import numpy as np

import layout

CODE = Path(__file__).resolve().parent
SETUP_PROMPT_NEW = 2          # warm-up requests: one prefill + one decode
TRACE_SECONDS = 3.0           # profiled sub-window at the end of the window
                              # (at most 30% of it)


class BenchError(Exception):
    """A run that cannot produce a result (exit code 3, no result line)."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _rehearsal(d: dict, on: bool) -> dict:
    out = {k: v for k, v in d.items() if k != "rehearsal"}
    if on:
        out.update(d.get("rehearsal", {}))
    return out


@dataclasses.dataclass
class Cell:
    name: str
    spec: dict
    mc: dict            # model configuration as run
    traffic: dict
    limits: dict
    chips: int


def load_cell(workload: str, rehearsal: bool) -> Cell:
    """Resolve a cell and its files by name. Data files resolve against the
    directory of the ``BENCHMARK.json`` in use."""
    spec_path = Path(os.environ.get("BENCH_SPEC")
                     or CODE.parent / "BENCHMARK.json")
    root = spec_path.parent
    spec = _load_json(spec_path)
    wl = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise BenchError(f"no workload {workload!r} in {spec_path}")
    cfg = next(c for c in spec["configs"] if c["name"] == wl["config"])
    return Cell(
        name=workload, spec=spec,
        mc=_rehearsal(_load_json(root / cfg["file"]), rehearsal),
        traffic=_rehearsal(_load_json(
            root / "bench" / "traffic" / f"{wl['traffic']}.json"), rehearsal),
        limits=_rehearsal(_load_json(
            root / "bench" / "limits" / f"{workload}.json"), rehearsal),
        chips=wl["chips"])


def metric_entries(cell: Cell, trace: bool) -> list:
    """The metrics this cell reports in this kind of run: end-to-end ones
    without a trace, per-layer ones with it."""
    spec, name = cell.spec, cell.name
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    if not trace:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if name in m.get("workloads", [name] if m["moves"] in mine
                             else [])]


class CompileWatch:
    """Counts and sums JAX's backend compiles and persistent-cache loads."""

    def __init__(self, jax):
        self.compiles = self.compile_s = 0.0
        self.loads = self.load_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.loads += 1
            self.load_s += duration

    def snapshot(self) -> tuple:
        return self.compiles, self.compile_s, self.loads, self.load_s


def _fleet(cell: Cell, recipe, seed: int):
    """The fleet as the program's ``QuantizedAdapter``s, made from the
    benchmark's seeded codes (``weights.fleet_codes``): per LoRA-targeted
    linear, one entry per layer and lead index (expert), in the row-major
    order in which the program flattens a stacked ``(L, *lead, r, in)``
    factor."""
    import jax

    import weights
    from repro.core.loraquant import QuantizedLoRA
    from repro.core.quant import QuantizedTensor
    from repro.serving.engine import QuantizedAdapter

    mc, fleet = cell.mc, cell.traffic["fleet"]
    codes = jax.device_get(weights.fleet_codes(
        mc, fleet["recipe"], seed, range(fleet["adapters"])))
    template = weights.lora_template(mc)
    r, bits, group = mc["lora_rank"], recipe.bits_high, recipe.group_size
    out = []
    for i in range(fleet["adapters"]):
        entries = {}
        for lin in layout.linears(mc):
            f = codes[lin.path]
            layers = []
            for at in np.ndindex((lin.count,) + lin.lead):
                at = (i,) + at
                h = int(f["h"][at])

                def qt(side, hi, dim):
                    rows = at + (slice(0, h) if hi else slice(h, r),)
                    pre = f"{side}{'h' if hi else 'l'}_"
                    scale = f[pre + "scale"][rows]
                    zero = (f[pre + "zero"][rows] if hi
                            else np.zeros(scale.shape, np.int32))
                    n = scale.shape[0]
                    return QuantizedTensor(
                        codes=f[pre + "codes"][rows], scale=scale,
                        zero=zero, bits=bits if hi else 1,
                        group_size=min(group, dim),
                        axis=1 if side == "a" else 0,
                        orig_shape=(n, dim) if side == "a" else (dim, n),
                        mode="rtn" if hi else "binary")

                layers.append(QuantizedLoRA(
                    b_high=qt("b", True, lin.m), a_high=qt("a", True, lin.k),
                    b_low=qt("b", False, lin.m), a_low=qt("a", False, lin.k),
                    h=h, rank=r, config=recipe))
            entries[lin.path] = layers
        out.append(QuantizedAdapter(entries=entries, template=template,
                                    recipe=recipe))
    return out


def paging_order(cell: Cell, window_seeds, seconds: float) -> list:
    """The fleet indices that open-loop windows of these seeds request,
    least requested first: paged in that order, the HBM slots hold the
    most requested adapters when the window opens, as in a server that has
    been serving this traffic for a while."""
    import loadgen

    counts = collections.Counter(
        p.adapter for s in window_seeds
        for p in loadgen.schedule(cell.traffic, s, cell.mc["vocab"],
                                  seconds))
    return sorted(counts, key=lambda a: (counts[a], a))


def _typed(tp, value):
    """``value`` as read from a configuration file, as the program's type
    ``tp``: a dataclass from an object, a tuple from a list, each part
    typed in turn; anything else as it is."""
    if dataclasses.is_dataclass(tp) and isinstance(value, dict):
        hints = typing.get_type_hints(tp)
        return tp(**{k: _typed(hints[k], v) for k, v in value.items()})
    args = [a for a in typing.get_args(tp) if a is not type(None)]
    if typing.get_origin(tp) is tuple and isinstance(value, list):
        return tuple(_typed(args[0], v) for v in value)
    if typing.get_origin(tp) is typing.Union and len(args) == 1:
        return _typed(args[0], value)
    return value


def model_config(mc: dict):
    """The program's ``ModelConfig`` for a configuration file: its keys
    that name a ``ModelConfig`` field, nested groups (``blocks``, ``mla``,
    ``moe``) typed from the field's annotation."""
    import weights
    from repro.configs.base import ModelConfig

    layout.groups(mc)           # the groups hold n_layers layers
    hints = typing.get_type_hints(ModelConfig)
    return ModelConfig(**{k: _typed(hints[k], v) for k, v in mc.items()
                          if k in hints and k != "dtype"},
                       dtype=weights.dtype_of(mc))


def setup(cell: Cell, seed: int, watch: CompileWatch, needed=None):
    """Build the served state and warm up every shape the traffic uses.
    ``needed`` lists the fleet indices whose adapter pages the run will
    read, in the order they are paged in (None: the whole fleet, by
    index); the whole fleet is registered either way. Returns
    ``(engine, parts)`` with ``parts`` the set-up's phases in seconds."""
    import jax

    import weights
    from repro.core import LoRAQuantConfig
    from repro.models import build_model
    from repro.serving.engine import AdapterStore, MultiLoRAEngine, Request

    mc, traffic = cell.mc, cell.traffic
    parts = {}
    t = time.perf_counter()
    model = build_model(model_config(mc))
    base = jax.block_until_ready(weights.base_params(mc, seed))
    parts["init"] = time.perf_counter() - t

    t = time.perf_counter()
    recipe = LoRAQuantConfig(**traffic["fleet"]["recipe"])
    store = AdapterStore(default_recipe=recipe)
    for i, qa in enumerate(_fleet(cell, recipe, seed)):
        store.register_quantized(f"a{i}", qa)
    parts["fleet"] = time.perf_counter() - t

    server = traffic["server"]
    engine = MultiLoRAEngine(
        model, {"base": base, "lora": weights.lora_template(mc)}, store,
        cache_capacity=server["cache_capacity"], mode="continuous",
        max_rows=server["max_rows"], hbm_slots=server["hbm_slots"])
    t = time.perf_counter()
    if needed is None:
        needed = range(traffic["fleet"]["adapters"])
    ids = [f"a{i}" for i in needed]
    build = getattr(engine.memory, "_host_page", None)
    if build is not None:
        # the host tier is numpy: its packing runs on the host's CPU, where
        # each small eager operation costs far less than on the chip
        with jax.default_device(jax.local_devices(backend="cpu")[0]):
            for aid in ids:
                build(aid)
    for aid in ids:
        engine.memory.acquire(aid, pin=False)
    parts["pages"] = time.perf_counter() - t
    parts["adapters_paged"] = len(ids)

    # warm-up requests read pages already in HBM slots: the swap-in path
    # was compiled by the acquires above, and a swap-in per request would
    # only add time
    resident = [a for a in ids if engine.memory.resident(a)] or ids
    t = time.perf_counter()
    c0 = watch.snapshot()
    rid = -1
    for length in traffic["prompt_tokens"]:
        for group in range(1, server["max_rows"] + 1):
            for k in range(group):
                engine.submit(Request(
                    request_id=rid, adapter_id=resident[k % len(resident)],
                    prompt=np.zeros(length, np.int32),
                    max_new_tokens=SETUP_PROMPT_NEW))
                rid -= 1
            engine.run()
    c1 = watch.snapshot()
    parts["warmup"] = time.perf_counter() - t
    parts["warmup_compiles"] = c1[0] - c0[0]
    parts["warmup_compile_s"] = c1[1] - c0[1]
    parts["warmup_cache_loads"] = c1[2] - c0[2]
    parts["warmup_cache_load_s"] = c1[3] - c0[3]
    # what set-up made lives as long as the server: keep it out of the
    # collections the window triggers, as a server does once loaded
    gc.collect()
    gc.freeze()
    return engine, parts


def compile_setup(jax) -> CompileWatch:
    """Point JAX's persistent compilation cache at the program's directory
    (``$JAX_COMPILATION_CACHE_DIR``, else the checkout's ``.jax_cache``),
    cache every program however small, and start counting compiles."""
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return CompileWatch(jax)


def request(p, due: float):
    """The program's ``Request`` for a planned request due at ``due``."""
    from repro.serving.engine import Request

    return Request(request_id=p.index, adapter_id=f"a{p.adapter}",
                   prompt=p.prompt, max_new_tokens=p.max_new, t_submit=due)


def reference_shape(traffic: dict) -> dict:
    """The reference's one shape per cell, so that it compiles once: as
    many rows as the check samples requests, each as long as the longest
    prompt plus the most new tokens (rounded up to 64), with logits read at
    as many positions as the most new tokens."""
    n = max(traffic["prompt_tokens"]) + traffic["output_tokens"]["max"]
    return {"rows": traffic["check"]["max_requests"],
            "length": -(-n // 64) * 64,
            "positions": traffic["output_tokens"]["max"]}


def _percentile(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q))


def _sample(window, seed: int, check: dict) -> list:
    """Requests finished in the window, drawn from the seed: the one with
    the most served tokens, then one from each batch row not yet in the
    sample (a fault confined to one row shows), then others, until
    ``min_tokens`` served tokens or ``max_requests`` requests."""
    done = [r for r in window.recs.values()
            if r.status == "DONE" and r.tokens and r.tokens[-1] <= window.end]
    if not done:
        return []
    done.sort(key=lambda r: r.planned.index)
    longest = max(done, key=lambda r: (len(r.output), -r.planned.index))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 2]).permutation(len(rest))
    picked, rows = [longest], {longest.row}
    for j in order:
        if len(picked) >= check["max_requests"]:
            break
        if rest[j].row is not None and rest[j].row not in rows:
            picked.append(rest[j])
            rows.add(rest[j].row)
    total = sum(len(r.output) for r in picked)
    for j in order:
        if total >= check["min_tokens"] or len(picked) >= check["max_requests"]:
            break
        if rest[j] not in picked:
            picked.append(rest[j])
            total += len(rest[j].output)
    return picked


def decide(res, limits: dict, bad: int) -> tuple:
    """``(checks, correct)`` of one run: the reference comparison ``res``
    (``logit_gaps``' result, or None where no request finished) against
    the cell's limits, and the requests whose token stamps disagreed."""
    checks = {
        "logit_gap": {"value": res["gap"] if res else None,
                      "limit": limits["logit_gap"]},
        "tokens_compared": {"value": res["tokens"] if res else 0,
                            "limit": 1},
        "bad_requests": {"value": bad, "limit": 0},
    }
    correct = (res is not None and res["gap"] <= limits["logit_gap"]
               and res["tokens"] >= 1 and bad == 0)
    return checks, correct


def run(args, t_start: float) -> int:
    rehearsal = os.environ.get("BENCH_REHEARSAL") == "1"
    cell = load_cell(args.workload, rehearsal)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if not rehearsal and (dev.platform != "tpu" or len(devices) < cell.chips):
        raise BenchError(f"cell {cell.name} needs {cell.chips} TPU chip(s); "
                         f"JAX found {len(devices)} {dev.platform} device(s)")
    import peaks

    try:
        peak = peaks.peaks(dev.device_kind)
    except KeyError as e:
        if not rehearsal:
            raise BenchError(e.args[0]) from e
        peak = None          # a CPU rehearsal reports no device metric
    watch = compile_setup(jax)

    import serve_loop

    needed = None
    if cell.traffic["arrivals"] == "open_loop":
        needed = paging_order(cell, [args.seed], args.seconds)
    engine, parts = setup(cell, args.seed, watch, needed)
    setup_s = time.perf_counter() - t_start
    log("setup_s {:.3f}: ".format(setup_s) + ", ".join(
        f"{k} {v:.3f}" if isinstance(v, float) else f"{k} {v}"
        for k, v in parts.items()))

    trace = None
    if args.trace:
        tdir = CODE / "out" / "trace"
        shutil.rmtree(tdir, ignore_errors=True)
        tdir.mkdir(parents=True)
        trace = {"dir": tdir,
                 "length": min(TRACE_SECONDS, 0.3 * args.seconds)}
    mem0 = engine.memory_stats()
    c0 = watch.snapshot()

    gc_pauses = []

    def on_gc(phase, info):
        if phase == "start":
            gc_pauses.append([info["generation"], time.perf_counter()])
        elif gc_pauses:
            gc_pauses[-1][1] = time.perf_counter() - gc_pauses[-1][1]

    gc.callbacks.append(on_gc)
    window = serve_loop.run_window(engine, request, cell.traffic,
                                   args.seed, cell.mc["vocab"], args.seconds,
                                   trace)
    gc.callbacks.remove(on_gc)
    c1 = watch.snapshot()
    mem1 = engine.memory_stats()
    stats = dev.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    late = window.lateness
    log(f"window {window.end - window.t0:.3f}s: {len(window.recs)} requests "
        f"submitted, {len(window.steps)} steps, {c1[0] - c0[0]:.0f} compiles"
        + (f"; generator late p50 {_percentile(late, 50) * 1e3:.3f} ms, "
           f"max {max(late) * 1e3:.3f} ms" if late else "")
        + f"; {len(gc_pauses)} garbage collections (longest "
        f"{max([p[1] for p in gc_pauses], default=0.0) * 1e3:.3f} ms, "
        f"{sum(p[0] == 2 for p in gc_pauses)} of the oldest generation)")

    red = events = None
    if trace is not None and window.trace_span is not None:
        import devtrace

        t = time.perf_counter()
        events = devtrace.load(str(trace["dir"]))
        red = devtrace.reduce(events)
        log(f"trace: {len(events['device'])} device events read in "
            f"{time.perf_counter() - t:.1f}s; busy {red['busy_s']:.6f}s of "
            f"{window.trace_span[1] - window.trace_span[0]:.6f}s; idle by "
            f"host span {red['idle_by_span']}")
    ctx = types.SimpleNamespace(
        cell=cell, mc=cell.mc, traffic=cell.traffic, window=window,
        setup_s=setup_s, compiles=c1[0] - c0[0], mem0=mem0, mem1=mem1,
        peak=peak, events=events, trace=red,
        max_rows=cell.traffic["server"]["max_rows"])
    metrics, computed = {}, []
    for m in metric_entries(cell, bool(args.trace)):
        value = layout.load(CODE / "metrics" / f"{m['name']}.py").read(ctx)
        if value is None:
            log(f"metric {m['name']}: nothing to read")
            continue
        computed.append(m["name"])
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        log(f"metric {m['name']} = {value!r} {m['unit']}")

    recs = list(window.recs.values())
    attempted = sum(r.due <= window.end for r in recs)
    failed = sum(r.status not in (None, "DONE") for r in recs)
    sample = _sample(window, args.seed, cell.traffic["check"])
    seqs = [(r.planned.adapter, r.planned.prompt, r.output) for r in sample]
    del engine, window, recs, sample
    gc.unfreeze()
    gc.collect()

    t = time.perf_counter()
    ref = layout.load(CODE / "references" / f"{cell.mc['reference']}.py")
    res = (ref.logit_gaps(cell.mc, cell.traffic["fleet"]["recipe"],
                          args.seed, seqs, **reference_shape(cell.traffic))
           if seqs else None)
    checks, correct = decide(res, cell.limits, ctx.window.bad)
    log(f"reference: {time.perf_counter() - t:.1f}s over {len(seqs)} "
        f"requests" + (f", argmax agreement {res['agree']:.4f}" if res else ""))

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if rehearsal:
        result["metrics"] = {}
        result["rehearsal"] = {"computed": computed}
    elif red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = (ctx.window.trace_span[1]
                              - ctx.window.trace_span[0])
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["gaps"]}
    result["checks"] = checks
    for k, v in checks.items():
        log(f"check {k} = {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0
