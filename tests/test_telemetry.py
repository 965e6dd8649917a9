"""Serving telemetry: histogram/percentile math under a fake clock, the
golden JSONL trace schema, export well-formedness, the span primitive (in
memory and in the profiler's trace), and the on/off parity contract
(telemetry and spans must never change tokens or kernel launches)."""

import glob
import json
import os

import jax
import numpy as np
import pytest

from conftest import smoke_cfg
from repro.core import LoRAQuantConfig
from repro.kernels.quant_matmul import kernel as qm_kernel
from repro.launch.serve import random_trained_lora
from repro.models import build_model
from repro.serving.engine import AdapterStore, MultiLoRAEngine, Request
from repro.serving.telemetry import (
    DEFAULT_LATENCY_BUCKETS,
    EVENT_SCHEMA,
    Histogram,
    ManualClock,
    MetricsRegistry,
    Telemetry,
    span,
)


# ---------------------------------------------------------------- primitives


def test_manual_clock():
    c = ManualClock(start=2.0)
    assert c() == 2.0
    c.advance(0.5)
    assert c() == 2.5
    c.sleep(1.5)                       # time.sleep drop-in
    assert c() == 4.0
    with pytest.raises(ValueError):
        c.advance(-1.0)


def test_histogram_percentiles_known_values():
    h = Histogram("lat", buckets=(1.0, 2.0, 4.0, 8.0))
    assert h.percentile(50) is None and h.mean is None   # empty
    for v in (0.5, 1.5, 3.0, 3.0, 7.0):
        h.observe(v)
    assert h.count == 5 and h.sum == pytest.approx(15.0)
    assert h.min == 0.5 and h.max == 7.0
    # rank interpolation inside the (2, 4] bucket
    assert h.percentile(50) == pytest.approx(2.5)
    # tail estimate clamped to the observed max, not the bucket bound
    assert h.percentile(99) == pytest.approx(7.0)
    assert h.percentile(0) == pytest.approx(0.5)
    assert h.percentile(100) == pytest.approx(7.0)
    assert h.mean == pytest.approx(3.0)
    s = h.summary()
    assert s["count"] == 5 and s["p50"] == pytest.approx(2.5)


def test_histogram_rejects_bad_buckets():
    with pytest.raises(ValueError):
        Histogram("h", buckets=())
    with pytest.raises(ValueError):
        Histogram("h", buckets=(2.0, 1.0))


def test_registry_labels_types_and_buckets():
    reg = MetricsRegistry()
    reg.counter("reqs_total", status="done").inc(3)
    reg.counter("reqs_total", status="failed").inc()
    assert reg.value("reqs_total") == 4            # family total
    assert reg.value("reqs_total", status="done") == 3
    # same (name, labels) -> same series object
    assert reg.counter("reqs_total", status="done") is reg.counter(
        "reqs_total", status="done")
    # one type per name (Prometheus contract)
    with pytest.raises(ValueError):
        reg.gauge("reqs_total")
    # one bucket grid per histogram family: first declaration wins
    h1 = reg.histogram("lat", buckets=(1.0, 2.0), status="a")
    h2 = reg.histogram("lat", buckets=(9.0,), status="b")
    assert h1.bounds == h2.bounds == (1.0, 2.0)
    with pytest.raises(ValueError):
        reg.counter("ok_total").inc(-1)


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("toks_total", help="tokens").inc(7)
    reg.gauge("depth").set(3)
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0), status="done")
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.to_prometheus()
    lines = text.strip().splitlines()
    assert "# HELP toks_total tokens" in lines
    assert "# TYPE toks_total counter" in lines
    assert "toks_total 7" in lines
    assert "depth 3" in lines
    # cumulative buckets + the implicit +Inf == _count
    assert 'lat_seconds_bucket{status="done",le="0.1"} 1' in lines
    assert 'lat_seconds_bucket{status="done",le="1"} 2' in lines
    assert 'lat_seconds_bucket{status="done",le="+Inf"} 3' in lines
    assert 'lat_seconds_count{status="done"} 3' in lines
    assert any(l.startswith('lat_seconds_sum{status="done"}')
               for l in lines)


def test_default_latency_buckets_ascending():
    assert all(a < b for a, b in zip(DEFAULT_LATENCY_BUCKETS,
                                     DEFAULT_LATENCY_BUCKETS[1:]))


def test_event_schema_enforced():
    tel = Telemetry(clock=ManualClock())
    with pytest.raises(ValueError):
        tel.event("submit", request_id=0)          # missing adapter_id
    with pytest.raises(ValueError):
        tel.event("submit", request_id=0, adapter_id="u", extra=1)
    tel.event("submit", request_id=0, adapter_id="u")
    tel.event("custom_event", anything="goes")     # unknown names pass through
    assert len(tel.events) == 2


def test_span_records_nesting_step_and_counts():
    """Under a fake clock a span records its start and end, the span open
    around it, the step of its step root, and its counts (given at entry
    or set at the end); chrome_trace() exports the recorded spans."""
    clock = ManualClock(start=1.0)
    tel = Telemetry(clock=clock)
    with span("engine.step", tel, step=7, rows=2) as root:
        clock.advance(0.5)
        with span("engine.decode", tel):
            clock.advance(0.25)
            with span("memory.swap_in", tel) as swap:
                clock.advance(0.125)
                swap.set(bytes=4096)
        with span("engine.retire", tel) as retire:
            retire.set(retired=1)
        root.set(admitted=0)
    with span("memory.acquire", tel, hit=1):     # outside any step
        clock.advance(1.0)

    got = [(r.name, r.start, r.end, r.parent, r.step, r.counts)
           for r in tel.spans]
    assert got == [
        ("engine.step", 1.0, 1.875, None, 7, {"rows": 2, "admitted": 0}),
        ("engine.decode", 1.5, 1.875, 0, 7, {}),
        ("memory.swap_in", 1.75, 1.875, 1, 7, {"bytes": 4096}),
        ("engine.retire", 1.875, 1.875, 0, 7, {"retired": 1}),
        ("memory.acquire", 1.875, 2.875, None, None, {"hit": 1}),
    ]
    assert tel._open == []

    doc = tel.chrome_trace()
    xs = {ev["name"]: ev for ev in doc["traceEvents"]
          if ev.get("ph") == "X" and ev["pid"] == 1}
    assert set(xs) == {"engine.step", "engine.decode", "memory.swap_in",
                       "engine.retire", "memory.acquire"}
    assert xs["engine.step"]["ts"] == 0.0
    assert xs["engine.step"]["dur"] == pytest.approx(875e3)
    assert xs["engine.step"]["args"] == {"rows": 2, "admitted": 0, "step": 7}
    assert xs["memory.swap_in"]["ts"] == pytest.approx(750e3)
    assert xs["memory.swap_in"]["args"] == {"bytes": 4096, "step": 7}
    assert xs["memory.acquire"]["args"] == {"hit": 1}


def test_span_without_telemetry_records_nothing():
    """With ``telemetry=None`` a span only opens the profiler annotation:
    nothing is kept in memory, and an attached Telemetry elsewhere sees
    none of it."""
    tel = Telemetry(clock=ManualClock())
    with span("engine.step", None, step=1) as root:
        with span("engine.decode", None) as inner:
            inner.set(rows=3)
        root.set(admitted=1)
    assert tel.spans == [] and tel._open == []
    assert [ev for ev in tel.chrome_trace()["traceEvents"]
            if ev.get("ph") == "X"] == []


# ------------------------------------------------------------- engine-driven


@pytest.fixture(scope="module")
def tiny_model():
    cfg = smoke_cfg("llama3.2-3b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def tiny_store(tiny_model):
    cfg, model, params = tiny_model
    store = AdapterStore(LoRAQuantConfig(rho=0.9, ste_steps=0))
    for i in range(3):
        store.register(f"u{i}", random_trained_lora(
            params["lora"], jax.random.PRNGKey(30 + i)))
    return store


def _requests(cfg, n=5, seed=7, max_new=3):
    rng = np.random.default_rng(seed)
    return [Request(request_id=rid, adapter_id=f"u{rid % 3}",
                    prompt=rng.integers(0, cfg.vocab,
                                        size=8).astype(np.int32),
                    max_new_tokens=max_new)
            for rid in range(n)]


def _run(tiny_model, tiny_store, telemetry=None, clock=None, n=5):
    cfg, model, params = tiny_model
    eng = MultiLoRAEngine(model, params, tiny_store, cache_capacity=64,
                          max_rows=2, hbm_slots=2,
                          telemetry=telemetry, clock=clock)
    for r in _requests(cfg, n=n):
        eng.submit(r)
    done = eng.run()
    return eng, done


def _no_python():
    """Profiler options without the Python tracer, which would record
    every Python call of the interpreted kernels (millions of events on
    the CPU); the spans are annotations and are recorded without it."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


GOLDEN_SCHEMA = {
    "submit": {"request_id", "adapter_id"},
    "admit": {"request_id", "adapter_id", "queue_wait_s", "wave", "row"},
    "prefill": {"wave", "rows", "request_ids", "tpad", "dur_s"},
    "decode_step": {"step", "dur_s", "active_rows", "max_rows", "queued"},
    "first_token": {"request_id", "ttft_s"},
    "retire": {"request_id", "adapter_id", "status", "cause", "tokens",
               "e2e_s", "decode_steps"},
}


def test_trace_schema_golden(tiny_model, tiny_store):
    """The JSONL event log is a stable contract: every lifecycle event
    carries exactly the golden field set (plus ts/event), in lifecycle
    order, for every request submitted."""
    # the schema constant itself is pinned — renaming a field or event is
    # a breaking change that must show up here, not just downstream
    assert {k: set(v) for k, v in EVENT_SCHEMA.items()} == GOLDEN_SCHEMA

    tel = Telemetry(clock=ManualClock())
    eng, done = _run(tiny_model, tiny_store, telemetry=tel)
    assert len(done) == 5

    events = [json.loads(l) for l in tel.to_jsonl().splitlines()]
    assert events, "engine run emitted no events"
    for ev in events:
        name = ev.pop("event")
        ts = ev.pop("ts")
        assert isinstance(ts, float)
        assert name in GOLDEN_SCHEMA, f"unknown event {name!r}"
        assert set(ev) == GOLDEN_SCHEMA[name], (name, sorted(ev))

    # per-request lifecycle: submit -> admit -> first_token -> retire
    by_req = {}
    for ev in (json.loads(l) for l in tel.to_jsonl().splitlines()):
        if "request_id" in ev:
            by_req.setdefault(ev["request_id"], []).append(ev["event"])
    assert set(by_req) == {0, 1, 2, 3, 4}
    for rid, seq in by_req.items():
        assert seq[0] == "submit" and seq[-1] == "retire", (rid, seq)
        assert seq.index("admit") < seq.index("first_token"), (rid, seq)

    # trace table agrees with the event log
    for rid, tr in tel.traces.items():
        assert tr.status == "done" and tr.cause == "ok"
        assert tr.tokens == 3 and tr.e2e_s >= 0 and tr.queue_wait_s >= 0


def test_histograms_under_fake_clock(tiny_model, tiny_store):
    """All three request-latency histograms fill, and the engine stats()
    view exposes their summaries."""
    clock = ManualClock()
    tel = Telemetry(clock=clock)
    eng, done = _run(tiny_model, tiny_store, telemetry=tel)
    lat = tel.latency_summary()
    for name in ("serving_ttft_seconds", "serving_e2e_seconds",
                 "serving_queue_wait_seconds"):
        assert lat[name]["count"] == 5, name
        assert lat[name]["p99"] is not None
    st = eng.stats()
    assert st["submitted"] == 5 and st["tokens"] == 15
    assert st["finished"] == {"done": 5}
    assert st["retire_causes"] == {"ok": 5}
    assert st["latency"]["serving_e2e_seconds"]["count"] == 5
    # registry totals agree with the engine counters
    reg = tel.registry
    assert reg.value("serving_requests_total", status="done") == 5
    assert reg.value("serving_decode_steps_total") == st["decode_steps"]
    assert reg.value("serving_admission_waves_total") == st["admission_waves"]


def test_memory_stats_hit_rate_and_per_pool(tiny_model, tiny_store):
    """A manager with zero lookups must report hit_rate=None (not the old
    vacuous 1.0); after traffic the rate is a real ratio with a per-pool
    breakdown."""
    cfg, model, params = tiny_model
    eng = MultiLoRAEngine(model, params, tiny_store, cache_capacity=64,
                          max_rows=2, hbm_slots=2)
    assert eng.memory_stats() == {}          # manager not built yet
    fresh = eng.memory.stats()               # force-build, still idle
    assert fresh["lookups"] == 0 and fresh["hit_rate"] is None

    for r in _requests(cfg, n=4):
        eng.submit(r)
    eng.run()
    st = eng.memory_stats()
    assert st["lookups"] > 0
    assert 0.0 <= st["hit_rate"] <= 1.0
    assert st["hits"] + st["misses"] == st["lookups"]
    assert st["per_pool"], "per-signature breakdown missing"
    for label, pool in st["per_pool"].items():
        for key in ("hits", "misses", "lookups", "hit_rate", "evictions",
                    "swap_ins", "swap_in_bytes", "capacity", "resident",
                    "pinned", "page_bytes"):
            assert key in pool, (label, key)
        assert pool["lookups"] == pool["hits"] + pool["misses"]
    assert st["swap_in_bytes"] > 0                 # 3 adapters, 2 slots
    assert set(st["prefetch"]) == {"hit", "staged", "failed", "no_slot"}


def test_parity_tokens_and_launches(tiny_model, tiny_store, tmp_path):
    """Telemetry and spans are observation only: an instrumented engine,
    with its spans traced by the profiler or not, must emit token-identical
    output and issue zero extra pallas_call launches compared to an
    uninstrumented one.

    Trace-time launch counts of *consecutive* engine runs oscillate with
    period 2 (jit-cache retention across runs), independent of telemetry
    — so each configuration runs twice and the steady-state SECOND runs
    (same cache parity) are compared."""
    def measured(telemetry):
        _run(tiny_model, tiny_store, telemetry=telemetry)
        before = dict(qm_kernel.LAUNCH_COUNTS)
        eng, done = _run(tiny_model, tiny_store, telemetry=telemetry)
        delta = {k: v - before.get(k, 0)
                 for k, v in qm_kernel.LAUNCH_COUNTS.items()
                 if v - before.get(k, 0)}
        return done, delta

    _run(tiny_model, tiny_store)                   # warm jit caches
    done_off, launches_off = measured(None)
    tel = Telemetry(clock=ManualClock())
    done_on, launches_on = measured(tel)
    with jax.profiler.trace(str(tmp_path), profiler_options=_no_python()):
        done_traced, launches_traced = measured(None)

    assert launches_on == launches_off, "telemetry changed kernel launches"
    assert launches_traced == launches_off, "tracing changed kernel launches"
    assert tel.spans, "the instrumented runs recorded no span"
    by_id_off = {r.request_id: r for r in done_off}
    assert len(done_on) == len(done_traced) == len(done_off) == 5
    for r in done_on + done_traced:
        np.testing.assert_array_equal(r.output, by_id_off[r.request_id].output)


PROGRAM_SPANS = ("engine.sweep", "engine.admit", "engine.select",
                 "memory.acquire", "memory.swap_in", "engine.prefill",
                 "engine.scatter",
                 "engine.decode.prep", "memory.prefetch", "engine.decode",
                 "engine.decode.sync", "engine.retire")


def test_spans_land_in_the_profiler_trace(tiny_model, tiny_store, tmp_path):
    """Run the paged engine (3 adapters over 2 slots, so slot misses are
    forced) under ``jax.profiler.trace`` and read the xplane back: every
    span of the serving step is on the host plane, inside an
    ``engine.step``; each swap-in lies inside an acquire or a prefetch; and
    a step's read-back follows its decode's dispatch (the last steps read
    back without dispatching)."""
    with jax.profiler.trace(str(tmp_path), profiler_options=_no_python()):
        _, done = _run(tiny_model, tiny_store)
    assert len(done) == 5
    files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(files) == 1
    data = jax.profiler.ProfileData.from_file(files[0])
    spans = sorted(
        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, dict(ev.stats))
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
        if ev.name.startswith(("engine.", "memory.")))
    names = {name for _, _, name, _ in spans}
    assert set(PROGRAM_SPANS) | {"engine.step"} <= names, sorted(names)

    def inside(s, names_):
        return any(o[2] in names_ and o[0] <= s[0] and s[1] <= o[1]
                   for o in spans if o is not s)

    steps = [s for s in spans if s[2] == "engine.step"]
    assert [s[3]["step_num"] for s in steps] == list(
        range(steps[0][3]["step_num"], steps[0][3]["step_num"] + len(steps)))
    for s in spans:
        if s[2] != "engine.step":
            assert inside(s, {"engine.step"}), s
    swaps = [s for s in spans if s[2] == "memory.swap_in"]
    for s in swaps:
        assert inside(s, {"memory.acquire", "memory.prefetch"}), s
        assert s[3]["bytes"] > 0
    assert {s[3]["hit"] for s in spans if s[2] == "memory.acquire"} \
        == {0, 1}
    for st in steps:
        within = [s for s in spans if st[0] <= s[0] and s[1] <= st[1]]
        dec = [s for s in within if s[2] == "engine.decode"]
        syncs = [s for s in within if s[2] == "engine.decode.sync"]
        assert len(dec) <= len(syncs) <= 1
        if dec:
            assert dec[0][1] <= syncs[0][0]
    assert sum(s[3]["admitted"] for s in steps) == 5
    assert sum(s[3]["retired"] for s in spans
               if s[2] == "engine.retire") == 5


def test_exports_parse_and_are_nonempty(tiny_model, tiny_store, tmp_path):
    """One paged run emits all three exports: Prometheus text with
    non-empty latency histograms and per-pool memory counters, parseable
    Chrome-trace JSON, and a JSONL log with one object per line."""
    tel = Telemetry(clock=ManualClock())
    eng, _ = _run(tiny_model, tiny_store, telemetry=tel)
    eng.memory_stats()                             # mirror pool gauges

    prom = tmp_path / "metrics.prom"
    trace = tmp_path / "trace.json"
    jsonl = tmp_path / "events.jsonl"
    tel.write_prometheus(str(prom))
    tel.write_chrome_trace(str(trace))
    tel.write_jsonl(str(jsonl))

    text = prom.read_text()
    for needle in ("serving_ttft_seconds_bucket", "serving_e2e_seconds_sum",
                   "serving_queue_wait_seconds_count",
                   "adapter_memory_hits_total{pool=",
                   "adapter_memory_swap_ins_total{pool="):
        assert needle in text, needle
    # exposition is line-structured: every non-comment line is "name value"
    for line in text.strip().splitlines():
        if not line.startswith("#"):
            name, value = line.rsplit(" ", 1)
            float(value)

    doc = json.loads(trace.read_text())
    names = {ev.get("name") for ev in doc["traceEvents"]}
    assert {"engine.step", "engine.prefill", "queue", "decode"} <= names
    spans = [ev for ev in doc["traceEvents"] if ev.get("ph") == "X"]
    assert spans and all(ev["dur"] >= 0 and ev["ts"] >= 0 for ev in spans)

    lines = jsonl.read_text().strip().splitlines()
    assert len(lines) == len(tel.events)
    assert all(json.loads(l) for l in lines)
