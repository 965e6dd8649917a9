"""The program's spans and the split of the device's idle time across them:
on a hand-made trace whose answers are worked out below, on a trace the
profiler writes here, and on a slice recorded on a TPU v5e
(``data/program_spans.json.gz``)."""

import gzip
import importlib.util
import json
import os
import types

import pytest

import devtrace
import spans

DEV = "/device:TPU:0"
OPS = devtrace.OPS_LINE


def _op(start, end):
    return (DEV, OPS, "fusion", float(start), float(end - start), "", False)


def _sp(name, start, end, **counts):
    return (name, float(start), float(end - start), counts)


# busy [0,100] [110,115] [130,135] [200,300] [400,500] [520,530] [600,700]
# [730,740]; idle (100,110) (115,130) (135,200) (300,400) (500,520)
# (530,600) (700,730): 310 ns
EVENTS = {
    "device": [_op(0, 100), _op(110, 115), _op(130, 135), _op(200, 300),
               _op(250, 260), _op(400, 500), _op(520, 530), _op(600, 700),
               _op(730, 740)],
    "host": [("step", 0.0, 180.0), ("generator", 180.0, 10.0),
             ("step", 190.0, 520.0), ("generator", 710.0, 10.0)],
}
SPANS = [
    _sp("engine.step", 2, 175, step_num=1, rows=2, admitted=0, pending=0),
    _sp("engine.decode", 2, 8),
    _sp("engine.decode.sync", 8, 140),
    _sp("engine.retire", 140, 170, retired=0),
    _sp("engine.step", 192, 705, step_num=2, rows=3, admitted=1, pending=0),
    _sp("engine.sweep", 192, 195, expired=0),
    _sp("engine.admit", 195, 560, rows=1, tpad=128),
    _sp("engine.select", 195, 350, picked=1),
    _sp("memory.acquire", 196, 349, hit=0),
    _sp("memory.swap_in", 200, 340, bytes=1000),
    _sp("engine.prefill", 350, 420, rows=1, tpad=128),
    _sp("engine.prefill.sync", 420, 510),
    _sp("engine.scatter", 510, 515, rows=1),
    _sp("engine.decode.prep", 560, 570, retiled=1),
    _sp("memory.prefetch", 570, 575, staged=0),
    _sp("engine.decode", 575, 580),
    _sp("engine.decode.sync", 580, 700),
    _sp("engine.retire", 700, 705, retired=1),
]


def test_gaps_split_by_overlap_across_innermost_spans():
    rep = spans.analyse(EVENTS, SPANS)
    assert rep["window"] == (0.0, 740.0)
    assert sum(b - a for a, b in rep["idle"]) == 310
    # (135,200) alone: decode.sync 5, retire 30, engine.step's own 5, the
    # loop's step 5 + 2, generator 10, sweep 3, select 1, acquire 4 (its
    # midpoint, 167.5, lies in retire)
    assert rep["by_name"] == {
        "engine.decode.sync": 50, "engine.retire": 35, "engine.step": 5,
        "step": 12, "generator": 20, "engine.sweep": 3, "engine.select": 2,
        "memory.acquire": 13, "memory.swap_in": 40, "engine.prefill": 50,
        "engine.prefill.sync": 10, "engine.scatter": 5, "engine.admit": 35,
        "engine.decode.prep": 10, "memory.prefetch": 5, "engine.decode": 5,
        "none": 10}
    assert rep["in_step_idle"] == 280           # 70 + 210


METRICS = ("step_idle_ms", "admit_idle_ms", "swapin_exposed_ms")


def _read(metric, events, program):
    """A metric's reader on a run whose trace held these events and spans."""
    path = os.path.join(os.path.dirname(spans.__file__), "metrics",
                        f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"m_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    ctx = types.SimpleNamespace(events=events,
                                program_spans=spans.analyse(events, program))
    return mod.read(ctx)


def test_the_three_readers():
    got = {m: _read(m, EVENTS, SPANS) for m in METRICS}
    assert got == {"step_idle_ms": pytest.approx(65e-6),    # 10 + 15 + 40 ns
                   "admit_idle_ms": pytest.approx(155e-6),  # 5+100+20+30 ns
                   "swapin_exposed_ms": pytest.approx(40e-6)}
    # a span reaching past the traced device window is not counted
    late = SPANS + [_sp("memory.swap_in", 735, 750, bytes=8)]
    assert _read("swapin_exposed_ms", EVENTS, late) == pytest.approx(40e-6)
    # a step or admission that admitted nothing is not an admission group
    empty = SPANS + [_sp("engine.step", 600, 650, admitted=1),
                     _sp("engine.admit", 601, 610, rows=0, tpad=0)]
    assert _read("step_idle_ms", EVENTS, empty) == pytest.approx(65e-6)
    assert _read("admit_idle_ms", EVENTS, empty) == pytest.approx(155e-6)


def test_innermost_pieces():
    pieces = spans.innermost([(0, 10, "a"), (2, 4, "b"), (4, 6, "c"),
                              (5, 6, "d"), (12, 13, "e")])
    assert pieces == [(0, 2, "a"), (2, 4, "b"), (4, 5, "c"), (5, 6, "d"),
                      (6, 10, "a"), (12, 13, "e")]


def test_silent_without_program_spans():
    """A program that writes no span (the parent of the change that added
    them) or a run without a trace: every reader returns None."""
    assert spans.analyse(EVENTS, []) is None
    assert spans.analyse({"device": [], "host": []}, SPANS) is None
    for metric in METRICS:
        assert _read(metric, EVENTS, []) is None
    assert spans.report(types.SimpleNamespace(events=None)) is None
    assert spans.load("/nonexistent/trace/dir") == []


def test_load_reads_annotations_and_counts(tmp_path):
    """The program's span primitive, under the profiler on this CPU, read
    back by ``load`` with its counts; other annotations are left out."""
    import jax

    from repro.serving.telemetry import span

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with jax.profiler.TraceAnnotation("step"):
            with span("engine.step", step=4, rows=2) as root:
                with span("memory.swap_in") as swap:
                    swap.set(bytes=64)
                root.set(admitted=1)
    got = spans.load(str(tmp_path))
    assert [(name, counts) for name, _, _, counts in got] == [
        ("engine.step", {"_r": 1, "step_num": 4, "rows": 2, "admitted": 1}),
        ("memory.swap_in", {"bytes": 64})]
    (_, s0, d0, _), (_, s1, d1, _) = got
    assert s0 <= s1 and s1 + d1 <= s0 + d0


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "program_spans.json.gz")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no TPU slice with program spans recorded yet")
def test_recorded_tpu_slice():
    """A slice of a ``--trace 1`` run of ``olmo1b-chat-zipf`` holding an
    admitting step and a swap-in; the expected numbers were worked out when
    it was cut, by a sweep over every endpoint rather than by merging
    intervals and cutting nested spans."""
    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    events = {"device": [tuple(e) for e in rec["events"]["device"]],
              "host": [tuple(h) for h in rec["events"]["host"]]}
    program = [tuple(s) for s in rec["spans"]]
    want = rec["expect"]
    rep = spans.analyse(events, program)
    assert sum(b - a for a, b in rep["idle"]) == pytest.approx(
        want["idle_ns"], abs=1)
    assert set(rep["by_name"]) == set(want["by_name"])
    for name, ns in want["by_name"].items():
        assert rep["by_name"][name] == pytest.approx(ns, abs=1), name
    for metric in METRICS:
        assert _read(metric, events, program) == pytest.approx(
            want[metric], rel=1e-9), metric
