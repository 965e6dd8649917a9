"""The reduction from trace events to busy time, idle gaps and kernel time:
on a hand-made trace whose answers are worked out below, and on a small
trace recorded on a TPU v5e (``data/trace_events.json.gz``)."""

import gzip
import json
import os
import types

import pytest

import devtrace
import programs

DEV = "/device:TPU:0"
OPS, MODS = devtrace.OPS_LINE, devtrace.MODULES_LINE


def _ev(line, name, start, dur, module="", pallas=False):
    return (DEV, line, name, float(start), float(dur), module, pallas)


HAND = {
    "device": [
        _ev(MODS, "jit_decode_step(1)", 0, 100),
        _ev(MODS, "jit_decode_step(1)", 150, 100),
        _ev(OPS, "fusion.1", 0, 40, "jit_decode_step"),
        _ev(OPS, "sgmv_fused", 30, 30, "jit_decode_step"),   # overlaps
        _ev(OPS, "fusion.2", 70, 20, "jit_decode_step"),
        _ev(OPS, "fusion.1", 150, 60, "jit_decode_step"),
        _ev(OPS, "fusion.3", 220, 20, "jit_decode_step"),
        _ev(OPS, "sgmv_fused", 300, 10, "other"),            # no program
    ],
    "host": [("step", 0, 95), ("idle", 95, 40), ("step", 140, 110)],
}


def test_union_and_busy():
    assert devtrace.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [(0, 3), (5, 10)]
    red = devtrace.reduce(HAND)
    # union [0,60] [70,90] [150,210] [220,240] [300,310] = 170 ns
    assert red["busy_s"] == pytest.approx(170e-9)
    assert red["span_s"] == pytest.approx(310e-9)


def test_gaps_attributed_to_host_spans():
    red = devtrace.reduce(HAND)
    # gaps 60-70 (mid 65, step), 90-150 (mid 120, idle), 210-220 (mid 215,
    # step), 240-300 (mid 270, no span)
    assert red["gaps"][:2] == [["idle", pytest.approx(60e-9)],
                               ["none", pytest.approx(60e-9)]] or \
        red["gaps"][:2] == [["none", pytest.approx(60e-9)],
                            ["idle", pytest.approx(60e-9)]]
    assert red["idle_by_span"]["step"] == (2, pytest.approx(20e-9))
    assert red["idle_by_span"]["idle"] == (1, pytest.approx(60e-9))


def test_programs_and_kernel_time():
    red = devtrace.reduce(HAND)
    runs = red["modules"]["jit_decode_step(1)"]
    assert runs == [(0.0, 100.0), (150.0, 100.0)]
    n, s = devtrace.op_seconds(HAND, lambda e: "sgmv" in e[2], runs)
    assert (n, s) == (1, pytest.approx(30e-9))
    top = dict(red["device_ops"])
    assert top["jit_decode_step/fusion.1"] == pytest.approx(100e-9)


def test_kernel_and_prefill_recognised_without_names():
    """The kernels carry no name yet: the SGMV kernel is known by its
    Pallas flag, and a prefill run by the number of operations in it."""
    assert programs.is_sgmv(_ev(OPS, "closed_call.4", 0, 1, pallas=True))
    assert programs.is_sgmv(_ev(OPS, "sgmv_fused", 0, 1))
    assert not programs.is_sgmv(_ev(OPS, "fusion.3", 0, 1))
    n = programs.PREFILL_MIN_OPS
    events = {"device": [_ev(MODS, "jit__lambda(7)", 0, 1000),
                         _ev(MODS, "jit__lambda(9)", 2000, 100)]
              + [_ev(OPS, f"fusion.{i}", i, 1, "jit__lambda")
                 for i in range(n)]
              + [_ev(OPS, "scatter.1", 2000, 50, "jit__lambda")],
              "host": []}
    ctx = types.SimpleNamespace(events=events,
                                trace=devtrace.reduce(events))
    assert programs.prefill_runs(ctx) == [(0.0, 1000.0)]


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "trace_events.json.gz")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no TPU trace recorded yet")
def test_recorded_tpu_trace():
    """A slice of a real ``--trace 1`` run of ``olmo1b-chat-zipf``; the
    expected numbers were worked out when it was cut, by a sweep over
    the ops' endpoints rather than by merging intervals."""
    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    events, want = rec["events"], rec["expect"]
    red = devtrace.reduce(events)
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < red["busy_s"] < red["span_s"]
    runs = [r for name, rs in red["modules"].items()
            if name.startswith(want["decode_module"]) for r in rs]
    assert len(runs) == want["decode_runs"]
    n, s = devtrace.op_seconds(events, programs.is_sgmv, runs)
    assert (n, s) == (want["kernel_ops"], pytest.approx(want["kernel_s"]))
    assert 0 < s < sum(d for _, d in runs) / 1e9
    assert sum(k for k, _ in red["idle_by_span"].values()) == want["gaps"]
    name, longest = red["gaps"][0]
    assert longest == pytest.approx(want["longest_gap_s"], abs=2e-9)
    assert name == want["longest_gap_span"]
