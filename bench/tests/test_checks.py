"""``correct`` comes out false when the timed path is broken underneath,
and the float8 control fails the limit: each at the rehearsal sizes, in
this process, with the harness's look for a chip skipped.

The faults (``faults.py``) are planted once set-up has built the engine,
as ``calibrate.py`` plants them at the cell's own size on the chip."""

import argparse
import contextlib
import json

import numpy as np
import pytest

import faults
import harness
import layout

CELL = "olmo1b-chat-zipf"


def _run(monkeypatch, capsys):
    monkeypatch.setenv("BENCH_REHEARSAL", "1")
    args = argparse.Namespace(workload=CELL, seed=3, seconds=3.0, trace=0)
    assert harness.run(args, 0.0) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(monkeypatch, capsys):
    assert _run(monkeypatch, capsys)["correct"] is True


@pytest.mark.parametrize("fault", faults.NAMES)
def test_broken_path_is_not_correct(fault, monkeypatch, capsys):
    setup = harness.setup

    def broken_setup(*args, **kwargs):
        engine, parts = setup(*args, **kwargs)
        stack.enter_context(faults.planted(engine, fault))
        return engine, parts

    monkeypatch.setattr(harness, "setup", broken_setup)
    with contextlib.ExitStack() as stack:
        out = _run(monkeypatch, capsys)
    assert out["correct"] is False
    gap = out["checks"]["logit_gap"]
    assert gap["value"] is None or gap["value"] > gap["limit"]


def test_float8_control_fails_the_limit():
    cell = harness.load_cell(CELL, rehearsal=True)
    ref = layout.load(harness.CODE / "references" /
                          f"{cell.mc['reference']}.py")
    rng = np.random.default_rng(0)
    seqs = [(a, rng.integers(0, cell.mc["vocab"], 16).astype(np.int32),
             rng.integers(0, cell.mc["vocab"], 12).astype(np.int32))
            for a in range(3)]
    res = ref.logit_gaps(cell.mc, cell.traffic["fleet"]["recipe"], 3, seqs,
                         rows=4, length=32, positions=12, control=True)
    _, correct = harness.decide(
        {"gap": res["control_gap"], "tokens": res["tokens"]}, cell.limits, 0)
    assert correct is False
