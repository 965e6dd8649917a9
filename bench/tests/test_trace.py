"""The reduction from trace events to busy time, idle gaps and kernel time:
on a hand-made trace whose answers are worked out below, and on a small
trace recorded on a TPU v5e (``data/trace_events.json.gz``)."""

import glob
import gzip
import json
import os
import types

import pytest

import devtrace
import programs

DEV = "/device:TPU:0"
OPS, MODS = devtrace.OPS_LINE, devtrace.MODULES_LINE


def _ev(line, name, start, dur, module="", pallas=False, scope=""):
    return (DEV, line, name, float(start), float(dur), module, pallas, scope)


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


PROGRAM = "jit(decode_step)/jit(decode_step)"
LOOP = PROGRAM + "/while"
SCOPED = {
    "device": [
        _ev(MODS, "jit_decode_step(1)", 0, 100),
        _ev(OPS, "while.1", 10, 80, scope=LOOP),
        _ev(OPS, "fusion.1", 10, 20, scope=LOOP + "/body/moe/dot_general"),
        _ev(OPS, "while.2", 30, 30, scope=LOOP + "/body/moe/while"),
        _ev(OPS, "fusion.2", 30, 10,
            scope=LOOP + "/body/moe/while/body/dot_general"),
        _ev(OPS, "fusion.3", 60, 30, scope=LOOP + "/body/attn/dot_general"),
        _ev(OPS, "fusion.4", 95, 5, scope=PROGRAM + "/moe"),
        _ev(OPS, "fusion.5", 120, 5, scope=LOOP + "/body/moe/add"),
    ],
    "host": [],
}


def _ctx(events):
    return types.SimpleNamespace(events=events, trace=devtrace.reduce(events))


def test_scope_seconds_by_component():
    """Operations are found by a whole component of their scope path, only
    inside the runs given; an operation inside another that matches (the
    loop under ``moe``, inside the loop's own operation) counts once."""
    ctx = _ctx(SCOPED)
    runs = programs.decode_runs(ctx)
    assert programs.scope_seconds(ctx, runs, "moe") == (
        3, pytest.approx((20 + 30 + 5) * 1e-9))
    assert programs.scope_seconds(ctx, runs, "attn") == (
        1, pytest.approx(30e-9))
    assert programs.scope_seconds(ctx, runs, "while") == (
        1, pytest.approx(80e-9))
    assert programs.scope_seconds(ctx, runs, "jit(decode_step)") == (
        2, pytest.approx(85e-9))
    assert programs.scope_seconds(ctx, runs, "mo") == (0, 0.0)
    assert programs.scope_seconds(ctx, [], "moe") == (0, 0.0)
    none = types.SimpleNamespace(events=None, trace=None)
    assert programs.scope_seconds(none, runs, "moe") == (0, 0.0)


def test_every_kernel_in_decode_counts_as_sgmv_while_unnamed():
    """The trace gives the SGMV kernel no name of its own (its op_name ends
    in the ``pallas_call`` every kernel's does), so a second, differently
    named kernel inside the decode program counts with it; one outside the
    decode program does not."""
    events = {"device": [
        _ev(MODS, "jit_decode_step(1)", 0, 100),
        _ev(OPS, "closed_call.74", 10, 20, pallas=True,
            scope=LOOP + "/body/closed_call/pallas_call"),
        _ev(OPS, "router.3", 40, 10, pallas=True,
            scope=LOOP + "/body/closed_call/moe/router/pallas_call"),
        _ev(OPS, "fusion.1", 60, 30, scope=LOOP + "/body/closed_call/dot"),
        _ev(OPS, "closed_call.9", 120, 5, pallas=True,
            scope="jit(_lambda)/pallas_call"),
    ], "host": []}
    ctx = _ctx(events)
    runs = programs.decode_runs(ctx)
    assert devtrace.op_seconds(events, programs.is_sgmv, runs) == (
        2, pytest.approx(30e-9))


def test_events_recorded_without_a_scope():
    """An event of a trace recorded before the scope was kept has seven
    fields, and no scope."""
    old = _ev(OPS, "fusion.1", 0, 1)[:7]
    assert devtrace.scope(old) == []
    assert devtrace.scope(_ev(OPS, "fusion.1", 0, 1)) == []
    assert devtrace.scope(SCOPED["device"][2])[-3:] == [
        "body", "moe", "dot_general"]
    assert devtrace.reduce({"device": [old], "host": []})["busy_s"] == \
        pytest.approx(1e-9)


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


def test_op_names_from_a_trace(tmp_path):
    """The scope path of each instruction, read from the HLO a profiler
    trace keeps of every program it ran (here the CPU's)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("layer_a"):
            y = jnp.sin(x) @ x
        return jnp.tanh(y).sum()

    x = jnp.ones((8, 8))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    names = devtrace.op_names(path)
    (program,) = [p for p in names if p.startswith("jit_f(")]
    paths = set(names[program].values())
    assert "jit(f)/layer_a/sin" in paths
    assert "jit(f)/tanh" in paths
    assert not any("layer_a" in p for p in paths if "tanh" in p)
    assert devtrace.op_names(path, {program}).keys() == {program}
    assert devtrace.op_names(path, set()) == {}


def _pb(*fields) -> bytes:
    """A protobuf message from ``(field number, value)`` pairs: an int, or
    bytes, a str or a nested message (a list of pairs), length-delimited."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += varint(number << 3) + varint(value)
            continue
        if isinstance(value, list):
            value = _pb(*value)
        elif isinstance(value, str):
            value = value.encode()
        out += varint(number << 3 | 2) + varint(len(value)) + value
    return out


def test_load_gives_each_device_op_its_scope(tmp_path):
    """A trace laid out as a TPU's: the device plane's ``XLA Modules`` line
    names a program run ``jit_f(5)``, its ``XLA Ops`` line has HLO
    instruction text for names, and the ``/host:metadata`` plane holds the
    program's ``HloProto`` as a ``Hlo Proto`` stat. Each op inside the run
    gets its instruction's ``op_name``; an op outside any run gets none."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("layer_a"):
            y = jnp.sin(x) @ x
        return jnp.tanh(y).sum()

    module = jax.jit(f).lower(jnp.ones((8, 8))).compiler_ir(
        "hlo").as_serialized_hlo_module_proto()
    names = {}
    devtrace._instruction_op_names(memoryview(_pb((1, module))), names)
    ops = sorted(n for n, path in names.items() if path)
    assert "jit(f)/layer_a/sin" in {names[n] for n in ops}

    def entry(key, value):              # a map<int64, message> entry
        return (4, [(1, key), (2, value)])

    hlo = [(1, 7), (2, "jit_f(5)"), (5, [(1, 1), (6, _pb((1, module)))])]
    metadata = [(1, 1), (2, devtrace.METADATA_PLANE), entry(7, hlo),
                (5, [(1, 1), (2, [(1, 1), (2, devtrace.HLO_STAT)])])]
    events = [(4, [(1, 10 + i), (2, i * 1000_000), (3, 500_000)])
              for i in range(len(ops))]
    events.append((4, [(1, 10), (2, 200_000_000), (3, 500_000)]))
    device = [(1, 2), (2, "/device:TPU:0"),
              (3, [(1, 1), (2, devtrace.MODULES_LINE), (3, 1000),
                   (4, [(1, 1), (2, 0), (3, 100_000_000)])]),
              (3, [(1, 2), (2, devtrace.OPS_LINE), (3, 1000), *events]),
              entry(1, [(1, 1), (2, "jit_f(5)")]),
              *(entry(10 + i, [(1, 10 + i), (2, f"%{n} = f32[8,8] op(%x)")])
                for i, n in enumerate(ops))]
    out = tmp_path / "plugins" / "profile" / "run"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(_pb((1, metadata), (1, device)))

    dev = devtrace.load(str(tmp_path))["device"]
    got = [(e[2].split()[0], devtrace.scope(e)) for e in dev
           if e[1] == OPS]
    assert got[:-1] == [(f"%{n}", names[n].split("/")) for n in ops]
    assert got[-1] == (f"%{ops[0]}", [])     # outside the program's run
    (run,) = [e for e in dev if e[1] == MODS]
    assert run[2] == "jit_f(5)" and run[7] == ""
