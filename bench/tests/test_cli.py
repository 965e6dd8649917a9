"""The command as a check runs it, on the CPU.

With ``BENCH_REHEARSAL=1`` each cell runs end to end at its files'
``rehearsal`` sizes (Pallas in the interpreter) and prints no metric;
without it the command refuses a machine with no TPU, and a checkout with
no program, printing no result."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _run(args, cwd=ROOT, rehearsal=True, extra_env=None, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_REHEARSAL", None)
    if rehearsal:
        env["BENCH_REHEARSAL"] = "1"
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "bench", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


def _result(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [w["name"] for w in _cells()["workloads"]])
def test_each_cell_rehearses(cell):
    spec = _cells()
    out = _result(_run(["--workload", cell, "--seed", "2147483700",
                        "--seconds", "4", "--trace", "0"]))
    assert out["correct"] is True
    assert out["metrics"] == {}          # nothing from a CPU is reported
    assert out["attempted"] > 0 and out["failed"] == 0
    e2e = {m["name"] for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])}
    assert set(out["rehearsal"]["computed"]) == e2e
    assert list(out)[-1] == "checks"
    assert out["checks"]["logit_gap"]["value"] <= \
        out["checks"]["logit_gap"]["limit"]


def test_no_tpu_no_result():
    proc = _run(["--workload", "olmo1b-chat-zipf", "--seed", "1",
                 "--seconds", "1", "--trace", "0"], rehearsal=False)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_without_the_program_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    proc = _run(["--workload", "olmo1b-chat-zipf", "--seed", "1",
                 "--seconds", "1", "--trace", "0"], cwd=str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def _w64_cell(spec, data):
    """A test configuration (olmo-1b at a narrower rehearsal width) and a
    test mix derived from the chat mix."""
    with open(os.path.join(BENCH, "configs", "olmo-1b.json")) as f:
        mc = json.load(f)
    mc.update(name="olmo-1b.w64", rehearsal=dict(mc["rehearsal"], d_model=64,
                                                  n_heads=2, n_kv_heads=1))
    with open(os.path.join(BENCH, "traffic", "chat-zipf.json")) as f:
        mix = json.load(f)
    mix["rehearsal"].update(prompt_tokens=[8], rate_per_s=3.0)
    data["configs/olmo-1b.w64.json"] = mc
    data["traffic/chat-short.json"] = mix
    spec["configs"].append({"name": "olmo-1b.w64", "source": mc["source"],
                            "file": "bench/configs/olmo-1b.w64.json",
                            "reduced": ["d_model"], "why": "test"})
    spec["workloads"].append({"name": "w64-chat-short",
                              "config": "olmo-1b.w64",
                              "traffic": "chat-short", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("w64-chat-short")
    return "w64-chat-short", {"itl_p95_ms", "setup_s"}


def _backlog_cell(spec, data):
    """A backlog mix on olmo-1b (a queue that never empties, every adapter
    resident, drawn uniformly), added as a mix file and a cell entry."""
    with open(os.path.join(BENCH, "traffic", "chat-zipf.json")) as f:
        mix = json.load(f)
    del mix["rate_per_s"]
    mix.update(arrivals="backlog", backlog=4, block=32)
    for sizes in (mix, mix["rehearsal"]):
        sizes.pop("rate_per_s", None)
        fleet = sizes["fleet"]
        fleet.update(popularity="uniform")
        fleet.pop("zipf_alpha")
        sizes["server"]["hbm_slots"] = fleet["adapters"]
    data["traffic/decode-batch.json"] = mix
    with open(os.path.join(BENCH, "configs", "olmo-1b.json")) as f:
        data["configs/olmo-1b.json"] = json.load(f)
    spec["workloads"].append({"name": "olmo1b-decode-batch",
                              "config": "olmo-1b",
                              "traffic": "decode-batch", "chips": 1,
                              "why": "test"})
    return "olmo1b-decode-batch", {"itl_p95_ms", "setup_s"}


@pytest.mark.parametrize("add", [_w64_cell, _backlog_cell],
                         ids=["test-config-and-mix", "backlog-mix"])
def test_a_cell_added_as_data_only(tmp_path, add):
    """A configuration, a mix and a cell found by name: data files and
    ``BENCHMARK.json`` entries, no file of the harness edited."""
    spec, data = _cells(), {}
    cell, e2e = add(spec, data)
    data[f"limits/{cell}.json"] = {"logit_gap": 1.0,
                                   "rehearsal": {"logit_gap": 1e-3}}
    for name, content in data.items():
        path = tmp_path / "bench" / name
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(content))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    out = _result(_run(["--workload", cell, "--seed", "5",
                        "--seconds", "3", "--trace", "0"],
                       extra_env={"BENCH_SPEC": str(tmp_path /
                                                    "BENCHMARK.json")}))
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["rehearsal"]["computed"]) == e2e


def _hashes(root) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).digest()
            for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def _mla_moe_cell(lora_on_experts: bool):
    """A model of MLA and MoE layers: one MLA + dense layer, then two MLA +
    MoE layers, at the program's ``deepseek-v3-671b`` smoke sizes, with 8
    experts in place of its 4 (the fleet's 1-bit side
    packs 8 features a byte, and the router's LoRA has one output per
    expert) and a capacity factor of ``n_experts``, so that no token drops.
    Neither the MTP head nor int8 expert storage is set."""
    mc = {"name": "mla-moe", "source": "test", "family": "moe",
          "n_layers": 3, "d_model": 128, "n_heads": 4, "n_kv_heads": 4,
          "d_ff": 256, "vocab": 512, "rope_theta": 10000.0,
          "tie_embeddings": False, "lora_rank": 16, "lora_alpha": 32.0,
          "dtype": "float32", "norm": "rmsnorm",
          "blocks": [{"count": 1, "pattern": ["mla"], "ffn": ["dense"]},
                     {"count": 2, "pattern": ["mla"], "ffn": ["moe"]}],
          "mla": {"q_lora_rank": 32, "kv_lora_rank": 16, "rope_head_dim": 8,
                  "nope_head_dim": 16, "v_head_dim": 16},
          "moe": {"n_experts": 8, "top_k": 2, "d_ff_expert": 64,
                  "n_shared": 1, "capacity_factor": 8.0,
                  "lora_on_experts": lora_on_experts}}
    with open(os.path.join(BENCH, "traffic", "chat-zipf.json")) as f:
        mix = json.load(f)
    mix["rehearsal"].update(prompt_tokens=[16], rate_per_s=4.0)
    mix["rehearsal"]["fleet"]["adapters"] = 4
    mix["rehearsal"]["server"].update(max_rows=2, hbm_slots=2)
    return mc, mix


def _add_kinds(blocks, kinds) -> set:
    """Copy ``tests/data/blocks/<kind>.py`` into the ``blocks`` directory for
    each of ``kinds`` whose module it does not hold yet, as the PR that adds
    a model's first layer of a kind writes it; give the copied files' paths
    relative to ``bench/``."""
    copied = set()
    for kind in sorted(kinds):
        if not (blocks / f"{kind}.py").exists():
            shutil.copy(os.path.join(BENCH, "tests", "data", "blocks",
                                     f"{kind}.py"), blocks)
            copied.add(f"blocks/{kind}.py")
    return copied


def test_add_kinds_copies_only_absent_modules(tmp_path):
    """A kind whose module the checkout has keeps it; an absent one is
    copied from the drafts."""
    (tmp_path / "mla.py").write_text("# the checkout's own\n")
    assert _add_kinds(tmp_path, {"mla", "moe"}) == {"blocks/moe.py"}
    assert (tmp_path / "mla.py").read_text() == "# the checkout's own\n"
    with open(os.path.join(BENCH, "tests", "data", "blocks", "moe.py")) as f:
        assert (tmp_path / "moe.py").read_text() == f.read()
    assert _add_kinds(tmp_path, {"mla", "moe"}) == set()


@pytest.mark.parametrize("lora_on_experts,present", [
    (False, ()), (True, ()), (False, ("mla", "moe"))],
    ids=["shared-lora", "expert-lora", "kinds-present"])
def test_a_model_of_new_kinds_added_as_files_only(tmp_path, lora_on_experts,
                                                  present):
    """Kinds of layer (``blocks/mla.py``, ``blocks/moe.py``), a
    configuration that groups them, a mix and a cell, added as new files to
    a copy of the checkout: the benchmark's trees match the program's
    ``Model.init``, its fleet matches ``AdapterStore.register``'s layout,
    and a short window serves every request, with no file of the harness
    edited. A kind's module is added only where the checkout has none:
    ``present`` puts the drafts in the copy first, as a checkout holds
    them once a model of those kinds is in the benchmark."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    blocks = tmp_path / "bench" / "blocks"
    _add_kinds(blocks, present)
    before = _hashes(tmp_path / "bench")

    spec = _cells()
    mc, mix = _mla_moe_cell(lora_on_experts)
    cell = "mla-moe-chat"
    spec["configs"].append({"name": "mla-moe", "source": "test",
                            "file": "bench/configs/mla-moe.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": cell, "config": "mla-moe",
                              "traffic": "chat-short", "chips": 1,
                              "why": "test"})
    data = {"configs/mla-moe.json": mc, "traffic/chat-short.json": mix,
            f"limits/{cell}.json": {"logit_gap": 1.0}}
    for name, content in data.items():
        (tmp_path / "bench" / name).write_text(json.dumps(content))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    kinds = {k for g in mc["blocks"] for k in g["pattern"] + g["ffn"]}
    copied = _add_kinds(blocks, kinds)
    assert copied == {f"blocks/{k}.py" for k in ("mla", "moe")
                      if k not in present}

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               BENCH_SPEC=str(tmp_path / "BENCHMARK.json"),
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "tests" /
                             "serve_new_kinds.py"), cell, "5", "3"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])

    after = _hashes(tmp_path / "bench")
    assert {p: after.get(p) for p in before} == before
    assert set(after) - set(before) == {*copied, *data}
    assert out["base"] and out["lora"] and out["fleet"]
    assert out["requests"] > 0 and out["done"] == out["requests"]
    assert out["bad"] == 0


def test_calibrate_reads_program_control_and_faults():
    """``calibrate.py`` at rehearsal sizes: the program's reading passes the
    harness's decision; the control's and every planted fault's fail it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_REHEARSAL="1")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "calibrate.py"), "--workload",
         "olmo1b-chat-zipf", "--seconds", "3", "--seeds", "4",
         "--fault-seeds", "4"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.strip().splitlines()]
    correct = {x["run"]: x["correct"] for x in lines}
    assert correct.pop("program") is True
    assert correct == {"control": False, "cache_unchanged": False,
                       "half_batch": False, "page_not_written": False,
                       "token_altered": False}
