"""The model side follows a configuration's layer groups and kinds.

For ``olmo-1b`` the weights, the fleet's codes, the reference's logits and
the counts are those the benchmark made before it read ``blocks``: the
digests and values below were computed with the single-group code that
hard-coded OLMo's layout (``wq wk wv wo wg wu wd`` in one ``sub_0``), on the
CPU, and every change of the layout code has to give them again, bit for
bit."""

import hashlib
import json
import os
from pathlib import Path

import jax
import numpy as np
import pytest

import counts
import harness
import layout
import weights
from repro.core import LoRAQuantConfig
from repro.serving.engine import AdapterStore
from serve_new_kinds import entry_shapes

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA_BLOCKS = Path(BENCH) / "tests" / "data" / "blocks"
SEED = 2147483700


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _olmo(rehearsal: bool, **changes) -> dict:
    with open(os.path.join(BENCH, "configs", "olmo-1b.json")) as f:
        return dict(harness._rehearsal(json.load(f), rehearsal), **changes)


def _recipe() -> dict:
    with open(os.path.join(BENCH, "traffic", "chat-zipf.json")) as f:
        return json.load(f)["rehearsal"]["fleet"]["recipe"]


@pytest.mark.parametrize("dtype, seed, want", [
    ("float32", SEED,
     "97c3cb306caa89313c8d2d0fb7c9a16df2722a014d4613f6433a39d4ccc34ff2"),
    ("bfloat16", 7,
     "2cbf0ed739469b9ff7ccc86b7151f09fe8d80bba0cd191d18ff908ed71bc0c45"),
])
def test_olmo_base_weights_match_the_single_group_layout(dtype, seed, want):
    base = weights.base_params(_olmo(True, dtype=dtype), seed)
    assert _digest(jax.tree_util.tree_leaves(base)) == want


def test_olmo_fleet_codes_match_the_single_group_layout():
    mc = _olmo(True)
    codes = weights.fleet_codes(mc, _recipe(), SEED, range(8))
    paths = [lin.path for lin in layout.linears(mc)]
    assert [p.rsplit("/", 1)[1] for p in paths] == [
        "wq", "wk", "wv", "wo", "wg", "wu", "wd"]
    assert _digest([codes[p][f] for p in paths for f in sorted(codes[p])]) \
        == "d7a07b8f2d5f9c16572900ce446ac27b01f5f7d434f8e43e02f08f1f73cf5adc"


@pytest.mark.parametrize("mode, want", [
    ("f32",
     "32470241912a46eb109aafeddb84cc7ca16c5bade0631bcf885c1c076ef60327"),
    ("fp8",
     "b757d006718b3d09fb41f1247d7ec79391dbfe53b4f3077d678e4ccf0addfd07"),
])
def test_olmo_reference_logits_match_the_single_group_layout(mode, want):
    mc = _olmo(True)
    ref = layout.load(harness.CODE / "references" / "dense_decoder.py")
    base = weights.base_params(mc, SEED)
    lora = ref.adapters(mc, _recipe(), SEED, [3, 0])
    tokens = (np.arange(2 * 16).reshape(2, 16) * 37 + 5) % mc["vocab"]
    where = np.array([[3, 15], [0, 8]])
    logits = np.asarray(ref.logits(mc, base, lora, tokens, where, mode=mode))
    assert _digest([logits]) == want


# full olmo-1b widths
@pytest.mark.parametrize("fn, args, want", [
    ("dense_flops_per_token", (), 2377646080),
    ("attention_flops", (1,), 131072),
    ("decode_step_flops", (1, 129), 2394554368),
    ("decode_step_flops", (16, 4800), 38671482880),
    ("decode_step_flops", (7, 2000), 16905666560),
    ("prefill_flops", ([128],), 305420828672),
    ("prefill_flops", ([128, 256, 256],), 1531399110656),
    ("sgmv_decode_step", (16, 9, 2, 128), (771751936, 92012544)),
    ("sgmv_decode_step", (1, 1, 2, 128), (48234496, 8388608)),
    ("sgmv_decode_step", (3, 2, 2, 128), (144703488, 19136512)),
])
def test_olmo_counts_match_the_single_group_layout(fn, args, want):
    assert getattr(counts, fn)(_olmo(False), *args) == want


def test_blocks_must_hold_n_layers():
    mc = _olmo(True, n_layers=3)
    with pytest.raises(ValueError, match="hold 2 layers"):
        layout.groups(mc)
    with pytest.raises(ValueError, match="hold 2 layers"):
        harness.model_config(mc)


def _test_kinds(monkeypatch):
    """Kinds of ``blocks/`` and those of the tests' data (``mla``, ``moe``)."""
    kind = layout.kind

    def find(name):
        path = DATA_BLOCKS / f"{name}.py"
        return layout.load(path) if path.exists() else kind(name)

    monkeypatch.setattr(layout, "kind", find)


def test_paths_that_share_a_last_name_all_reach_the_fleet(monkeypatch):
    """A MoE layer's ``ffn/shared/wg`` and ``ffn/experts/wg`` are two
    linears: both reach every adapter, one entry per layer and expert, as
    ``AdapterStore.register`` lays out a float adapter of the same
    template."""
    _test_kinds(monkeypatch)
    mc = _olmo(True, n_layers=2, blocks=[
        {"count": 2, "pattern": ["attn"], "ffn": ["moe"]}],
        moe={"n_experts": 8, "top_k": 2, "d_ff_expert": 64, "n_shared": 1,
             "lora_on_experts": True})
    traffic = {"fleet": {"adapters": 1, "recipe": _recipe()}}
    cell = harness.Cell(name="t", spec={}, mc=mc, traffic=traffic, limits={},
                        chips=1)
    recipe = LoRAQuantConfig(**_recipe())
    entries = harness._fleet(cell, recipe, SEED)[0].entries
    shared, experts = "/groups/0/sub_0/ffn/shared/wg", \
        "/groups/0/sub_0/ffn/experts/wg"
    assert len(entries[shared]) == 2 and len(entries[experts]) == 2 * 8
    assert set(entries) == {lin.path for lin in layout.linears(mc)}

    rng = np.random.default_rng(0)
    floats = jax.tree_util.tree_map(
        lambda s: (rng.normal(size=s.shape) * 0.02).astype(np.float32),
        weights.lora_template(mc))
    stored = AdapterStore(default_recipe=recipe).register("f", floats)
    assert entry_shapes(entries) == entry_shapes(stored.entries)
