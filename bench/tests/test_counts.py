"""Operation and byte counts worked out by hand on small shapes, and the
peak table."""

import pytest

import counts
import layout
import peaks

# one tiny decoder: d 8, 2 heads of 4 (1 KV head), d_ff 16, vocab 10, rank 2
MC = {"n_layers": 3, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
      "head_dim": 4, "d_ff": 16, "vocab": 10, "lora_rank": 2,
      "blocks": [{"count": 3, "pattern": ["attn"], "ffn": ["dense"]}]}
# the same three layers as a group of one two-sub-block layer and a group of
# one single
MC2 = dict(MC, blocks=[
    {"count": 1, "pattern": ["attn", "attn"], "ffn": ["dense", "dense"]},
    {"count": 1, "pattern": ["attn"], "ffn": ["dense"]}])


def test_path_shapes():
    names = {"wq": (8, 8), "wk": (8, 4), "wv": (8, 4), "wo": (8, 8),
             "wg": (8, 16), "wu": (8, 16), "wd": (16, 8)}
    role = {n: "mixer" if n in ("wq", "wk", "wv", "wo") else "ffn"
            for n in names}
    assert {lin.path: (lin.k, lin.m) for lin in layout.linears(MC)} == {
        f"/groups/0/sub_0/{role[n]}/{n}": s for n, s in names.items()}


def test_dense_flops_per_token():
    # projections: 64+32+32+64+128+128+128 = 576 MACs; LoRA r(in+out):
    # 2·(16+12+12+16+24+24+24) = 256 MACs; per layer 2·(576+256) = 1664
    # FLOPs; 3 layers = 4992; head 2·10·8 = 160
    assert counts.dense_flops_per_token(MC) == 4992 + 160


def test_attention_and_decode_flops():
    # 4 · layers · heads · head_dim · keys = 4·3·2·4 = 96 per key
    assert counts.attention_flops(MC, 5) == 480
    assert counts.decode_step_flops(MC, 2, 5 + 7) == 2 * 5152 + 96 * 12
    assert counts.decode_step_flops(MC, 0, 0) == 0


def test_prefill_flops():
    # 3 tokens: 3 · 5152 dense + causal keys 1+2+3 = 6 → 96 · 6
    assert counts.prefill_flops(MC, [3]) == 3 * 5152 + 576
    assert counts.prefill_flops(MC, [3, 1]) == 4 * 5152 + 576 + 96


def test_sgmv_call():
    # k 256, m 128, rank 16, 2-bit high side, groups of 128, 4 rows, 2
    # adapters. FLOPs: 2 sides · 2·4·(256·16 + 16·128) = 98304.
    # Bytes: x 2·4·256 = 2048; y 4·4·128 = 2048; per adapter codes
    # 16·384·2/8 + 16·384/8 = 1536 + 768; groups 2·2 + 2·1 = 6 per row, 16
    # rows, 8 bytes each (scale + zero) = 768 → 3072 per adapter.
    assert counts.sgmv_adapter_bytes(256, 128, 16, 2, 128) == 3072
    assert counts.sgmv_call(4, 256, 128, 16, 2, 128, 2) == (
        98304, 2048 + 2048 + 2 * 3072)


def test_sgmv_decode_step_sums_paths_and_layers():
    f, b = counts.sgmv_decode_step(MC, 2, 1, 2, 128)
    want_f = want_b = 0
    for lin in layout.linears(MC):
        ff, bb = counts.sgmv_call(2, lin.k, lin.m, 2, 2, 128, 1)
        want_f, want_b = want_f + ff, want_b + bb
    assert (f, b) == (3 * want_f, 3 * want_b)


def test_two_groups_two_subs_count_as_their_layers():
    """Counts walk every group and sub-block: 21 linears in three layers,
    each counted once, and the same totals as one group of three."""
    lins = layout.linears(MC2)
    assert len(lins) == 21 and {lin.count for lin in lins} == {1}
    assert [lin.path for lin in lins][6:9] == [
        "/groups/0/sub_0/ffn/wd", "/groups/0/sub_1/mixer/wq",
        "/groups/0/sub_1/mixer/wk"]
    assert counts.dense_flops_per_token(MC2) == 4992 + 160
    assert counts.attention_flops(MC2, 5) == 480
    assert counts.decode_step_flops(MC2, 2, 12) == 2 * 5152 + 96 * 12
    assert counts.prefill_flops(MC2, [3, 1]) == 4 * 5152 + 576 + 96
    assert counts.sgmv_decode_step(MC2, 2, 1, 2, 128) == \
        counts.sgmv_decode_step(MC, 2, 1, 2, 128)


def test_roofline_names_its_bound():
    peak = peaks.peaks("TPU v5 lite")
    t, bound = counts.roofline_seconds(197e12, 1.0, peak)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = counts.roofline_seconds(1.0, 819e9, peak)
    assert (t, bound) == (pytest.approx(1.0), "memory")


def test_peaks_v5e_and_unknown_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
