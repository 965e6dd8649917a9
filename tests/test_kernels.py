"""Pallas kernel validation against the pure-jnp oracle (in the Pallas
interpreter on a CPU backend): shape/dtype sweeps for the fused dequant
matmuls and the SGMV variants."""

import jax.numpy as jnp
import numpy as np
import pytest

import dataclasses

import jax

from repro.core import LoRAQuantConfig, quantize_lora
from repro.core.quant import (
    binary_quantize,
    rtn_quantize,
    storage_bits,
    unpack_codes,
)
from repro.kernels.quant_matmul.ops import (
    FUSED_VMEM_BUDGET,
    _fused_vmem_estimate,
    _kernel_layout,
    lora_apply_quantized,
    pack_adapter_layers,
    sgmv_apply,
    sgmv_apply_packed,
    stack_packed_adapters,
)
from repro.kernels.quant_matmul.kernel import (
    LAUNCH_COUNTS,
    _unpack_dequant_grouped,
    matmul_out,
    matmul_rhs,
    reset_launch_counts,
)
from repro.kernels.quant_matmul.ref import (
    ref_lora_apply,
    ref_quant_matmul_out,
    ref_quant_matmul_rhs,
    ref_sgmv,
)

SHAPES = [(16, 256, 128), (37, 512, 256), (128, 1024, 384), (8, 128, 2048)]


def _rand(shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape).astype(np.float32) * 0.05).astype(dtype)


@pytest.mark.slow
@pytest.mark.parametrize("t,k,m", SHAPES)
@pytest.mark.parametrize("mode,bits", [("rtn", 2), ("rtn", 4), ("binary", 1)])
@pytest.mark.parametrize("xdtype", [jnp.float32, jnp.bfloat16])
def test_matmul_rhs_vs_ref(t, k, m, mode, bits, xdtype):
    r = 16
    a = _rand((r, k), jnp.float32, seed=bits)
    q = (rtn_quantize(a, bits, 128, axis=1) if mode == "rtn"
         else binary_quantize(a, 128, axis=1))
    x = _rand((t, k), xdtype, seed=t)
    codes, scale, zero, _ = _kernel_layout(q)
    tp = -(-t // 8) * 8
    xp = jnp.pad(x, ((0, tp - t), (0, 0)))
    got = matmul_rhs(xp, codes, scale, zero, bits=q.bits,
                     binary=(mode == "binary"), tile_t=8)[:t]
    want = ref_quant_matmul_rhs(x.astype(jnp.float32), q)
    np.testing.assert_allclose(np.asarray(got[:, :r]), np.asarray(want),
                               rtol=2e-2 if xdtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2 if xdtype == jnp.bfloat16 else 1e-5)


@pytest.mark.slow
@pytest.mark.parametrize("t,k,m", SHAPES[:3])
@pytest.mark.parametrize("mode", ["rtn", "binary"])
def test_matmul_out_vs_ref(t, k, m, mode):
    r = 16
    bt = _rand((r, m), jnp.float32, seed=7)
    q = (rtn_quantize(bt, 2, 128, axis=1) if mode == "rtn"
         else binary_quantize(bt, 128, axis=1))
    h = _rand((t, r), jnp.float32, seed=5)
    codes, scale, zero, _ = _kernel_layout(q)
    hp = jnp.pad(h, ((0, -(-t // 8) * 8 - t), (0, codes.shape[0] - r)))
    got = matmul_out(hp, codes, scale, zero, bits=q.bits,
                     binary=(mode == "binary"), tile_t=8)[:t]
    want = ref_quant_matmul_out(h, q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("rho,bits_high", [(0.8, 2), (0.9, 2), (0.9, 3)])
def test_lora_apply_full_pipeline(rho, bits_high):
    rng = np.random.default_rng(0)
    m, n, r = 384, 512, 16
    u = np.linalg.qr(rng.normal(size=(m, r)))[0]
    v = np.linalg.qr(rng.normal(size=(n, r)))[0]
    s = np.exp(-0.4 * np.arange(r))
    b = jnp.asarray((u * np.sqrt(s)).astype(np.float32))
    a = jnp.asarray((np.sqrt(s)[:, None] * v.T).astype(np.float32))
    ql = quantize_lora(b, a, LoRAQuantConfig(rho=rho, bits_high=bits_high,
                                             ste_steps=0))
    x = _rand((23, n), jnp.float32, seed=9)
    got = lora_apply_quantized(x, ql)
    want = x @ ql.delta_w().T
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("mode", ["rtn", "binary"])
@pytest.mark.parametrize("segs", [
    [0, 1, 2, 1],
    [2, 2, 0],
    [1],
])
def test_sgmv_vs_ref(mode, segs):
    rng = np.random.default_rng(1)
    m, n, r, tile = 256, 384, 16, 8
    qas, qbts = [], []
    for i in range(3):
        a = _rand((r, n), jnp.float32, seed=10 + i)
        b = _rand((m, r), jnp.float32, seed=20 + i)
        if mode == "rtn":
            qas.append(rtn_quantize(a, 2, 128, axis=1))
            qbts.append(rtn_quantize(b, 2, 128, axis=0))
        else:
            qas.append(binary_quantize(a, 128, axis=1))
            qbts.append(binary_quantize(b, 128, axis=0))
    seg_ids = np.repeat(segs, tile)
    x = _rand((len(seg_ids), n), jnp.float32, seed=3)
    seg_map = jnp.asarray(np.asarray(segs, np.int32))
    got = sgmv_apply(x, qas, qbts, seg_map, tile_t=tile)
    want = ref_sgmv(x, qas, qbts, seg_ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_kernel_layout_rank_padding():
    a = _rand((3, 256), jnp.float32)   # rank 3 → padded to 8
    q = rtn_quantize(a, 2, 128, axis=1)
    codes, scale, zero, r = _kernel_layout(q)
    assert codes.shape[0] == 8 and r == 3
    assert float(jnp.abs(scale[3:]).max()) == 0.0


# --------------------------------------------------------------------------
# fused single-pass kernels
# --------------------------------------------------------------------------

def _decayed_qlora(m, n, r, *, rho=0.9, bits_high=2, group_size=128,
                   decay=0.4, seed=0):
    rng = np.random.default_rng(seed)
    u = np.linalg.qr(rng.normal(size=(m, r)))[0]
    v = np.linalg.qr(rng.normal(size=(n, r)))[0]
    s = np.exp(-decay * np.arange(r))
    b = jnp.asarray((u * np.sqrt(s)).astype(np.float32))
    a = jnp.asarray((np.sqrt(s)[:, None] * v.T).astype(np.float32))
    return quantize_lora(b, a, LoRAQuantConfig(
        rho=rho, bits_high=bits_high, group_size=group_size, ste_steps=0))


@pytest.mark.slow
@pytest.mark.parametrize("bits_high", [2, 3, 4])
@pytest.mark.parametrize("rho", [0.8, 1.0])     # rho=1.0 → h == r, no low part
@pytest.mark.parametrize("t", [23, 64])         # non-multiple + multiple of tile
def test_fused_lora_apply(bits_high, rho, t):
    m, n, r = 384, 512, 16
    ql = _decayed_qlora(m, n, r, rho=rho, bits_high=bits_high, seed=bits_high)
    assert (ql.a_low is None) == (rho == 1.0)
    x = _rand((t, n), jnp.float32, seed=t)
    got = lora_apply_quantized(x, ql, fused=True)
    want = x @ ql.delta_w().T
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    # the legacy two-pass path now covers every width the fused path does
    # (group-aware unpack ported, incl. 3-bit uint32) — sweep parity on both
    two_pass = lora_apply_quantized(x, ql, fused=False)
    assert float(jnp.max(jnp.abs(got - two_pass))) <= 1e-3


def test_fused_binary_low_path_contributes():
    # rho low enough that most energy sits in the binary sub-LoRA
    ql = _decayed_qlora(256, 256, 16, rho=0.3, decay=0.1, seed=5)
    assert ql.a_low is not None
    x = _rand((16, 256), jnp.float32, seed=1)
    got = lora_apply_quantized(x, ql, fused=True)
    want = x @ ql.delta_w().T
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_fused_is_single_pallas_call():
    ql = _decayed_qlora(256, 384, 16, rho=0.8)
    assert ql.a_low is not None
    x = _rand((16, 384), jnp.float32)
    reset_launch_counts()
    lora_apply_quantized(x, ql, fused=True)
    assert dict(LAUNCH_COUNTS) == {"fused_lora": 1}
    reset_launch_counts()
    lora_apply_quantized(x, ql, fused=False)
    assert dict(LAUNCH_COUNTS) == {"matmul_rhs": 2, "matmul_out": 2}

    ql_hi = _decayed_qlora(256, 384, 16, rho=1.0)   # h == r: no low factors
    assert ql_hi.a_low is None
    reset_launch_counts()
    lora_apply_quantized(x, ql_hi, fused=True)
    assert dict(LAUNCH_COUNTS) == {"fused_lora": 1}
    reset_launch_counts()
    lora_apply_quantized(x, ql_hi, fused=False)
    assert dict(LAUNCH_COUNTS) == {"matmul_rhs": 1, "matmul_out": 1}


@pytest.mark.parametrize("mode", ["rtn", "binary"])
def test_sgmv_fused_vs_two_pass(mode):
    rng = np.random.default_rng(4)
    m, n, r, tile = 256, 384, 16, 8
    qas, qbts = [], []
    for i in range(3):
        a = _rand((r, n), jnp.float32, seed=30 + i)
        b = _rand((m, r), jnp.float32, seed=40 + i)
        if mode == "rtn":
            qas.append(rtn_quantize(a, 2, 128, axis=1))
            qbts.append(rtn_quantize(b, 2, 128, axis=0))
        else:
            qas.append(binary_quantize(a, 128, axis=1))
            qbts.append(binary_quantize(b, 128, axis=0))
    segs = [1, 0, 2, 2]
    seg_ids = np.repeat(segs, tile)
    x = _rand((len(seg_ids), n), jnp.float32, seed=6)
    seg_map = jnp.asarray(np.asarray(segs, np.int32))

    reset_launch_counts()
    fused = sgmv_apply(x, qas, qbts, seg_map, tile_t=tile,
                       fused=True)
    assert dict(LAUNCH_COUNTS) == {"sgmv_fused": 1}
    reset_launch_counts()
    two = sgmv_apply(x, qas, qbts, seg_map, tile_t=tile,
                     fused=False)
    assert dict(LAUNCH_COUNTS) == {"sgmv_rhs": 1, "sgmv_out": 1}
    np.testing.assert_allclose(np.asarray(fused), np.asarray(two),
                               rtol=1e-5, atol=1e-3)
    want = ref_sgmv(x, qas, qbts, seg_ids)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# packed heterogeneous batches (both sub-LoRAs, mixed h, one pallas_call)
# --------------------------------------------------------------------------

def _packed_layer(qls, tile_t):
    """NA single-layer QuantizedLoRAs → per-layer PackedLoRABatch (NA, Rp, ·)."""
    pb = stack_packed_adapters([pack_adapter_layers([q]) for q in qls],
                               tile_t=tile_t)
    return jax.tree_util.tree_map(lambda x: x[0], pb)   # drop the L axis


@pytest.mark.parametrize(
    "bits_high",
    [2, pytest.param(3, marks=pytest.mark.slow)])  # 3-bit: slow interpreted
def test_sgmv_packed_mixed_h_vs_ref(bits_high):
    """Mixed-adapter apply straight from packed codes: adapters with
    DIFFERENT split indices h (incl. one with no binary part at all) in one
    batch must match the per-adapter oracle, in one pallas_call."""
    m, n, r, tile = 256, 384, 16, 8
    qls = [
        _decayed_qlora(m, n, r, rho=0.8, bits_high=bits_high, seed=50),
        _decayed_qlora(m, n, r, rho=0.95, bits_high=bits_high, decay=0.2,
                       seed=51),
        _decayed_qlora(m, n, r, rho=1.0, bits_high=bits_high, seed=52),
    ]
    hs = {q.h for q in qls}
    assert len(hs) > 1 and qls[2].a_low is None
    segs = [1, 0, 2, 1, 2]
    seg_rows = jnp.asarray(np.repeat(segs, tile).astype(np.int32))
    x = _rand((len(segs) * tile, n), jnp.float32, seed=60)

    pb = dataclasses.replace(_packed_layer(qls, tile), seg=seg_rows)
    reset_launch_counts()
    got = sgmv_apply_packed(x, pb, scaling=1.5)
    assert dict(LAUNCH_COUNTS) == {"sgmv_fused": 1}

    want = np.zeros((x.shape[0], m), np.float32)
    for i, a in enumerate(np.repeat(segs, tile)):
        want[i] = 1.5 * np.asarray(x[i] @ qls[a].delta_w().T)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_sgmv_packed_decode_tile_one():
    """tile_t=1 — the decode shape: every row its own adapter, unsorted."""
    m, n, r = 128, 256, 8
    qls = [_decayed_qlora(m, n, r, rho=0.7, seed=70 + i, decay=0.2 * (i + 1))
           for i in range(3)]
    seg = jnp.asarray(np.asarray([2, 0, 1, 0], np.int32))
    x = _rand((4, n), jnp.float32, seed=71)
    pb = dataclasses.replace(_packed_layer(qls, 1), seg=seg)
    got = sgmv_apply_packed(x, pb)
    for i, a in enumerate(np.asarray(seg)):
        want = np.asarray(x[i] @ qls[a].delta_w().T)
        np.testing.assert_allclose(np.asarray(got[i]), want,
                                   rtol=1e-4, atol=1e-4)


def test_sgmv_packed_requires_seg():
    qls = [_decayed_qlora(128, 256, 8, seed=80)]
    pb = _packed_layer(qls, 8)
    x = _rand((8, 256), jnp.float32)
    with pytest.raises(ValueError, match="segment ids"):
        sgmv_apply_packed(x, pb)


def test_sgmv_packed_folded_expert_axis_vs_ref():
    """Extra-lead-dim leaves (MoE per-expert adapters): entries packed with
    fold=E land at index a·E + e of the stacked adapter axis, and folded
    seg ids gather exactly the (adapter, expert) codes — the layout the MoE
    dispatch consumes at tile_t=1."""
    m, n, r, e_dim, na = 128, 256, 8, 3, 2
    qls = [[_decayed_qlora(m, n, r, rho=0.8 + 0.05 * e, seed=90 + 10 * a + e)
            for e in range(e_dim)] for a in range(na)]
    # per adapter: one layer × E experts in row-major (layer, expert) order
    entries = [pack_adapter_layers(qls[a], fold=e_dim) for a in range(na)]
    assert entries[0].ah_codes.shape[:2] == (1, e_dim)   # (L, fold, Rp, ·)
    pb = stack_packed_adapters(entries, tile_t=1)
    assert pb.fold == e_dim
    assert pb.ah_codes.shape[:2] == (1, na * e_dim)      # (L, NA·fold, ·)
    pb = jax.tree_util.tree_map(lambda x: x[0], pb)      # drop the L axis

    pairs = [(1, 2), (0, 0), (1, 0), (0, 1)]             # (adapter, expert)
    folded = jnp.asarray(np.asarray([a * e_dim + e for a, e in pairs],
                                    np.int32))
    x = _rand((len(pairs), n), jnp.float32, seed=91)
    got = sgmv_apply_packed(x, dataclasses.replace(pb, seg=folded))
    for i, (a, e) in enumerate(pairs):
        want = np.asarray(x[i] @ qls[a][e].delta_w().T)
        np.testing.assert_allclose(np.asarray(got[i]), want,
                                   rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# kernel layout: whole bytes per code, exact round trip
# --------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [1, 2, 3, 4])
def test_plane_layout_bits_per_code(bits):
    """The bit-plane-major kernel layout spends exactly ``bits`` per code
    (K = 3072, 24 groups of 128) and dequantizes to what the stored codes
    dequantize to."""
    a = _rand((16, 3072), jnp.float32, seed=bits)
    q = (binary_quantize(a, 128, axis=1) if bits == 1
         else rtn_quantize(a, bits, 128, axis=1))
    codes, scale, zero, _ = _kernel_layout(q)
    assert codes.dtype == jnp.uint8
    assert codes.size * 8 == a.size * bits
    assert storage_bits(q) == a.size * bits + scale.size * (
        16 + (0 if bits == 1 else bits))
    got = _unpack_dequant_grouped(codes, scale, None if bits == 1 else zero,
                                  bits, 128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(q.dequantize()))
    assert unpack_codes(q.codes, q.bits, 128).shape == (16, 24, 128)


# --------------------------------------------------------------------------
# VMEM: the fused kernel keeps whole (tile_t, K) / (tile_t, M) tiles, so a
# wide output shrinks the token tile instead of leaving the fused path
# --------------------------------------------------------------------------

def test_fused_vmem_shrinks_token_tile():
    # synthetic large-M shape: the (128, M) output tile alone is
    # 128·32768·4 B = 16 MB > FUSED_VMEM_BUDGET
    m, n, r = 32768, 256, 8
    ql = _decayed_qlora(m, n, r, rho=1.0, seed=13)
    assert _fused_vmem_estimate(ql, 128) > FUSED_VMEM_BUDGET
    assert _fused_vmem_estimate(ql, 16) <= FUSED_VMEM_BUDGET
    x = _rand((128, n), jnp.float32, seed=14)
    reset_launch_counts()
    got = lora_apply_quantized(x, ql, fused=True)
    assert dict(LAUNCH_COUNTS) == {"fused_lora": 1}
    want = x @ ql.delta_w().T
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got[:, :512]),
                               np.asarray(want[:, :512]),
                               rtol=1e-4, atol=1e-4)


def test_fused_vmem_guard_keeps_fused_for_normal_shapes():
    ql = _decayed_qlora(384, 512, 16, rho=0.8, seed=15)
    assert _fused_vmem_estimate(ql, 128) <= FUSED_VMEM_BUDGET
    x = _rand((16, 512), jnp.float32, seed=16)
    reset_launch_counts()
    lora_apply_quantized(x, ql, fused=True)
    assert dict(LAUNCH_COUNTS) == {"fused_lora": 1}


def test_odd_k_apply_regression():
    # K = 2112 with 64-wide groups: the pre-fix `max(tile_k, 128)` silently
    # dropped the last 64 columns of every K tile sweep.
    k = 2112
    ql = _decayed_qlora(256, k, 8, rho=0.9, group_size=64, seed=7)
    x = _rand((9, k), jnp.float32, seed=8)
    want = x @ ql.delta_w().T
    for fused in (True, False):
        got = lora_apply_quantized(x, ql, fused=fused)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
