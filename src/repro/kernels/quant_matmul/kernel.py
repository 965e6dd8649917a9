"""Pallas TPU kernels: fused dequantize + skinny matmul for packed LoRA
factors, plus the segment-gathered multi-adapter (SGMV) variant.

TPU adaptation of Punica's CUDA SGMV (DESIGN.md §2): instead of warp-level
gathers, requests are host-bucketed into contiguous *segments* per adapter;
the grid walks token tiles and a scalar-prefetched ``tile→adapter`` map
selects which adapter's packed codes the BlockSpec index_map pulls into
VMEM. Dequantization (a shift-and-mask per quant group, then that group's
scale and zero-point) happens in VMEM/VREGs; only packed bytes cross
HBM→VMEM, so adapter bandwidth is AvgBits/16 of the fp16 path — these
matmuls are memory-bound at decode, so bandwidth is wall-time.

Layout contract (what ``ops._kernel_layout`` builds from
``repro.core.quant`` storage), in brief:
  codes  (R, C) uint8   — :func:`pack_planes`: bit-plane-major bytes, plane
         ``p`` of a row is one contiguous run of its codes; 3-bit codes
         are a 2-bit plus a 1-bit segment
  scale  (R, G) fp32
  zero   (R, G) int32   — RTN only
R is padded to the fp32 sublane multiple (8). The full walkthrough — bit
layouts per width, the rank-padding rules that make heterogeneous-``h``
adapter stacks uniform, and the VMEM budget — lives in
``docs/packed_format.md``.

Two kernel families:

* **two-pass** (``matmul_rhs`` / ``matmul_out``, ``sgmv_rhs`` / ``sgmv_out``)
  — the reference path: one ``pallas_call`` per factor, the rank-R
  intermediate ``h`` round-trips through HBM between them. Uses the same
  unpack as the fused path, so every bit-width the fused kernels serve has
  a two-pass reference.
* **fused single-pass** (``fused_lora`` / ``sgmv_fused``) — ONE
  ``pallas_call`` per layer. Per token tile the kernel unpacks + dequants
  the A and B factors of both sub-LoRAs in VMEM and emits
  ``y = (x @ A_hiᵀ) @ B_hi + (x @ A_loᵀ) @ B_lo`` directly — ``h`` never
  touches HBM and ``x`` is read exactly once.

Every kernel holds whole feature rows: a grid step sees the full K of its
x tile and the full packed factors (a few KB to tens of KB at rank 16), so
the only tiled axis is tokens. ``ops.lora_apply_quantized`` shrinks the
token tile when the fused kernel's VMEM estimate would not fit.
"""

from __future__ import annotations

import collections
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Trace-time kernel-launch accounting. Every kernel builder below records its
# name here once per ``pallas_call`` issued (the apply wrappers in ops.py are
# deliberately unjitted, so one logical apply == one recorded trace). Used by
# tests and benchmarks to assert fused-vs-two-pass launch counts, and by
# the serving telemetry layer (via launch *sinks*) to export the same
# counts as first-class ``pallas_launches_total{kernel=...}`` metrics.
LAUNCH_COUNTS: "collections.Counter[str]" = collections.Counter()

# Registered observers: each is called with the kernel name at every
# recorded launch. Sinks must be cheap and must not raise — they run at
# jit trace time inside kernel builders.
_LAUNCH_SINKS: list = []


def reset_launch_counts() -> None:
    LAUNCH_COUNTS.clear()


def add_launch_sink(sink) -> None:
    """Register a ``sink(name)`` callable observing every kernel launch
    (idempotent: re-adding an already-registered sink is a no-op)."""
    if sink not in _LAUNCH_SINKS:
        _LAUNCH_SINKS.append(sink)


def remove_launch_sink(sink) -> None:
    if sink in _LAUNCH_SINKS:
        _LAUNCH_SINKS.remove(sink)


def _record_launch(name: str) -> None:
    LAUNCH_COUNTS[name] += 1
    for sink in _LAUNCH_SINKS:
        sink(name)


def interpret_mode() -> bool:
    """Whether ``pallas_call`` runs in the Pallas interpreter: natively on
    a TPU backend, interpreted everywhere else (the CPU test runs). Read
    at trace time by every kernel builder below; nothing falls back from
    a failed native compile to the interpreter."""
    return jax.default_backend() != "tpu"


def _planes(bits: int):
    """Byte bit-plane segments ``(width, shift)`` of a ``bits``-wide code:
    one segment for 1/2/4/8 bits; a 3-bit code is a 2-bit plane plus a
    1-bit plane, so every width packs into whole bytes."""
    return ((2, 0), (1, 2)) if bits == 3 else ((bits, 0),)


def _plane_lanes(n: int, width: int) -> int:
    """Bytes per row of a ``width``-bit segment over ``n`` codes: each byte
    carries ``8 // width`` codes, one per plane."""
    return -(-n // (8 // width))


@functools.partial(jax.jit, static_argnames=("bits",))
def pack_planes(q, bits: int):
    """Integer codes ``(R, N)`` → the kernel's packed layout ``(R, C)``
    uint8 (``docs/packed_format.md`` §2).

    Per bit-plane segment of ``L = ceil(N / per)`` bytes a row, byte ``j``
    holds codes ``j, L + j, 2L + j, …`` at bits ``[p·width, (p+1)·width)``:
    plane ``p`` is the contiguous run of codes ``[p·L, (p+1)·L)``. One
    shift-and-mask per plane unpacks it onto consecutive lanes, so no lane
    axis is ever split or interleaved, and a row spends exactly ``bits``
    per code whenever ``per`` divides ``N``."""
    r, n = q.shape
    segs = []
    for width, shift in _planes(bits):
        per = 8 // width
        lanes = _plane_lanes(n, width)
        part = (q.astype(jnp.int32) >> shift) & ((1 << width) - 1)
        part = jnp.pad(part, ((0, 0), (0, lanes * per - n)))
        part = part.reshape(r, per, lanes)
        segs.append(functools.reduce(
            jnp.bitwise_or, [part[:, p] << (width * p) for p in range(per)]))
    return jnp.concatenate(segs, axis=1).astype(jnp.uint8)


def _infer_group(codes, scale, bits: int, group: Optional[int]) -> int:
    """A row holds ``bits`` per code, so the group size follows from the
    code and scale shapes."""
    if group is not None:
        return group
    return codes.shape[-1] * 8 // (bits * scale.shape[-1])


def _unpack_dequant_grouped(codes, scale, zero, bits: int, group: int):
    """Unpack + dequantize: codes (R, C) uint8 → fp32 (R, NG·group).

    Each bit-plane is one shift-and-mask of its segment; the planes join
    along lanes into the row's codes in order (3-bit codes add the 1-bit
    segment above the 2-bit one). Then each of the ``NG`` (=
    scale.shape[1]) quant groups — a ``group``-lane slice — takes its own
    scale column. At ``group == 128`` and lane-tile multiples of codes per
    plane every slice and join is lane-tile aligned.
    """
    ng = scale.shape[1]
    n = ng * group
    w = codes.astype(jnp.int32)
    q, off = None, 0
    for width, shift in _planes(bits):
        lanes = _plane_lanes(n, width)
        seg = w[:, off:off + lanes]
        planes = [(seg >> (width * p)) & ((1 << width) - 1)
                  for p in range(8 // width)]
        part = jnp.concatenate(planes, axis=1)[:, :n]
        q = part if q is None else q | (part << shift)
        off += lanes
    q = q.astype(jnp.float32)
    cols = []
    for gi in range(ng):
        qg = q[:, gi * group:(gi + 1) * group]
        s = scale[:, gi:gi + 1]
        if zero is None:                              # binary: {0,1} → ±scale
            cols.append(s * (qg * 2.0 - 1.0))
        else:
            cols.append(s * (qg - zero[:, gi:gi + 1].astype(jnp.float32)))
    return cols[0] if ng == 1 else jnp.concatenate(cols, axis=1)


def _dot_t(x, w):
    """``x @ w.T`` in fp32 without materializing the transpose."""
    return jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tiles(x, tile_t: int):
    """(T, D) → (T/tile_t, tile_t, D): a token tile is then a block whose
    last two dims equal the array's, legal for any ``tile_t`` (a decode
    tile of one row included)."""
    return x.reshape(x.shape[0] // tile_t, tile_t, x.shape[1])


# --------------------------------------------------------------------------
# single-adapter: h = x @ dequant(A)ᵀ      (A: (R, K) row-grouped)
# --------------------------------------------------------------------------

def _matmul_rhs_kernel(x_ref, codes_ref, scale_ref, zero_ref, o_ref, *,
                       bits: int, binary: bool, group: int, k: int):
    w = _unpack_dequant_grouped(
        codes_ref[...], scale_ref[...],
        None if binary else zero_ref[...], bits, group)   # (R, ≥K)
    o_ref[...] = _dot_t(x_ref[...].astype(jnp.float32), w[:, :k])


def matmul_rhs(x, codes, scale, zero, *, bits: int, binary: bool,
               group: Optional[int] = None, tile_t: int = 128):
    """x (T, K) @ dequant(codes...)ᵀ → (T, R) fp32; T % tile_t == 0. Each
    grid step holds one token tile and the whole packed factor."""
    t, k = x.shape
    r = codes.shape[0]
    tile_t = min(tile_t, t)
    group = _infer_group(codes, scale, bits, group)

    kern = functools.partial(_matmul_rhs_kernel, bits=bits, binary=binary,
                             group=group, k=k)
    _record_launch("matmul_rhs")
    return pl.pallas_call(
        kern,
        grid=(t // tile_t,),
        in_specs=[
            pl.BlockSpec((tile_t, k), lambda i: (i, 0)),
            pl.BlockSpec(codes.shape, lambda i: (0, 0)),
            pl.BlockSpec(scale.shape, lambda i: (0, 0)),
            pl.BlockSpec(zero.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_t, r), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, r), jnp.float32),
        interpret=interpret_mode(),
    )(x, codes, scale, zero)


# --------------------------------------------------------------------------
# single-adapter: y = h @ dequant(Bᵀ)      (Bᵀ: (R, M) row-grouped)
# --------------------------------------------------------------------------

def _matmul_out_kernel(h_ref, codes_ref, scale_ref, zero_ref, o_ref, *,
                       bits: int, binary: bool, group: int):
    w = _unpack_dequant_grouped(
        codes_ref[...], scale_ref[...],
        None if binary else zero_ref[...], bits, group)   # (R, Mp)
    o_ref[...] = jnp.dot(h_ref[...].astype(jnp.float32), w,
                         preferred_element_type=jnp.float32)


def matmul_out(h, codes, scale, zero, *, bits: int, binary: bool,
               group: Optional[int] = None, tile_t: int = 128):
    """h (T, R) @ dequant(codes: (R, M))ᵀ-free → (T, Mp) fp32, where
    ``Mp = n_groups · group`` (== M except when the last quant group is
    padded — callers slice ``[:, :m]``)."""
    t, r = h.shape
    group = _infer_group(codes, scale, bits, group)
    mp = scale.shape[1] * group
    tile_t = min(tile_t, t)

    kern = functools.partial(_matmul_out_kernel, bits=bits, binary=binary,
                             group=group)
    _record_launch("matmul_out")
    return pl.pallas_call(
        kern,
        grid=(t // tile_t,),
        in_specs=[
            pl.BlockSpec((tile_t, r), lambda i: (i, 0)),
            pl.BlockSpec(codes.shape, lambda i: (0, 0)),
            pl.BlockSpec(scale.shape, lambda i: (0, 0)),
            pl.BlockSpec(zero.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_t, mp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, mp), jnp.float32),
        interpret=interpret_mode(),
    )(h, codes, scale, zero)


# --------------------------------------------------------------------------
# SGMV: per-token-tile adapter selection via scalar prefetch
# --------------------------------------------------------------------------

def _adapter_specs(codes, scale, zero):
    """BlockSpecs gathering one adapter's packed side (the scalar-prefetched
    segment map picks which) from an ``(NA, R, ·)`` stack."""
    return [pl.BlockSpec((None,) + a.shape[1:],
                         lambda i, seg: (seg[i], 0, 0))
            for a in (codes, scale, zero)]


def _token_spec(tile_t: int, d: int):
    return pl.BlockSpec((None, tile_t, d), lambda i, seg: (i, 0, 0))


def _sgmv_grid(n_tiles: int, in_specs, out_specs):
    """One grid step per token tile; the segment map is scalar-prefetched
    so the index maps can pick each tile's adapter."""
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(n_tiles,),
        in_specs=in_specs, out_specs=out_specs)


def _sgmv_kernel(seg_map_ref, x_ref, codes_ref, scale_ref, zero_ref, o_ref, *,
                 bits: int, binary: bool, group: int, k: int):
    w = _unpack_dequant_grouped(
        codes_ref[...], scale_ref[...],
        None if binary else zero_ref[...], bits, group)  # (R, ≥K)
    o_ref[...] = _dot_t(x_ref[...].astype(jnp.float32), w[:, :k])


def sgmv_rhs(x, codes, scale, zero, seg_map, *, bits: int, binary: bool,
             group: Optional[int] = None, tile_t: int = 8):
    """Segment-gathered h = x @ Aᵀ with per-tile adapters.

    x (T, K); codes (NA, R, words); seg_map (T/tile_t,) int32 — adapter id of
    each token tile (host-side bucketing pads segments to tile multiples).
    """
    t, k = x.shape
    na, r, _ = codes.shape
    group = _infer_group(codes, scale, bits, group)

    kern = functools.partial(_sgmv_kernel, bits=bits, binary=binary,
                             group=group, k=k)
    grid_spec = _sgmv_grid(
        t // tile_t,
        [_token_spec(tile_t, k), *_adapter_specs(codes, scale, zero)],
        _token_spec(tile_t, r))
    _record_launch("sgmv_rhs")
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t // tile_t, tile_t, r), jnp.float32),
        interpret=interpret_mode(),
    )(seg_map, _tiles(x, tile_t), codes, scale, zero).reshape(t, r)


def _sgmv_out_kernel(seg_map_ref, h_ref, codes_ref, scale_ref, zero_ref,
                     o_ref, *, bits: int, binary: bool, group: int, m: int):
    w = _unpack_dequant_grouped(
        codes_ref[...], scale_ref[...],
        None if binary else zero_ref[...], bits, group)  # (R, ≥M)
    o_ref[...] = jnp.dot(h_ref[...].astype(jnp.float32), w[:, :m],
                         preferred_element_type=jnp.float32)


def sgmv_out(h, codes, scale, zero, seg_map, *, bits: int, binary: bool,
             group: Optional[int] = None, m: Optional[int] = None,
             tile_t: int = 8):
    """Segment-gathered y = h @ dequant(Bᵀ) with per-tile adapters.

    h (T, R); codes (NA, R, words); seg_map (T/tile_t,). ``m`` overrides the
    output width when the last quant group of B is padded."""
    t, r = h.shape
    group = _infer_group(codes, scale, bits, group)
    if m is None:
        m = scale.shape[2] * group

    kern = functools.partial(_sgmv_out_kernel, bits=bits, binary=binary,
                             group=group, m=m)
    grid_spec = _sgmv_grid(
        t // tile_t,
        [_token_spec(tile_t, r), *_adapter_specs(codes, scale, zero)],
        _token_spec(tile_t, m))
    _record_launch("sgmv_out")
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t // tile_t, tile_t, m), jnp.float32),
        interpret=interpret_mode(),
    )(seg_map, _tiles(h, tile_t), codes, scale, zero).reshape(t, m)


# --------------------------------------------------------------------------
# fused single-pass apply: y = (x @ Ahiᵀ) @ Bhi + (x @ Aloᵀ) @ Blo
# in ONE pallas_call — h_hi/h_lo never leave VMEM.
# --------------------------------------------------------------------------

QuantSide = tuple  # (codes (R, C), scale (R, G), zero (R, G))


def fused_lora(
    x,                               # (T, K) — T % tile_t == 0
    a_hi: QuantSide, b_hi: QuantSide,
    a_lo: Optional[QuantSide] = None, b_lo: Optional[QuantSide] = None,
    *,
    m: int,                          # output width (== B's M)
    bits_hi: int, binary_hi: bool,
    bits_lo: int = 1, binary_lo: bool = True,
    group_ah: int, group_bh: int,
    group_al: int = 0, group_bl: int = 0,
    tile_t: int = 128,
):
    """Single-pass fused quantized LoRA apply (see module docstring).

    The grid walks token tiles; every step holds one ``(tile_t, K)`` x tile
    and the whole packed A and B factors (constant index maps: fetched
    once), dequantizes them in VMEM, and emits the ``(tile_t, M)`` output
    tile from ``h = x @ Aᵀ`` without ``h`` touching HBM.
    """
    t, k = x.shape
    has_low = a_lo is not None

    def kernel(*refs):
        if has_low:
            (x_ref, ahc, ahs, ahz, alc, als, alz,
             bhc, bhs, bhz, blc, bls, blz, o_ref) = refs
        else:
            (x_ref, ahc, ahs, ahz, bhc, bhs, bhz, o_ref) = refs
        xf = x_ref[...].astype(jnp.float32)

        def apply(ac, as_, az, bc, bs, bz, bits, binary, ga, gb):
            wa = _unpack_dequant_grouped(
                ac[...], as_[...], None if binary else az[...], bits, ga)
            h = _dot_t(xf, wa[:, :k])                       # (Tt, R)
            wb = _unpack_dequant_grouped(
                bc[...], bs[...], None if binary else bz[...], bits, gb)
            return jnp.dot(h, wb[:, :m], preferred_element_type=jnp.float32)

        acc = apply(ahc, ahs, ahz, bhc, bhs, bhz, bits_hi, binary_hi,
                    group_ah, group_bh)
        if has_low:
            acc += apply(alc, als, alz, blc, bls, blz, bits_lo, binary_lo,
                         group_al, group_bl)
        o_ref[...] = acc

    sides = [*a_hi] + ([*a_lo] if has_low else []) + [*b_hi]
    sides += [*b_lo] if has_low else []
    in_specs = [pl.BlockSpec((tile_t, k), lambda i: (i, 0))]
    in_specs += [pl.BlockSpec(a.shape, lambda i: (0, 0)) for a in sides]
    _record_launch("fused_lora")
    return pl.pallas_call(
        kernel,
        grid=(t // tile_t,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile_t, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, m), jnp.float32),
        interpret=interpret_mode(),
    )(x, *sides)


# --------------------------------------------------------------------------
# fused SGMV: per-token-tile adapter selection AND both matmuls in one kernel
# --------------------------------------------------------------------------

def sgmv_fused(
    x, a_codes, a_scale, a_zero, b_codes, b_scale, b_zero, seg_map, *,
    bits_a: int, binary_a: bool, group_a: int,
    bits_b: int, binary_b: bool, group_b: int,
    a_lo=None, b_lo=None,
    bits_lo: int = 1, binary_lo: bool = True,
    group_al: int = 0, group_bl: int = 0,
    m: Optional[int] = None,
    tile_t: int = 8,
):
    """Single-kernel heterogeneous multi-adapter apply.

    x (T, K); a_codes (NA, R, ·); b_codes (NA, R, ·); seg_map (T/tile_t,)
    int32 adapter id per token tile. The scalar-prefetched ``seg_map`` drives
    the BlockSpec index maps of BOTH factor sides, so each grid step DMAs one
    adapter's packed A and B and computes ``y = (x @ Aᵀ) @ B`` entirely in
    VMEM — the (tile_t, R) ``h`` exists only in registers/VREGs. Token
    tiles are blocks of a ``(T/tile_t, tile_t, K)`` view, so ``tile_t = 1``
    (decode: one row per sequence) is as legal a block as ``tile_t = 8``.

    ``a_lo``/``b_lo`` (each an (NA, R_lo, ·) codes/scale/zero triple) add the
    LoRAQuant binary sub-LoRA in the SAME launch:
    ``y = (x @ A_hiᵀ) @ B_hi + (x @ A_loᵀ) @ B_lo`` — this is the
    serve-from-packed-codes decode path, where a whole mixed-adapter batch of
    both sub-LoRAs is ONE ``pallas_call``. Rank rows padded with zero scales
    (adapters whose split ``h`` differs, or layers with no low part at all)
    dequantize to 0 and contribute nothing, so heterogeneous-``h`` adapter
    stacks are exact.

    ``m`` overrides the output width when the last quant group of B is padded
    (M not a multiple of ``group_b``); the dequantized pad columns are sliced
    off in-kernel before the output dot.
    """
    t, k = x.shape
    has_low = a_lo is not None
    if m is None:
        m = b_scale.shape[2] * group_b

    def kernel(*refs):
        if has_low:
            (seg_map_ref, x_ref, ac, as_, az, bc, bs, bz,
             alc, als, alz, blc, bls, blz, o_ref) = refs
        else:
            (seg_map_ref, x_ref, ac, as_, az, bc, bs, bz, o_ref) = refs
        xf = x_ref[...].astype(jnp.float32)
        wa = _unpack_dequant_grouped(
            ac[...], as_[...], None if binary_a else az[...], bits_a, group_a)
        h = _dot_t(xf, wa[:, :k])                           # (Tt, R)
        wb = _unpack_dequant_grouped(
            bc[...], bs[...], None if binary_b else bz[...], bits_b, group_b)
        acc = jnp.dot(h, wb[:, :m], preferred_element_type=jnp.float32)
        if has_low:
            wal = _unpack_dequant_grouped(
                alc[...], als[...], None if binary_lo else alz[...],
                bits_lo, group_al)
            h_lo = _dot_t(xf, wal[:, :k])                   # (Tt, R_lo)
            wbl = _unpack_dequant_grouped(
                blc[...], bls[...], None if binary_lo else blz[...],
                bits_lo, group_bl)
            acc += jnp.dot(h_lo, wbl[:, :m], preferred_element_type=jnp.float32)
        o_ref[...] = acc

    in_specs = [_token_spec(tile_t, k)]
    in_specs += _adapter_specs(a_codes, a_scale, a_zero)
    in_specs += _adapter_specs(b_codes, b_scale, b_zero)
    operands = [a_codes, a_scale, a_zero, b_codes, b_scale, b_zero]
    if has_low:
        in_specs += _adapter_specs(*a_lo) + _adapter_specs(*b_lo)
        operands += [*a_lo, *b_lo]

    grid_spec = _sgmv_grid(t // tile_t, in_specs,
                           _token_spec(tile_t, m))
    _record_launch("sgmv_fused")
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t // tile_t, tile_t, m), jnp.float32),
        interpret=interpret_mode(),
    )(seg_map, _tiles(x, tile_t), *operands).reshape(t, m)
