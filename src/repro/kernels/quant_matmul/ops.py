"""jit'd public wrappers around the Pallas quant-matmul kernels.

These adapt :class:`repro.core.quant.QuantizedTensor` storage into the
kernel layout (flatten group dims, pad the rank to the fp32 sublane
multiple) and provide the full sub-LoRA application:

    lora_apply_quantized(x, qlora) ≈ x @ qlora.delta_w().T

Whether the kernels run natively or in the Pallas interpreter follows the
JAX backend (``kernel.interpret_mode``): native on a TPU, interpreted on
the CPU test runs.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.loraquant import QuantizedLoRA
from repro.core.quant import QuantizedTensor, unpack_codes

from .kernel import (
    fused_lora,
    matmul_out,
    matmul_rhs,
    pack_planes,
    sgmv_fused,
    sgmv_rhs,
)

SUBLANE = 8

# VMEM the fused single-pass kernel may budget for per grid step (a TPU
# v5e core has 16 MiB of scoped VMEM by default; the rest is headroom for
# the compiler's own scratch). lora_apply_quantized halves the token tile
# until _fused_vmem_estimate fits.
FUSED_VMEM_BUDGET = 12 << 20


def _fused_vmem_estimate(qlora: QuantizedLoRA, tile_t: int) -> int:
    """Upper-bound bytes the fused kernel keeps in VMEM in one grid step:
    the double-buffered ``(tile_t, K)`` x tile and ``(tile_t, M)`` output
    tile plus the fp32 copy of x, and per factor its packed form and its
    fp32 dequantized form."""
    k = qlora.a_high.orig_shape[1]
    m = qlora.b_high.orig_shape[0]
    est = tile_t * k * 4 * 3 + tile_t * m * 4 * 2
    for q in (qlora.a_high, qlora.b_high, qlora.a_low, qlora.b_low):
        if q is not None:
            est += (q.codes.size * q.codes.dtype.itemsize
                    + q.scale.size * 8 + q.scale.shape[0] * (k + m) * 4)
    return est


def _kernel_layout(q: QuantizedTensor, pad_r: Optional[int] = None):
    """QuantizedTensor → (codes (R, C) uint8, scale (R, G), zero (R, G)).

    Works for row-grouped (axis=1) tensors; column-grouped B factors
    (axis=0) are the same buffers viewed as Bᵀ. The stored codes are
    re-packed into the kernel's bit-plane-major layout
    (:func:`~repro.kernels.quant_matmul.kernel.pack_planes`): exactly
    ``bits`` per code, as stored. R is zero-padded to the sublane multiple
    (zero scale rows dequantize to 0 — no effect).
    """
    r, ng = q.scale.shape
    codes = unpack_codes(q.codes, q.bits, q.group_size)
    codes = pack_planes(codes.reshape(r, ng * q.group_size), q.bits)
    scale = q.scale
    zero = q.zero
    rp = pad_r or (-(-r // SUBLANE) * SUBLANE)
    if rp != r:
        codes = jnp.pad(codes, ((0, rp - r), (0, 0)))
        scale = jnp.pad(scale, ((0, rp - r), (0, 0)))
        zero = jnp.pad(zero, ((0, rp - r), (0, 0)))
    return codes, scale, zero, r


def _pad_tokens(x, tile_t):
    t = x.shape[0]
    tp = -(-t // tile_t) * tile_t
    if tp != t:
        x = jnp.pad(x, ((0, tp - t), (0, 0)))
    return x, t


def _side(x, q: QuantizedTensor, tile_t):
    codes, scale, zero, r = _kernel_layout(q)
    h = matmul_rhs(x, codes, scale, zero, bits=q.bits,
                   binary=q.mode == "binary", group=q.group_size,
                   tile_t=tile_t)
    return h, r


def _quant_m(q: QuantizedTensor) -> int:
    """Logical output width of a B factor, whether stored column-grouped
    ``(M, R)`` (axis=0) or as the transposed row-grouped ``(R, M)`` view."""
    return q.orig_shape[0] if q.axis == 0 else q.orig_shape[1]


def _out_side(h, q: QuantizedTensor, tile_t):
    codes, scale, zero, r = _kernel_layout(q)
    if h.shape[1] != codes.shape[0]:
        h = jnp.pad(h, ((0, 0), (0, codes.shape[0] - h.shape[1])))
    y = matmul_out(h, codes, scale, zero, bits=q.bits,
                   binary=q.mode == "binary", group=q.group_size,
                   tile_t=tile_t)
    return y[:, : _quant_m(q)]        # drop a padded last group's columns


def _fused_apply(x, qlora: QuantizedLoRA, tile_t) -> jax.Array:
    """Single-``pallas_call`` application of both sub-LoRAs (kernel.fused_lora)."""
    ah = qlora.a_high
    bh = qlora.b_high
    ahc, ahs, ahz, _ = _kernel_layout(ah)
    bhc, bhs, bhz, _ = _kernel_layout(bh)
    m = bh.orig_shape[0]              # B is (M, R) column-grouped
    kwargs = dict(
        m=m,
        bits_hi=ah.bits, binary_hi=ah.mode == "binary",
        group_ah=ah.group_size, group_bh=bh.group_size,
        tile_t=tile_t,
    )
    a_lo = b_lo = None
    if qlora.a_low is not None:
        al, bl = qlora.a_low, qlora.b_low
        alc, als, alz, _ = _kernel_layout(al)
        blc, bls, blz, _ = _kernel_layout(bl)
        a_lo = (alc, als, alz)
        b_lo = (blc, bls, blz)
        kwargs.update(bits_lo=al.bits, binary_lo=al.mode == "binary",
                      group_al=al.group_size, group_bl=bl.group_size)
    return fused_lora(x, (ahc, ahs, ahz), (bhc, bhs, bhz), a_lo, b_lo,
                      **kwargs)


def lora_apply_quantized(
    x: jax.Array,                    # (T, K) activations
    qlora: QuantizedLoRA,
    *,
    scaling: float = 1.0,
    tile_t: int = 128,
    fused: bool = True,
) -> jax.Array:
    """Packed-LoRA application: high (RTN) + low (binary) sub-LoRAs.

    Matches ``scaling * x @ qlora.delta_w().T`` (B column-grouped tensors are
    consumed as their transposed row-grouped buffers directly — zero-copy).

    ``fused=True`` (default) issues exactly ONE ``pallas_call``: the (T, R)
    intermediates stay in VMEM and ``x`` crosses HBM once. The kernel holds
    whole ``(tile_t, K)`` and ``(tile_t, M)`` tiles, so the token tile is
    halved (down to 8 rows) until :func:`_fused_vmem_estimate` fits
    :data:`FUSED_VMEM_BUDGET`. ``fused=False`` runs the two-pass reference
    (up to four ``pallas_call``s, ``h`` round-trips through HBM), which
    covers every bit-width the fused path does.
    """
    if fused:
        while tile_t > 8 and _fused_vmem_estimate(qlora, tile_t) > \
                FUSED_VMEM_BUDGET:
            tile_t //= 2
    xp, t = _pad_tokens(x, min(tile_t, max(x.shape[0], 1)))
    tt = min(tile_t, xp.shape[0])
    if fused:
        y = _fused_apply(xp, qlora, tt)
        return (scaling * y[:t]).astype(x.dtype)
    h_hi, _ = _side(xp, qlora.a_high, tt)
    y = _out_side(h_hi, qlora.b_high, tt)
    if qlora.a_low is not None:
        h_lo, _ = _side(xp, qlora.a_low, tt)
        y = y + _out_side(h_lo, qlora.b_low, tt)
    return (scaling * y[:t]).astype(x.dtype)


# --------------------------------------------------------------------------
# SGMV — batched heterogeneous adapters
# --------------------------------------------------------------------------

def stack_adapter_side(qs: Sequence[QuantizedTensor]):
    """Stack per-adapter QuantizedTensors (same shape/config) into the
    (NA, R, ·) kernel layout."""
    parts = [_kernel_layout(q) for q in qs]
    codes = jnp.stack([p[0] for p in parts])
    scale = jnp.stack([p[1] for p in parts])
    zero = jnp.stack([p[2] for p in parts])
    return codes, scale, zero


def sgmv_apply(
    x: jax.Array,                    # (T, K), segment-sorted rows
    qas: Sequence[QuantizedTensor],  # per-adapter A (R, K)
    qbts: Sequence[QuantizedTensor],  # per-adapter Bᵀ-view (R, M)
    seg_map: jax.Array,              # (T // tile_t,) adapter id per tile
    *,
    scaling: float = 1.0,
    tile_t: int = 8,
    fused: bool = True,
) -> jax.Array:
    """Heterogeneous multi-LoRA apply; host buckets requests so each token
    tile is single-adapter (pad segments to tile_t).

    ``fused=True`` (default) runs BOTH factor matmuls in a single
    ``pallas_call`` with the scalar-prefetched segment map driving the
    adapter gather for A and B together — the (T, R) intermediate never
    leaves VMEM. ``fused=False`` is the two-kernel reference path.
    """
    from .kernel import sgmv_out

    a_codes, a_scale, a_zero = stack_adapter_side(qas)
    b_codes, b_scale, b_zero = stack_adapter_side(qbts)
    if fused:
        y = sgmv_fused(
            x, a_codes, a_scale, a_zero, b_codes, b_scale, b_zero, seg_map,
            bits_a=qas[0].bits, binary_a=qas[0].mode == "binary",
            group_a=qas[0].group_size,
            bits_b=qbts[0].bits, binary_b=qbts[0].mode == "binary",
            group_b=qbts[0].group_size,
            tile_t=tile_t)
        return (scaling * y).astype(x.dtype)
    h = sgmv_rhs(x, a_codes, a_scale, a_zero, seg_map,
                 bits=qas[0].bits, binary=qas[0].mode == "binary",
                 group=qas[0].group_size, tile_t=tile_t)
    y = sgmv_out(h, b_codes, b_scale, b_zero, seg_map,
                 bits=qbts[0].bits, binary=qbts[0].mode == "binary",
                 group=qbts[0].group_size, m=_quant_m(qbts[0]),
                 tile_t=tile_t)
    return (scaling * y).astype(x.dtype)


# --------------------------------------------------------------------------
# packed multi-adapter batches — the serve-from-codes decode path
# --------------------------------------------------------------------------

@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=(
        "ah_codes", "ah_scale", "ah_zero", "bh_codes", "bh_scale", "bh_zero",
        "al_codes", "al_scale", "al_zero", "bl_codes", "bl_scale", "bl_zero",
        "seg",
    ),
    meta_fields=("bits_hi", "group_ah", "group_bh", "group_al", "group_bl",
                 "k", "m", "rank", "tile_t", "fold"),
)
@dataclasses.dataclass(frozen=True)
class PackedLoRABatch:
    """One LoRA-linear path, packed for heterogeneous multi-adapter serving.

    Device-resident form of ``NA`` adapters' :class:`QuantizedLoRA` leaves at
    one path (e.g. ``attn/wq``) — never dequantized. Array layout (see
    ``docs/packed_format.md``):

    * before the model's layer scan: ``(L, NA·fold, Rp, ·)`` — the scan
      slices the leading layer axis like any other stacked param;
    * inside one layer (what :func:`sgmv_apply_packed` consumes):
      ``(NA·fold, Rp, ·)``.

    ``fold`` is the number of sub-entries each adapter contributes to the
    stacked axis: 1 for plain ``(L, r, in)`` leaves, ``E`` for leaves with an
    extra lead dim (MoE per-expert adapters ``(L, E, r, in)``), whose expert
    axis is folded into the adapter axis so the SGMV kernels stay untouched.
    The entry for (adapter ``a``, sub-entry ``e``) sits at index
    ``a * fold + e``; consumers of folded leaves (``models/ffn.py``) build
    per-row segment ids accordingly.

    ``Rp`` is the LoRA rank padded to the fp32 sublane multiple; every
    adapter's high rows occupy ``[0, h)`` and low rows ``[0, r - h)`` of their
    side, with zero-scale padding rows above — padding dequantizes to exactly
    0, which is what makes adapters with *different* split indices ``h``
    stackable into one uniform batch. The low (binary) side is always
    materialized (all-zero when ``h == r``).

    ``seg`` is the per-token-row adapter index, shape ``(T_rows,)`` after the
    scan slice (stored ``(L, T_rows)`` broadcast before it). It is attached
    late — by ``Model._backbone`` from the batch-level ``lora["seg"]`` — so
    the packed codes themselves are batch-independent and cacheable.
    """

    ah_codes: jax.Array
    ah_scale: jax.Array
    ah_zero: jax.Array
    bh_codes: jax.Array
    bh_scale: jax.Array
    bh_zero: jax.Array
    al_codes: jax.Array
    al_scale: jax.Array
    al_zero: jax.Array
    bl_codes: jax.Array
    bl_scale: jax.Array
    bl_zero: jax.Array
    seg: Optional[jax.Array]
    bits_hi: int
    group_ah: int
    group_bh: int
    group_al: int
    group_bl: int
    k: int
    m: int
    rank: int
    tile_t: int
    fold: int = 1


def _zero_side(rp: int, dim: int, group: int):
    """All-zero binary-side kernel layout for layers with ``h == r``: the
    same shapes :func:`_kernel_layout` produces for a real 1-bit tensor of
    ``rp`` rows over ``dim`` features (zero scales → dequantizes to 0)."""
    g = min(group, dim)
    ng = -(-dim // g)
    return (jnp.zeros((rp, -(-ng * g // 8)), jnp.uint8),
            jnp.zeros((rp, ng), jnp.float32),
            jnp.zeros((rp, ng), jnp.int32))


def pack_adapter_layers(qls: Sequence[QuantizedLoRA],
                        fold: int = 1) -> PackedLoRABatch:
    """Stack one adapter's per-layer :class:`QuantizedLoRA` list into the
    ``(L, Rp, ·)`` kernel layout (an adapter-axis-free
    :class:`PackedLoRABatch`; :func:`stack_packed_adapters` adds ``NA``).

    ``fold > 1`` handles leaves with an extra lead dim (MoE per-expert
    adapters): ``qls`` then holds ``L·fold`` entries in row-major
    ``(layer, sub-entry)`` order and the arrays come out ``(L, fold, Rp, ·)``
    so the stacking step can merge the sub-entry axis into the adapter axis.

    All layers must share shapes and quant config (true by construction for
    one LoRA-linear path of one model). The low side is materialized even for
    layers whose split kept every pair high (``h == r``).
    """
    if not qls:
        raise ValueError("cannot pack an empty layer list")
    if fold < 1 or len(qls) % fold:
        raise ValueError(f"entry count {len(qls)} must be a multiple of "
                         f"fold {fold}")
    q0 = qls[0]
    r = q0.rank
    rp = -(-r // SUBLANE) * SUBLANE
    k = q0.a_high.orig_shape[1]
    m = q0.b_high.orig_shape[0]
    bits = q0.a_high.bits
    group = q0.config.group_size
    group_al = min(group, k)
    group_bl = min(group, m)
    sides = {name: [] for name in
             ("ah", "bh", "al", "bl")}
    for q in qls:
        if (q.rank, q.a_high.orig_shape[1], q.b_high.orig_shape[0],
                q.a_high.bits) != (r, k, m, bits):
            raise ValueError("pack_adapter_layers needs uniform layer shapes "
                             "and quant config")
        sides["ah"].append(_kernel_layout(q.a_high, pad_r=rp)[:3])
        sides["bh"].append(_kernel_layout(q.b_high, pad_r=rp)[:3])
        if q.a_low is not None:
            sides["al"].append(_kernel_layout(q.a_low, pad_r=rp)[:3])
            sides["bl"].append(_kernel_layout(q.b_low, pad_r=rp)[:3])
        else:
            sides["al"].append(_zero_side(rp, k, group))
            sides["bl"].append(_zero_side(rp, m, group))
    def _stack(layers, i):
        arr = jnp.stack([layer[i] for layer in layers])
        if fold > 1:                     # (L·fold, Rp, ·) → (L, fold, Rp, ·)
            arr = arr.reshape((arr.shape[0] // fold, fold) + arr.shape[1:])
        return arr

    stacked = {name: [_stack(layers, i) for i in range(3)]
               for name, layers in sides.items()}
    return PackedLoRABatch(
        *stacked["ah"], *stacked["bh"], *stacked["al"], *stacked["bl"],
        seg=None,
        bits_hi=bits,
        group_ah=q0.a_high.group_size, group_bh=q0.b_high.group_size,
        group_al=group_al, group_bl=group_bl,
        k=k, m=m, rank=r, tile_t=1, fold=fold,
    )


_PACKED_ARRAY_FIELDS = (
    "ah_codes", "ah_scale", "ah_zero", "bh_codes", "bh_scale", "bh_zero",
    "al_codes", "al_scale", "al_zero", "bl_codes", "bl_scale", "bl_zero",
)


def stack_packed_adapters(entries: Sequence[PackedLoRABatch],
                          tile_t: int = 8) -> PackedLoRABatch:
    """Stack per-adapter packed entries (each ``(L, Rp, ·)``, or
    ``(L, fold, Rp, ·)`` for extra-lead-dim leaves) along a new adapter
    axis → ``(L, NA·fold, Rp, ·)``, the form the model's layer scan
    slices. Adapters must share shapes and quant config (one
    :class:`~repro.serving.engine.AdapterStore` guarantees this)."""
    e0 = entries[0]
    for e in entries[1:]:
        if (e.bits_hi, e.k, e.m, e.rank, e.group_ah, e.group_bh, e.fold) != (
                e0.bits_hi, e0.k, e0.m, e0.rank, e0.group_ah, e0.group_bh,
                e0.fold):
            raise ValueError(
                "heterogeneous batches require adapters with one shape and "
                "quant config; re-register through a single AdapterStore")

    def _stack(f):
        arr = jnp.stack([getattr(e, f) for e in entries], axis=1)
        if e0.fold > 1:            # (L, NA, fold, Rp, ·) → (L, NA·fold, Rp, ·)
            arr = arr.reshape(arr.shape[:1] + (-1,) + arr.shape[3:])
        return arr

    arrays = {f: _stack(f) for f in _PACKED_ARRAY_FIELDS}
    return dataclasses.replace(e0, **arrays, tile_t=tile_t)


def retile_packed(tree, tile_t: int):
    """Return a copy of a packed lora tree with every leaf's token-tile size
    replaced (prefill and decode share the packed codes but tile differently:
    whole padded prompts vs one row per sequence)."""
    def one(leaf):
        if isinstance(leaf, PackedLoRABatch):
            return dataclasses.replace(leaf, tile_t=tile_t)
        return leaf
    return jax.tree_util.tree_map(
        one, tree, is_leaf=lambda n: isinstance(n, PackedLoRABatch))


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("buckets", "lookups", "seg"),
    meta_fields=(),
)
@dataclasses.dataclass(frozen=True)
class PackedLoRABuckets:
    """A *mixed-recipe* multi-adapter batch: one :class:`PackedLoRABatch`
    per packed-layout signature (``bits_high`` / group size / low width —
    see ``LoRAQuantConfig.layout_signature``), plus per-bucket lookup tables
    mapping the batch-global segment id to that bucket's local adapter
    index (``-1`` = the adapter lives in another bucket).

    Serving semantics (``docs/recipes.md``): token rows carry ONE global
    seg id space (adapter order for the static packed path, HBM slot ids
    under the paged tier); :func:`sgmv_apply_buckets` runs one fused SGMV
    ``pallas_call`` per bucket over all rows — non-member rows gather a
    clamped index and are masked out of the accumulated output, which is
    exact because LoRA is linear. A uniform-recipe batch never constructs
    this container (``pack_batch`` / ``serving_tree`` return a bare
    :class:`PackedLoRABatch`), so the homogeneous fast path stays exactly
    one dispatch per layer.

    Array layout mirrors the single-bucket leaf: every bucket's arrays and
    each ``(NA_total,)`` lookup are stored with the leading layer axis
    (``(L, ...)``) so the model's layer scan slices them together; ``seg``
    is attached late by ``Model._backbone`` like the single-bucket case.
    """

    buckets: tuple                  # of PackedLoRABatch (seg=None inside)
    lookups: tuple                  # of (L?, NA_total) int32, -1 = absent
    seg: Optional[jax.Array] = None

    @property
    def fold(self) -> int:
        return self.buckets[0].fold

    @property
    def tile_t(self) -> int:
        return self.buckets[0].tile_t


def sgmv_apply_buckets(x: jax.Array, pbs: PackedLoRABuckets, *,
                       scaling: float = 1.0) -> jax.Array:
    """Mixed-recipe heterogeneous LoRA apply: one fused SGMV dispatch per
    layout bucket, outputs accumulated with per-row membership masks.
    ``pbs.seg`` is the per-row *global* segment id; each bucket's lookup
    remaps it to a bucket-local adapter index."""
    if pbs.seg is None:
        raise ValueError("PackedLoRABuckets has no segment ids attached; "
                         "serve through MultiLoRAEngine (or set lora['seg'])")
    seg = pbs.seg.astype(jnp.int32)
    y = None
    for pb, lut in zip(pbs.buckets, pbs.lookups):
        local = jnp.take(lut, seg)
        member = local >= 0
        yb = sgmv_apply_packed(
            x, dataclasses.replace(pb, seg=jnp.maximum(local, 0)),
            scaling=scaling)
        yb = jnp.where(member[:, None], yb, jnp.zeros_like(yb))
        y = yb if y is None else y + yb
    return y.astype(x.dtype)


def sgmv_apply_packed(x: jax.Array, pb: PackedLoRABatch, *,
                      scaling: float = 1.0) -> jax.Array:
    """Heterogeneous multi-adapter LoRA apply straight from packed codes.

    ``x`` is ``(T_rows, K)`` with ``pb`` in its per-layer ``(NA, Rp, ·)``
    form and ``pb.seg`` the per-row adapter index; rows of one tile
    (``pb.tile_t`` consecutive rows) must map to a single adapter — the
    engine guarantees this by padding prompts to a tile multiple. Both
    sub-LoRAs of the selected adapter are applied in ONE ``pallas_call``
    (:func:`repro.kernels.quant_matmul.kernel.sgmv_fused`)."""
    if pb.seg is None:
        raise ValueError("PackedLoRABatch has no segment ids attached; "
                         "serve through MultiLoRAEngine (or set lora['seg'])")
    t, k = x.shape
    if k != pb.k:
        raise ValueError(f"x features {k} != packed adapter K {pb.k}")
    if t % pb.tile_t or t != pb.seg.shape[0]:
        raise ValueError(
            f"rows {t} must equal len(seg) {pb.seg.shape[0]} and divide into "
            f"tiles of {pb.tile_t}")
    seg_tiles = pb.seg[:: pb.tile_t]
    y = sgmv_fused(
        x, pb.ah_codes, pb.ah_scale, pb.ah_zero,
        pb.bh_codes, pb.bh_scale, pb.bh_zero, seg_tiles,
        bits_a=pb.bits_hi, binary_a=False, group_a=pb.group_ah,
        bits_b=pb.bits_hi, binary_b=False, group_b=pb.group_bh,
        a_lo=(pb.al_codes, pb.al_scale, pb.al_zero),
        b_lo=(pb.bl_codes, pb.bl_scale, pb.bl_zero),
        bits_lo=1, binary_lo=True,
        group_al=pb.group_al, group_bl=pb.group_bl,
        m=pb.m, tile_t=pb.tile_t)
    return (scaling * y).astype(x.dtype)
