"""Shared model building blocks: norms, rotary embeddings, LoRA-injected
linear layers, embeddings.

Conventions
-----------
* Weights are stored ``(in, out)`` so the forward is ``x @ w``.
* LoRA factors follow the paper: ``A: (r, in)``, ``B: (out, r)``; the update
  is ``ΔW = B A`` applied as ``((x @ Aᵀ) @ Bᵀ) * scaling``.
* Every parameter tree is a plain nested dict (pytree); layer stacks carry a
  leading ``(L, ...)`` axis and are consumed by ``lax.scan``.
* ``dtype`` is the compute/storage dtype of the frozen base (bf16 on TPU);
  LoRA params and all norm/stat math stay fp32.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def rmsnorm(x: jax.Array, weight: Optional[jax.Array], eps: float = 1e-6,
            plus_one: bool = False) -> jax.Array:
    """RMSNorm in fp32. ``plus_one`` is the gemma convention (w ≡ 1 + w̃)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(var + eps)
    if weight is not None:
        w = weight.astype(jnp.float32)
        xf = xf * ((1.0 + w) if plus_one else w)
    return xf.astype(x.dtype)


def nonparam_layernorm(x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """OLMo's non-parametric LayerNorm: standardize, no scale, no bias."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)


def apply_norm(x: jax.Array, p: Optional[Params], kind: str) -> jax.Array:
    if kind == "rmsnorm":
        return rmsnorm(x, p["w"])
    if kind == "rmsnorm_plus1":
        return rmsnorm(x, p["w"], plus_one=True)
    if kind == "nonparam_ln":
        return nonparam_layernorm(x)
    raise ValueError(kind)


def init_norm(d: int, kind: str) -> Optional[Params]:
    if kind == "nonparam_ln":
        return {}
    if kind == "rmsnorm_plus1":
        return {"w": jnp.zeros((d,), jnp.float32)}
    return {"w": jnp.ones((d,), jnp.float32)}


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    """Gemma-2 logit soft-capping: cap * tanh(x / cap)."""
    if cap is None:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


# --------------------------------------------------------------------------
# rotary embeddings
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """Standard RoPE. ``x: (..., T, H, Dh)``, ``positions: (..., T)``."""
    dh = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(dh, theta), jnp.float32)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., T, Dh/2)
    angles = angles[..., None, :]  # broadcast over heads
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array,
    positions: jax.Array,           # (3, ..., T): temporal / height / width
    sections: Sequence[int],        # e.g. (16, 24, 24) halves, sums to Dh/2
    theta: float = 1000000.0,
) -> jax.Array:
    """Qwen2-VL multimodal RoPE: the rotary half-dims are partitioned into
    three sections, each rotated by its own positional stream. For pure-text
    tokens the three streams coincide and M-RoPE reduces to RoPE."""
    dh = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(dh, theta), jnp.float32)  # (Dh/2,)
    # pick the positional stream per frequency index
    sec_ids = np.repeat(np.arange(len(sections)), sections)  # (Dh/2,)
    assert sec_ids.shape[0] == dh // 2, "M-RoPE sections must sum to Dh/2"
    pos = positions.astype(jnp.float32)                       # (3, ..., T)
    pos_per_freq = jnp.take(pos, jnp.asarray(sec_ids), axis=0)  # (Dh/2, ..., T)
    pos_per_freq = jnp.moveaxis(pos_per_freq, 0, -1)          # (..., T, Dh/2)
    angles = pos_per_freq * freqs
    angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# LoRA-injected linear
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LoRASpec:
    rank: int = 16
    alpha: float = 32.0
    dtype: Any = jnp.float32

    @property
    def scaling(self) -> float:
        return self.alpha / self.rank


def init_linear(key, d_in: int, d_out: int, dtype) -> Params:
    scale = 1.0 / np.sqrt(d_in)
    w = jax.random.uniform(key, (d_in, d_out), jnp.float32, -scale, scale)
    return {"w": w.astype(dtype)}


def init_lora(key, d_in: int, d_out: int, spec: LoRASpec) -> Params:
    """Paper-standard init: A ~ Kaiming-uniform, B = 0 (ΔW starts at 0)."""
    ka, _ = jax.random.split(key)
    scale = 1.0 / np.sqrt(d_in)
    a = jax.random.uniform(ka, (spec.rank, d_in), jnp.float32, -scale, scale)
    b = jnp.zeros((d_out, spec.rank), jnp.float32)
    return {"a": a.astype(spec.dtype), "b": b.astype(spec.dtype)}


def linear(
    x: jax.Array,
    base: Params,
    lora: Optional[Params] = None,
    scaling: float = 2.0,
) -> jax.Array:
    """``x @ W (+ LoRA)``. The LoRA path computes in the LoRA dtype and is a
    rank-r bottleneck: (x Aᵀ) Bᵀ — never materializes ΔW.

    ``lora`` may also be a LoRAQuant-compressed adapter leaf, applied
    straight from packed codes by a single-pass fused Pallas kernel — no fp
    materialization, one ``pallas_call`` (see ``docs/serving.md``):

    * ``repro.core.QuantizedLoRA`` — one adapter for the whole batch;
    * ``repro.kernels.PackedLoRABatch`` — a stack of adapters with per-token
      segment ids (heterogeneous multi-adapter serving), dispatched to the
      fused SGMV kernel. The seg ids index whatever adapter axis the stack
      carries: store-wide adapter order for the static packed mode, HBM
      **slot** ids under the paged memory tier (``docs/adapter_memory.md``);
      leaves with a folded extra lead dim (MoE experts, ``fold > 1``) are
      consumed by the MoE dispatch in ``models/ffn.py`` instead, which
      builds folded ``(adapter, expert)`` seg ids per dispatch-buffer row;
    * ``repro.kernels.PackedLoRABuckets`` — a *mixed-recipe* batch: one
      stack per packed-layout signature, dispatched as one SGMV call per
      bucket with per-row membership masks (``docs/recipes.md``)."""
    y = x @ base["w"]
    if lora is None:
        return y
    from repro.core.loraquant import QuantizedLoRA
    from repro.kernels import PackedLoRABatch, PackedLoRABuckets

    if isinstance(lora, QuantizedLoRA):
        from repro.kernels import lora_apply_quantized

        x2 = x.reshape(-1, x.shape[-1])
        upd = lora_apply_quantized(x2, lora, scaling=scaling, fused=True)
        return y + upd.reshape(y.shape).astype(y.dtype)
    if isinstance(lora, PackedLoRABatch):
        from repro.kernels import sgmv_apply_packed

        x2 = x.reshape(-1, x.shape[-1])
        upd = sgmv_apply_packed(x2, lora, scaling=scaling)
        return y + upd.reshape(y.shape).astype(y.dtype)
    if isinstance(lora, PackedLoRABuckets):
        from repro.kernels import sgmv_apply_buckets

        x2 = x.reshape(-1, x.shape[-1])
        upd = sgmv_apply_buckets(x2, lora, scaling=scaling)
        return y + upd.reshape(y.shape).astype(y.dtype)
    xl = x.astype(lora["a"].dtype)
    upd = (xl @ lora["a"].T) @ lora["b"].T
    return y + (scaling * upd).astype(y.dtype)


# --------------------------------------------------------------------------
# embeddings
# --------------------------------------------------------------------------

def init_embedding(key, vocab: int, d: int, dtype) -> Params:
    e = jax.random.normal(key, (vocab, d), jnp.float32) * 0.02
    return {"e": e.astype(dtype)}


def embed(tokens: jax.Array, p: Params) -> jax.Array:
    return jnp.take(p["e"], tokens, axis=0)


def unembed(x: jax.Array, p: Params) -> jax.Array:
    return x @ p["e"].T
