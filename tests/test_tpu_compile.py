"""Ahead-of-time compiles of the served kernels for a TPU v5e that is
described, not attached: the chip's own compiler must accept every
``pallas_call`` of the serving path at ``llama3.2-3b`` widths (d_model 3072,
KV width 1024, d_ff 8192, rank 16, quant group 128), for 2-, 3- and 4-bit
heads and the 1-bit tail. Nothing runs; the compiled HLO must hold a
native kernel (``tpu_custom_call``), not the interpreter's loop.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import LoRAQuantConfig, quantize_lora
from repro.core.quant import binary_quantize
from repro.kernels import (
    PackedLoRABuckets,
    lora_apply_quantized,
    pack_adapter_layers,
    sgmv_apply_buckets,
    sgmv_apply_packed,
    stack_packed_adapters,
)
from repro.kernels.quant_matmul import kernel as kernel_mod

RANK = 16
N_ADAPTERS = 8
# (K, M) of the LoRA-wrapped projections: q/o, k/v, gate/up, down
PROJECTIONS = [(3072, 3072), (3072, 1024), (3072, 8192), (8192, 3072)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def native(monkeypatch):
    """Lower the kernels for the chip: the backend seen here is the CPU,
    whose branch would pick the interpreter."""
    monkeypatch.setattr(kernel_mod, "interpret_mode", lambda: False)
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)


def _qlora(k, m, bits_high, rho=0.9, seed=0):
    rng = np.random.default_rng(seed)
    u = np.linalg.qr(rng.normal(size=(m, RANK)))[0]
    v = np.linalg.qr(rng.normal(size=(k, RANK)))[0]
    s = np.exp(-0.3 * np.arange(RANK))
    b = jnp.asarray((u * np.sqrt(s)).astype(np.float32))
    a = jnp.asarray((np.sqrt(s)[:, None] * v.T).astype(np.float32))
    return quantize_lora(b, a, LoRAQuantConfig(rho=rho, bits_high=bits_high,
                                               ste_steps=0))


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _layer_stack(k, m, bits_high, tile_t, sharding):
    """Shapes of one layer's ``(NA, Rp, ·)`` packed stack on the chip."""
    entry = pack_adapter_layers([_qlora(k, m, bits_high)])
    pb = jax.eval_shape(
        lambda: stack_packed_adapters([entry] * N_ADAPTERS, tile_t=tile_t))
    pb = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), pb)
    return _on(sharding, pb)


def _assert_native(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k,m", PROJECTIONS)
@pytest.mark.parametrize("rows,tile_t", [(8, 1), (64, 8)],
                         ids=["decode", "prefill"])
def test_sgmv_fused_compiles(one_chip, native, k, m, rows, tile_t):
    pb = _layer_stack(k, m, 2, tile_t, one_chip)
    x = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)

    def apply(x, pb, seg):
        return sgmv_apply_packed(x, dataclasses.replace(pb, seg=seg))

    _assert_native(jax.jit(apply).lower(x, pb, seg).compile())


@pytest.mark.parametrize("bits_high", [3, 4])
def test_sgmv_fused_decode_head_widths(one_chip, native, bits_high):
    pb = _layer_stack(3072, 8192, bits_high, 1, one_chip)
    x = jax.ShapeDtypeStruct((8, 3072), jnp.bfloat16, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)

    def apply(x, pb, seg):
        return sgmv_apply_packed(x, dataclasses.replace(pb, seg=seg))

    _assert_native(jax.jit(apply).lower(x, pb, seg).compile())


def test_sgmv_buckets_mixed_recipe_compiles(one_chip, native):
    """A 2@0.9 bucket and a 4@0.95 bucket in one decode batch."""
    stacks = tuple(_layer_stack(3072, 3072, bits, 1, one_chip)
                   for bits in (2, 4))
    luts = tuple(jax.ShapeDtypeStruct((2 * N_ADAPTERS,), jnp.int32,
                                      sharding=one_chip) for _ in stacks)
    x = jax.ShapeDtypeStruct((8, 3072), jnp.bfloat16, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)

    def apply(x, stacks, luts, seg):
        return sgmv_apply_buckets(
            x, PackedLoRABuckets(buckets=stacks, lookups=luts, seg=seg))

    compiled = jax.jit(apply).lower(x, stacks, luts, seg).compile()
    _assert_native(compiled)
    assert compiled.as_text().count("tpu_custom_call") >= 2


@pytest.mark.parametrize("bits_high,rho", [(1, 1.0), (2, 0.9), (3, 0.9),
                                           (4, 0.9)],
                         ids=["binary", "2bit", "3bit", "4bit"])
def test_fused_lora_compiles(one_chip, native, bits_high, rho):
    """``lora_apply_quantized`` (single adapter, ``fused_lora``) per head
    width; ``binary`` is a 1-bit head alone, the others carry the 1-bit
    tail as their low sub-LoRA."""
    if bits_high == 1:
        ql = _binary_qlora(3072, 8192)
    else:
        ql = _qlora(3072, 8192, bits_high, rho=rho)
    qs = _on(one_chip, ql)
    x = jax.ShapeDtypeStruct((128, 3072), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda x, q: lora_apply_quantized(x, q)).lower(
        x, qs).compile()
    _assert_native(compiled)


def _binary_qlora(k, m):
    """A LoRA whose only sub-LoRA is the 1-bit binary one."""
    ql = _qlora(k, m, 2, rho=1.0)
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.normal(size=(RANK, k)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(m, RANK)).astype(np.float32))
    return dataclasses.replace(ql, a_high=binary_quantize(a, 128, axis=1),
                               b_high=binary_quantize(b, 128, axis=0))
