"""Pallas TPU kernels for the perf-critical serving path: fused
dequantization of packed LoRAQuant factors + skinny matmuls (single-adapter
and SGMV multi-adapter variants). They run natively on a TPU backend and
in the Pallas interpreter elsewhere (``quant_matmul.kernel.interpret_mode``);
the pure-jnp oracle lives in quant_matmul/ref.py."""

from .quant_matmul import (
    PackedLoRABatch,
    PackedLoRABuckets,
    lora_apply_quantized,
    pack_adapter_layers,
    retile_packed,
    sgmv_apply,
    sgmv_apply_buckets,
    sgmv_apply_packed,
    stack_packed_adapters,
)

__all__ = [
    "PackedLoRABatch",
    "PackedLoRABuckets",
    "lora_apply_quantized",
    "pack_adapter_layers",
    "retile_packed",
    "sgmv_apply",
    "sgmv_apply_buckets",
    "sgmv_apply_packed",
    "stack_packed_adapters",
]
