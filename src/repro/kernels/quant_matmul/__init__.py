from .ops import (
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
from . import ref

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
    "ref",
]
