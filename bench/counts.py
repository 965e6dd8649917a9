"""Operation and byte counts, worked out from shapes alone.

These are the numerators of every roofline and utilization the benchmark
reports; the denominators are times from the device trace and the peaks in
``peaks.py``. ``mc`` is a configuration file's contents (``configs/*.json``).
"""

from __future__ import annotations

from typing import Sequence

import layout


def linear_flops(k: int, m: int, rank: int) -> int:
    """FLOPs one token costs in a ``k → m`` linear with a rank-``rank``
    adapter on it (``rank`` 0: none)."""
    return 2 * k * m + 2 * rank * (k + m)


def dense_flops_per_token(mc: dict) -> int:
    """Matrix-multiply FLOPs one token costs outside attention's score and
    value products: every layer's kinds (``blocks/<kind>.py``), each with its
    adapters, and the output head."""
    per_layers = sum(g.count * mod.flops_per_token(mc)
                     for _, g, _, _, mod in layout.roles(mc))
    return per_layers + 2 * mc["vocab"] * mc["d_model"]


def attention_flops(mc: dict, keys: int) -> int:
    """Score and value FLOPs of one query token attending to ``keys`` keys,
    over all layers."""
    return keys * sum(g.count * mod.attention_flops_per_key(mc)
                      for _, g, _, _, mod in layout.roles(mc))


def decode_step_flops(mc: dict, rows: int, keys: int) -> int:
    """Model FLOPs of one decode step: one new token for each of ``rows``
    active rows, attending to ``keys`` keys over all of them (each row's
    prompt plus its tokens so far). Rows that are not in use count
    nothing."""
    return rows * dense_flops_per_token(mc) + attention_flops(mc, keys)


def prefill_flops(mc: dict, prompt_lengths: Sequence[int]) -> int:
    """Model FLOPs of prefilling prompts of these lengths, causal attention
    (token ``i`` attends to ``i + 1`` keys) and the head on every token."""
    per_tok = dense_flops_per_token(mc)
    return sum(n * per_tok + attention_flops(mc, n * (n + 1) // 2)
               for n in prompt_lengths)


def sgmv_adapter_bytes(k: int, m: int, rank: int, bits_hi: int,
                       group: int) -> int:
    """HBM bytes of one adapter's packed layout for one path and layer, as
    the fused SGMV kernel reads it: the high (``bits_hi``-bit RTN) and low
    (1-bit) sides of A ``(rank, k)`` and B ``(rank, m)``, each with a float32
    scale and an int32 zero per quant group (the binary side's zeros are
    stored and read too)."""
    groups = 2 * (k // min(group, k)) + 2 * (m // min(group, m))
    codes = rank * (k + m) * bits_hi // 8 + rank * (k + m) // 8
    return codes + rank * groups * (4 + 4)


def sgmv_call(t: int, k: int, m: int, rank: int, bits_hi: int, group: int,
              n_adapters: int) -> tuple:
    """``(flops, bytes)`` of one fused SGMV call over ``t`` rows: the high
    and the low sub-LoRA, each ``2·t·(k·rank + rank·m)`` (both sides padded
    to ``rank`` rows, as the kernel computes them; unpacking and
    dequantizing are not counted), reading a bf16 ``x (t, k)``, writing a
    float32 ``y (t, m)``, and reading the packed layout of each of the
    ``n_adapters`` distinct adapters its rows select."""
    flops = 2 * 2 * t * (k * rank + rank * m)
    nbytes = (2 * t * k + 4 * t * m
              + n_adapters * sgmv_adapter_bytes(k, m, rank, bits_hi, group))
    return flops, nbytes


def sgmv_decode_step(mc: dict, rows: int, n_adapters: int, bits_hi: int,
                     group: int) -> tuple:
    """``(flops, bytes)`` of every SGMV call of one decode step: one call per
    LoRA-targeted linear per layer, ``rows`` rows each (``tile_t = 1``). A
    per-expert adapter's calls run over the expert dispatch's rows, which
    only its kind knows: such a linear is refused here."""
    flops = nbytes = 0
    for lin in layout.linears(mc):
        if lin.lead:
            raise ValueError(f"{lin.path}: per-expert SGMV calls are not "
                             "counted here")
        f, b = sgmv_call(rows, lin.k, lin.m, mc["lora_rank"], bits_hi, group,
                         n_adapters)
        flops += lin.count * f
        nbytes += lin.count * b
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_bytes, "memory") if t_bytes >= t_flops else (t_flops, "compute")
