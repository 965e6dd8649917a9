"""Kernel-level benchmark: fused single-pass vs two-pass quantized LoRA
apply, and batched vs per-layer adapter quantization.

On a CPU backend the Pallas kernels run in the interpreter, so wall-times
(tagged ``(interpret)``) are NOT device times; the reported derived
metrics are

* kernel-launch counts (fused path must be exactly 1 ``pallas_call``),
* the HBM-traffic model — packed bytes vs fp16 bytes per adapter apply,
  plus the two-pass overhead the fused kernel eliminates: a second read of
  ``x`` and the write+read round-trip of the (T, R) fp32 intermediates —
  which is what determines decode-time speedup on the memory-bound path,
* adapter-onboarding throughput (batched stack pipeline vs Python loop),
  which is what bounds how fast uploaded adapters can be quantized at the
  many-users serving tier.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import LoRAQuantConfig, quantize_lora, quantize_lora_stack
from repro.core.quant import storage_bits
from repro.kernels.quant_matmul import kernel as _kernel
from repro.kernels.quant_matmul.ops import SUBLANE, lora_apply_quantized

from .common import timing_label


def _decayed_pair(m, n, r, rng, decay=0.4):
    u = np.linalg.qr(rng.normal(size=(m, r)))[0]
    v = np.linalg.qr(rng.normal(size=(n, r)))[0]
    s = np.exp(-decay * np.arange(r))
    b = jnp.asarray((u * np.sqrt(s)).astype(np.float32))
    a = jnp.asarray((np.sqrt(s)[:, None] * v.T).astype(np.float32))
    return b, a


def _pad8(r):
    return -(-r // SUBLANE) * SUBLANE


def run(report):
    rng = np.random.default_rng(0)
    m = n = 2048
    r = 16
    t_tokens = 64
    b, a = _decayed_pair(m, n, r, rng)
    ql = quantize_lora(b, a, LoRAQuantConfig(rho=0.9, bits_high=2, ste_steps=0))
    x = jnp.asarray(rng.normal(size=(t_tokens, n)).astype(np.float32))
    ref = x @ ql.delta_w().T

    results = {}
    for name, fused in (("fused", True), ("two_pass", False)):
        _kernel.reset_launch_counts()
        y = lora_apply_quantized(x, ql, fused=fused)
        launches = sum(_kernel.LAUNCH_COUNTS.values())
        err = float(jnp.max(jnp.abs(y - ref)))
        t0 = time.perf_counter()
        for _ in range(3):
            lora_apply_quantized(x, ql, fused=fused).block_until_ready()
        us = (time.perf_counter() - t0) / 3 * 1e6
        results[name] = dict(launches=launches, err=err, us=us)
        report(f"kernels.{name},lora_apply,pallas_calls={launches},"
               f"us_per_call={us:.0f}{timing_label()},maxerr={err:.2e}")

    # HBM traffic model (memory-bound decode: bytes == wall time)
    packed_bytes = ql.total_bits() / 8
    fp16_bytes = ql.num_params() * 2
    x_bytes = x.size * x.dtype.itemsize
    n_sides = 1 if ql.a_low is None else 2
    # two-pass: x is re-read per sub-LoRA side and each (T, R) h is written
    # by the rhs kernel then read back by the out kernel.
    h_bytes = sum(t_tokens * _pad8(q.scale.shape[0]) * 4
                  for q in (ql.a_high, ql.a_low) if q is not None)
    two_pass_extra = (n_sides - 1) * x_bytes + 2 * h_bytes
    report(f"kernels.traffic,model,packed_mb={packed_bytes/1e6:.3f},"
           f"fp16_mb={fp16_bytes/1e6:.3f},"
           f"hbm_reduction={fp16_bytes/packed_bytes:.2f}x,"
           f"two_pass_extra_kb={two_pass_extra/1e3:.1f},"
           f"h_roundtrip_kb={2*h_bytes/1e3:.1f},"
           f"fused_saving={two_pass_extra/(packed_bytes+x_bytes)*100:.1f}%")

    ok_fused = results["fused"]["launches"] == 1 and results["fused"]["err"] < 1e-3
    report(f"kernels.check,fused_single_call_exact,"
           f"{'PASS' if ok_fused else 'FAIL'}")
    report(f"kernels.check,two_pass_vs_fused_calls_{results['two_pass']['launches']}v1,"
           f"{'PASS' if results['two_pass']['launches'] > results['fused']['launches'] else 'FAIL'}")
    report(f"kernels.check,hbm_reduction_gt_8x,"
           f"{'PASS' if fp16_bytes / packed_bytes > 8 else 'FAIL'}")

    # ---- adapter-onboarding throughput: batched stack vs per-layer loop ----
    L, ms, ns, rs = 8, 256, 256, 8
    pairs = [_decayed_pair(ms, ns, rs, rng, decay=0.2 + 0.05 * i)
             for i in range(L)]
    b_stack = jnp.stack([p[0] for p in pairs])
    a_stack = jnp.stack([p[1] for p in pairs])
    cfg = LoRAQuantConfig(ste_steps=0, refine="none")

    # warmup (compile) then time
    quantize_lora_stack(b_stack, a_stack, cfg)
    t0 = time.perf_counter()
    batched = quantize_lora_stack(b_stack, a_stack, cfg)
    jax.block_until_ready([q.a_high.codes for q in batched])
    dt_batched = time.perf_counter() - t0

    quantize_lora(b_stack[0], a_stack[0], cfg)
    t0 = time.perf_counter()
    loop = [quantize_lora(b_stack[i], a_stack[i], cfg) for i in range(L)]
    jax.block_until_ready([q.a_high.codes for q in loop])
    dt_loop = time.perf_counter() - t0

    worst = max(float(jnp.max(jnp.abs(qb.delta_w() - ql_.delta_w())))
                for qb, ql_ in zip(batched, loop))
    report(f"kernels.quant_pipeline,batched_vs_loop,layers={L},"
           f"batched_lps={L/dt_batched:.1f},loop_lps={L/dt_loop:.1f},"
           f"speedup={dt_loop/dt_batched:.2f}x,maxdiff={worst:.2e}")
    report(f"kernels.check,batched_matches_loop,"
           f"{'PASS' if worst < 1e-5 else 'FAIL'}")
    return results["fused"]["err"]
