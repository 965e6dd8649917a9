"""Budget-fitted recipe frontier: AvgBits ↔ reconstruction error ↔
serving throughput.

``fit_recipe`` turns the paper's Table-2 AvgBits axis into a serving API:
for each target budget b ∈ {1.0, 1.5, 2.0, 3.0} it searches ``(bits_high,
rho)`` against the adapter's singular values and the exact storage-bit
accounting. This suite reports, per target,

* the fitted recipe and its **achieved** AvgBits (checked within 0.25 of
  the target — the acceptance tolerance),
* relative reconstruction error ``||ΔW_q - ΔW|| / ||ΔW||`` over a small
  decaying-spectrum adapter set,
* fused-kernel apply throughput (tagged ``(interpret)`` on a CPU backend:
  relative numbers only — wider codes unpack more bit-planes per weight).

Checks assert the frontier is well-formed: every target within tolerance
and error strictly decreasing as the budget grows.

    PYTHONPATH=src python -m benchmarks.run --only recipes --json BENCH_kernels.json
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.core import LoRAQuantConfig, fit_recipe, quantize_lora
from repro.kernels import lora_apply_quantized

from .common import timing_label

TARGETS = (1.0, 1.5, 2.0, 3.0)
N_ADAPTERS = 3
M, N, R = 256, 512, 16
T_TOKENS = 64
APPLY_REPEATS = 3


def _adapters():
    out = []
    for seed in range(N_ADAPTERS):
        g = np.random.default_rng(seed)
        u = np.linalg.qr(g.normal(size=(M, R)))[0]
        v = np.linalg.qr(g.normal(size=(N, R)))[0]
        s = np.exp(-0.4 * np.arange(R))
        b = (u * np.sqrt(s)).astype(np.float32)
        a = (np.sqrt(s)[:, None] * v.T).astype(np.float32)
        out.append((b, a))
    return out


def run(report):
    pairs = _adapters()
    x = jnp.asarray(np.random.default_rng(9).normal(
        size=(T_TOKENS, N)).astype(np.float32))

    rows = []
    for target in TARGETS:
        rec = fit_recipe(pairs, target, base=LoRAQuantConfig(ste_steps=0))
        qs = [quantize_lora(jnp.asarray(b), jnp.asarray(a), rec)
              for b, a in pairs]
        achieved = (sum(q.total_bits() for q in qs)
                    / sum(q.num_params() for q in qs))
        err = float(np.mean([
            np.linalg.norm(np.asarray(q.delta_w()) - b @ a)
            / np.linalg.norm(b @ a)
            for q, (b, a) in zip(qs, pairs)]))
        lora_apply_quantized(x, qs[0])                      # warmup / trace
        t0 = time.perf_counter()
        for _ in range(APPLY_REPEATS):
            lora_apply_quantized(x, qs[0]).block_until_ready()
        tok_s = T_TOKENS * APPLY_REPEATS / (time.perf_counter() - t0)
        rows.append((target, rec, achieved, err, tok_s))
        report(f"recipes.frontier,target_{target:g},"
               f"recipe={rec.bits_high}@{rec.rho:.3f},"
               f"avg_bits={achieved:.3f},recon_rel_err={err:.4f},"
               f"tok_s={tok_s:.1f}{timing_label()}")

    within = all(abs(ach - t) <= 0.25 for t, _, ach, _, _ in rows)
    report(f"recipes.check,budget_within_quarter_bit,"
           f"{'PASS' if within else 'FAIL'}")
    errs = [err for *_, err, _ in rows]
    monotone = all(errs[i] > errs[i + 1] for i in range(len(errs) - 1))
    report(f"recipes.check,error_decreases_with_budget,"
           f"{'PASS' if monotone else 'FAIL'}")
    return rows
