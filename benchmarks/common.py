"""Shared harness for the paper-table benchmarks.

Pipeline (CPU-scale proxy of the paper's setup, DESIGN.md §4):

1. pretrain a tiny base LM on Markov task A (full-param);
2. freeze it, train a rank-16 LoRA on Markov task B ("customization");
3. post-training-quantize the adapter with each method;
4. report eval CE loss on task B + AvgBits.

The quality ORDERING across methods is the reproduced claim; absolute
numbers are proxy-scale. Everything is deterministic (seeded).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.pipeline import DataConfig, make_batch
from repro.launch.step import make_train_step
from repro.models import build_model
from repro.optim import OptimizerConfig, adamw_update, init_opt_state

BASE_SEED = 0
TASK_B_SEED = 101


def timing_label() -> str:
    """Tag printed after every time: ``(interpret)`` when the Pallas kernels
    ran in the interpreter (a CPU backend — not a device time), otherwise
    the device kind the time was taken on."""
    from repro.kernels.quant_matmul.kernel import interpret_mode

    if interpret_mode():
        return "(interpret)"
    return f"({jax.devices()[0].device_kind})"


@functools.lru_cache(maxsize=2)
def trained_setup(base_steps: int = 250, lora_steps: int = 200,
                  arch: str = "llama3.2-3b"):
    """Returns (cfg, model, params) with a trained base and trained LoRA."""
    cfg = get_config(arch, "smoke")
    cfg = dataclasses.replace(cfg, dtype=jnp.float32, vocab=256)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    # --- 1. full-param pretraining on task A ---
    dc_a = DataConfig(seq_len=128, global_batch=8, vocab=cfg.vocab,
                      seed=BASE_SEED)
    opt_cfg = OptimizerConfig(lr=3e-3, total_steps=base_steps)
    opt = init_opt_state(params["base"])

    @jax.jit
    def base_step(base, opt, batch):
        def loss_fn(b):
            return model.train_loss({"base": b, "lora": params["lora"]},
                                    batch)[0]

        loss, g = jax.value_and_grad(loss_fn)(base)
        base, opt, _ = adamw_update(g, opt, base, opt_cfg)
        return base, opt, loss

    base = params["base"]
    for step in range(base_steps):
        batch = {k: jnp.asarray(v) for k, v in make_batch(dc_a, step).items()}
        base, opt, loss = base_step(base, opt, batch)
    params = {"base": base, "lora": params["lora"]}

    # --- 2. LoRA training on task B (frozen base) ---
    dc_b = DataConfig(seq_len=128, global_batch=8, vocab=cfg.vocab,
                      seed=TASK_B_SEED)
    lora_cfg = OptimizerConfig(lr=2e-3, total_steps=lora_steps)
    step_fn = jax.jit(make_train_step(model, lora_cfg, 1))
    lopt = init_opt_state(params["lora"])
    for step in range(lora_steps):
        batch = {k: jnp.asarray(v) for k, v in make_batch(dc_b, step).items()}
        params, lopt, m = step_fn(params, lopt, batch)
    return cfg, model, params


def eval_loss(cfg, model, params, n_batches: int = 8,
              seed: int = TASK_B_SEED) -> float:
    dc = DataConfig(seq_len=128, global_batch=8, vocab=cfg.vocab, seed=seed)
    f = jax.jit(lambda p, b: model.train_loss(p, b)[1]["ce"])
    losses = []
    for step in range(10_000, 10_000 + n_batches):   # held-out steps
        batch = {k: jnp.asarray(v) for k, v in make_batch(dc, step).items()}
        losses.append(float(f(params, batch)))
    return float(np.mean(losses))


# --------------------------------------------------------------------------
# adapter-tree <-> per-layer (B, A) plumbing
# --------------------------------------------------------------------------

def apply_to_adapters(
    lora_tree,
    fn: Callable[[jnp.ndarray, jnp.ndarray], Tuple[jnp.ndarray, jnp.ndarray, float, int]],
):
    """Apply ``fn(B, A) -> (B', A', total_bits, n_params)`` to every LoRA
    linear (flattening stacked layer/expert dims) and rebuild the tree.
    Returns (new_tree, avg_bits)."""
    total_bits = 0.0
    total_params = 0

    def walk(node):
        nonlocal total_bits, total_params
        if isinstance(node, dict):
            if set(node.keys()) == {"a", "b"}:
                a, b = node["a"], node["b"]
                lead = a.shape[:-2]
                a2 = a.reshape((-1,) + a.shape[-2:])
                b2 = b.reshape((-1,) + b.shape[-2:])
                new_a, new_b = [], []
                for i in range(a2.shape[0]):
                    bq, aq, bits, n = fn(b2[i], a2[i])
                    new_a.append(aq)
                    new_b.append(bq)
                    total_bits += bits
                    total_params += n
                return {
                    "a": jnp.stack(new_a).reshape(a.shape).astype(a.dtype),
                    "b": jnp.stack(new_b).reshape(b.shape).astype(b.dtype),
                }
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v) for v in node]
        if isinstance(node, tuple):
            return tuple(walk(v) for v in node)
        return node

    new_tree = walk(lora_tree)
    return new_tree, total_bits / max(total_params, 1)


def fp16_fn(b, a):
    bits = (b.size + a.size) * 16
    return b, a, float(bits), b.size + a.size


def make_method_table() -> Dict[str, Callable]:
    """name -> fn(B, A) for every Table-1 row."""
    from repro.core import LoRAQuantConfig, quantize_lora
    from repro.core.baselines import (
        billm_lora, bin_lora, gptq_lora, pbllm_lora, rtn_lora)

    def lq(bits_high, rho, refine="ste"):
        def fn(b, a):
            ql = quantize_lora(b, a, LoRAQuantConfig(
                rho=rho, bits_high=bits_high, refine=refine, ste_steps=60))
            bq, aq = ql.materialize()
            # keep factor shapes: pad/truncate rank (h+low == r always here)
            return bq, aq, float(ql.total_bits()), ql.num_params()
        return fn

    def baseline(callable_, *args, **kw):
        def fn(b, a):
            qp = callable_(b, a, *args, **kw)
            bq, aq = qp.materialize()
            return bq, aq, qp.total_bits, qp.num_params
        return fn

    return {
        "fp16": fp16_fn,
        "bin": baseline(bin_lora),
        "rtn1": baseline(rtn_lora, 1),
        "rtn2": baseline(rtn_lora, 2),
        "gptq2": baseline(gptq_lora, 2),
        "pbllm": baseline(pbllm_lora),
        "billm": baseline(billm_lora),
        "loraquant_2@0.8": lq(2, 0.8),
        "loraquant_2@0.9": lq(2, 0.9),
        "loraquant_3@0.8": lq(3, 0.8),
        "loraquant_3@0.9": lq(3, 0.9),
        "loraquant_2@0.9_als": lq(2, 0.9, refine="als"),
        "loraquant_3@0.9_als": lq(3, 0.9, refine="als"),
    }


def quantize_model_adapters(params, method_fn):
    new_lora, avg_bits = apply_to_adapters(params["lora"], method_fn)
    return {"base": params["base"], "lora": new_lora}, avg_bits
