"""Multi-LoRA serving: many users' adapters resident in quantized form,
onboarded in one bucketed dispatch, and served by the continuous-batching
scheduler straight from packed codes (fused SGMV on every LoRA linear — no
adapter is ever dequantized; see docs/serving.md).

    PYTHONPATH=src python examples/multi_lora_serving.py
"""

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import LoRAQuantConfig
from repro.core.quant import rtn_quantize
from repro.kernels.quant_matmul.ops import sgmv_apply
from repro.kernels.quant_matmul.ref import ref_sgmv
from repro.launch.serve import main as serve_main, random_trained_lora


def kernel_demo():
    """The SGMV hot path: one launch serves a batch mixing 3 adapters."""
    rng = np.random.default_rng(0)
    d, r, n_adapters, tile = 512, 16, 3, 8
    qas, qbts = [], []
    for i in range(n_adapters):
        a = jnp.asarray(rng.normal(size=(r, d)).astype(np.float32) * 0.02)
        b = jnp.asarray(rng.normal(size=(d, r)).astype(np.float32) * 0.02)
        qas.append(rtn_quantize(a, 2, 128, axis=1))
        qbts.append(rtn_quantize(b, 2, 128, axis=0))
    segs = [0, 1, 2, 1]                      # tile→adapter map
    seg_ids = np.repeat(segs, tile)
    x = jnp.asarray(rng.normal(size=(len(seg_ids), d)).astype(np.float32))
    y = sgmv_apply(x, qas, qbts, jnp.asarray(segs, jnp.int32), tile_t=tile)
    err = float(jnp.max(jnp.abs(y - ref_sgmv(x, qas, qbts, seg_ids))))
    print(f"[sgmv] heterogeneous batch of {len(seg_ids)} tokens × "
          f"{n_adapters} adapters in one kernel; maxerr vs oracle {err:.1e}")


def onboarding_demo():
    """Cross-adapter bucketed onboarding: N uploads, one SVD dispatch per
    distinct leaf shape (AdapterStore.register_many)."""
    from repro.models import build_model
    from repro.serving.engine import AdapterStore

    cfg = get_config("llama3.2-3b", "smoke")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    store = AdapterStore(LoRAQuantConfig(ste_steps=0))
    uploads = {
        f"user_{i}": random_trained_lora(params["lora"], jax.random.PRNGKey(i))
        for i in range(4)
    }
    store.register_many(uploads)
    print(f"[onboard] {len(uploads)} adapters quantized in one bucketed "
          f"dispatch; store stats: {store.stats()}")


if __name__ == "__main__":
    kernel_demo()
    onboarding_demo()
    # End-to-end continuous serving: the step-based scheduler admits every
    # request into a batch row, decodes straight from packed codes, and
    # retires rows as they finish (swap --mode packed for the static
    # one-batch path, or --mode materialize for the fp-LRU segment loop).
    serve_main(["--arch", "llama3.2-3b", "--adapters", "4", "--requests", "8",
                "--prompt-len", "16", "--max-new", "4",
                "--mode", "continuous", "--max-rows", "4"])
    # Bounded-HBM multi-tenancy: 16 registered adapters served through a
    # 4-slot HBM pool — the other 12 pages live in the host tier and fault
    # in on demand (prefetched one step ahead; pinned while a row decodes;
    # LRU-evicted otherwise). Token streams are identical to the run above
    # the budget; only the [serve] adapter-memory stats line changes.
    serve_main(["--arch", "llama3.2-3b", "--adapters", "16", "--requests",
                "32", "--prompt-len", "16", "--max-new", "4",
                "--mode", "continuous", "--max-rows", "4", "--slots", "4"])
    # Mixed-precision fleet (docs/recipes.md): two premium adapters keep
    # 4- and 3-bit recipes while the rest run the 2-bit default — ONE
    # batch, one SGMV dispatch per recipe-layout bucket per layer, and the
    # per-adapter avg_bits column shows the spread.
    serve_main(["--arch", "llama3.2-3b", "--adapters", "4", "--requests",
                "8", "--prompt-len", "16", "--max-new", "4",
                "--mode", "continuous", "--max-rows", "4",
                "--recipe", "user_0=4@0.95", "--recipe", "user_1=3@0.9"])
