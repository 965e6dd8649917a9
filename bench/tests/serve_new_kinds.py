"""Serve a cell's configuration through the harness, without a reference,
from the checkout this file lies in:

    BENCH_SPEC=<BENCHMARK.json> python bench/tests/serve_new_kinds.py \\
        <workload> <seed> <seconds>

With ``PYTHONPATH`` naming the program's ``src``. It prints one JSON line:
whether the benchmark's base weights and LoRA template match the program's
``Model.init`` in structure, shapes and dtypes; whether the fleet as the
harness registers it has the entries ``AdapterStore.register`` gives a float
adapter of the same template; and how the requests of a short window at the
mix's rehearsal sizes ended. ``test_cli`` runs it on a model whose kinds of
layer were added as new files."""

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def entry_shapes(entries) -> dict:
    """Per path, each entry's ``(in, out)`` features and rank; a rank as an
    SVD of the factors caps it, ``min(rank, in, out)``."""
    return {p: [(q.a_high.orig_shape[1], q.b_high.orig_shape[0],
                 min(q.rank, q.a_high.orig_shape[1], q.b_high.orig_shape[0]))
                for q in qs] for p, qs in entries.items()}


def main(workload: str, seed: int, seconds: float) -> dict:
    sys.path.insert(0, BENCH)
    import jax
    import numpy as np

    import harness
    import serve_loop
    import weights
    from repro.core import LoRAQuantConfig
    from repro.models import build_model
    from repro.serving.engine import AdapterStore

    def shapes(tree):
        return (jax.tree_util.tree_structure(tree),
                [(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(tree)])

    cell = harness.load_cell(workload, rehearsal=True)
    mc = cell.mc
    program = jax.eval_shape(build_model(harness.model_config(mc)).init,
                             jax.random.PRNGKey(0))
    template = weights.lora_template(mc)
    out = {
        "base": shapes(jax.eval_shape(lambda: weights.base_params(mc, seed)))
        == shapes(program["base"]),
        "lora": shapes(template) == shapes(program["lora"]),
    }

    engine, _ = harness.setup(cell, seed, harness.CompileWatch(jax))
    rng = np.random.default_rng(seed)
    floats = jax.tree_util.tree_map(
        lambda s: (rng.normal(size=s.shape) * 0.02).astype(np.float32),
        template)
    recipe = LoRAQuantConfig(**cell.traffic["fleet"]["recipe"])
    stored = AdapterStore(default_recipe=recipe).register("f", floats)
    out["fleet"] = (entry_shapes(engine.store.quantized["a0"].entries)
                    == entry_shapes(stored.entries))

    window = serve_loop.run_window(engine, harness.request, cell.traffic,
                                   seed, mc["vocab"], seconds, None)
    recs = [r for r in window.recs.values() if r.due <= window.end]
    out.update(requests=len(recs),
               done=sum(r.status == "DONE" for r in recs),
               bad=window.bad)
    return out


if __name__ == "__main__":
    print(json.dumps(main(sys.argv[1], int(sys.argv[2]),
                          float(sys.argv[3]))), flush=True)
