"""Causal self-attention with grouped key/value heads (``n_kv_heads``
shared by ``n_heads / n_kv_heads`` query heads each), no biases: the
program's ``attn`` mixer, and the layout of ``blocks/`` (see
``layout.py``)."""

import counts


def _widths(mc):
    d, dh = mc["d_model"], mc["head_dim"]
    return d, mc["n_heads"] * dh, mc["n_kv_heads"] * dh


def lora_linears(mc):
    d, q, kv = _widths(mc)
    return {"wq": (d, q, ()), "wk": (d, kv, ()), "wv": (d, kv, ()),
            "wo": (q, d, ())}


def base_leaves(mc):
    return [(f"{name}/w", (k, m), mc["dtype"], "fan_in")
            for name, (k, m, _) in lora_linears(mc).items()]


def flops_per_token(mc):
    return sum(counts.linear_flops(k, m, mc["lora_rank"])
               for k, m, _ in lora_linears(mc).values())


def attention_flops_per_key(mc):
    """Scores ``q·k`` and the value sum, ``2·head_dim`` each per head."""
    return 4 * mc["n_heads"] * mc["head_dim"]
