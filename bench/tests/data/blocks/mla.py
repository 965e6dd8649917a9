"""Multi-head latent attention as the program's ``mla`` mixer has it
(``models/attention.py``), in the layout of ``blocks/`` (``layout.py``):
queries through a ``q_lora_rank`` bottleneck, keys and values from one
``kv_lora_rank`` latent per token plus a shared rotary key. A test's kind
module: no cell runs it."""

import counts


def _w(mc):
    m = mc["mla"]
    return (mc["d_model"], mc["n_heads"], m["q_lora_rank"],
            m["kv_lora_rank"], m["rope_head_dim"], m["nope_head_dim"],
            m["v_head_dim"])


def lora_linears(mc):
    d, h, ql, kvl, rope, nope, v = _w(mc)
    return {"wq_down": (d, ql, ()), "wq_up": (ql, h * (nope + rope), ()),
            "wkv_down": (d, kvl, ()), "wo": (h * v, d, ())}


def _linears(mc):
    d, h, ql, kvl, rope, nope, v = _w(mc)
    return {"wq_down": (d, ql), "wq_up": (ql, h * (nope + rope)),
            "wkv_down": (d, kvl), "wk_rope": (d, rope),
            "wk_up": (kvl, h * nope), "wv_up": (kvl, h * v),
            "wo": (h * v, d)}


def base_leaves(mc):
    _, _, ql, kvl, *_ = _w(mc)
    dt, lin = mc["dtype"], _linears(mc)
    leaf = lambda n: (f"{n}/w", lin[n], dt, "fan_in")
    return [leaf("wq_down"), leaf("wq_up"),
            ("q_norm/w", (ql,), "float32", "ones"), leaf("wkv_down"),
            ("kv_norm/w", (kvl,), "float32", "ones"), leaf("wk_rope"),
            leaf("wk_up"), leaf("wv_up"), leaf("wo")]


def flops_per_token(mc):
    targeted = lora_linears(mc)
    return sum(counts.linear_flops(k, m, mc["lora_rank"] if n in targeted
                                   else 0)
               for n, (k, m) in _linears(mc).items())


def attention_flops_per_key(mc):
    """Absorbed decode: scores against the latent and the rotary key, the
    value sum over the latent."""
    _, h, _, kvl, rope, _, _ = _w(mc)
    return 2 * h * (kvl + rope) + 2 * h * kvl
