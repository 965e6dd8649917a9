"""Mixture of experts as the program's ``moe`` feed-forward has it
(``models/ffn.py``), in the layout of ``blocks/`` (``layout.py``): a
float32 router over ``n_experts`` SwiGLU experts of width
``d_ff_expert``, ``top_k`` of them per token, plus ``n_shared`` shared
experts as one SwiGLU of ``n_shared · d_ff_expert``. LoRA on the router and
the shared experts, and on every expert where ``lora_on_experts``. A test's
kind module: no cell runs it."""

import counts


def _w(mc):
    moe = mc["moe"]
    return (mc["d_model"], moe["d_ff_expert"], moe["n_experts"],
            moe["d_ff_expert"] * moe["n_shared"])


def _glu(d, f, lead=()):
    return {"wg": (d, f, lead), "wu": (d, f, lead), "wd": (f, d, lead)}


def lora_linears(mc):
    d, f, e, fs = _w(mc)
    out = {"router": (d, e, ())}
    if fs:
        out.update({f"shared/{n}": s for n, s in _glu(d, fs).items()})
    if mc["moe"]["lora_on_experts"]:
        out.update({f"experts/{n}": s for n, s in _glu(d, f, (e,)).items()})
    return out


def base_leaves(mc):
    d, f, e, fs = _w(mc)
    dt = mc["dtype"]
    out = [("router/w", (d, e), "float32", "fan_in")]
    out += [(f"experts/{n}/w", (e, k, m), dt, "fan_in")
            for n, (k, m, _) in _glu(d, f).items()]
    if fs:
        out += [(f"shared/{n}/w", (k, m), dt, "fan_in")
                for n, (k, m, _) in _glu(d, fs).items()]
    return out


def flops_per_token(mc):
    d, f, e, fs = _w(mc)
    r = mc["lora_rank"]
    expert_r = r if mc["moe"]["lora_on_experts"] else 0
    return (counts.linear_flops(d, e, r)
            + mc["moe"]["top_k"] * sum(counts.linear_flops(k, m, expert_r)
                                       for k, m, _ in _glu(d, f).values())
            + sum(counts.linear_flops(k, m, r)
                  for k, m, _ in _glu(d, fs).values() if fs))


def attention_flops_per_key(mc):
    return 0
