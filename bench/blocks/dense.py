"""SwiGLU feed-forward, ``wd(silu(wg x) · wu x)``, width ``d_ff``: the
program's ``dense`` feed-forward (see ``layout.py``)."""

import counts


def lora_linears(mc):
    d, f = mc["d_model"], mc["d_ff"]
    return {"wg": (d, f, ()), "wu": (d, f, ()), "wd": (f, d, ())}


def base_leaves(mc):
    return [(f"{name}/w", (k, m), mc["dtype"], "fan_in")
            for name, (k, m, _) in lora_linears(mc).items()]


def flops_per_token(mc):
    return sum(counts.linear_flops(k, m, mc["lora_rank"])
               for k, m, _ in lora_linears(mc).values())


def attention_flops_per_key(mc):
    return 0
