"""Seeded weights of a cell: the base model and the adapter fleet.

Both are made on the device, each in one jitted call, from ``--seed`` alone,
so the reference can make the same values again after the program's state
is freed, without taking anything the program made.

* The base is random (``uniform(±1/sqrt(in))`` projections, ``N(0, 0.02²)``
  embeddings, unit norm weights) in the configuration's dtype, laid out as
  the program's parameter tree (``{"groups": [{"sub_0": ...}]}``, layer
  stacks on a leading axis). Each kind of layer (``blocks/<kind>.py``) lists
  its leaves; the random ones draw one key each, across groups, sub-blocks
  and kinds in declared order, then the embedding and the head.
* The fleet arrives already quantized, as a restarting server loads it from
  storage: per adapter, LoRA-targeted linear and layer (and expert, for a
  per-expert adapter), LoRAQuant's canonical storage form
  at rank ``r`` — a ``bits_high``-bit RTN high side and a 1-bit low side of
  both factors, codes packed little-endian into bytes (code ``i`` of a word
  at bits ``[i·bits, (i+1)·bits)``), a float32 scale per group of ``group``
  features and an integer zero point per RTN group, and the split ``h``:
  rank components ``< h`` use the high side, the rest the low side. Codes
  are uniform, and the scales decay over the rank components like the
  spectrum of a trained adapter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import layout

SPECTRUM_DECAY = 0.3      # per rank component, as a trained adapter's
FACTOR_RMS = 0.02         # RMS entry of a dequantized LoRA factor
H_RANGE = (1, 4)          # split h drawn per (adapter, path, layer)


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (seeds above 32 bits
    fold their high word in rather than being truncated)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def dtype_of(mc: dict):
    return jnp.dtype(mc["dtype"])


def base_params(mc: dict, seed: int) -> dict:
    """The base model's weights for ``seed``, on the default device."""
    return jax.jit(functools.partial(_base, mc))(
        jax.random.fold_in(seed_key(seed), 0))


def _init(rule: str, keys, shape, dtype):
    """A leaf by its kind's init rule: ``fan_in`` (a linear ``(..., in,
    out)``, ``uniform(±1/sqrt(in))``, drawing the next of ``keys``) or
    ``ones``."""
    if rule == "fan_in":
        s = 1.0 / np.sqrt(shape[-2])
        return jax.random.uniform(next(keys), shape, dtype, -s, s)
    if rule == "ones":
        return jnp.ones(shape, dtype)
    raise ValueError(f"no init rule {rule!r}")


def _base(mc, key):
    d, vocab = mc["d_model"], mc["vocab"]
    dt = dtype_of(mc)
    parametric = mc["norm"] != "nonparam_ln"

    def norm(lead):
        return {"w": jnp.ones(lead + (d,), jnp.float32)} if parametric else {}

    groups = [{sub.name: {"mixer": {}, "mixer_norm": norm((g.count,)),
                          "ffn": {}, "ffn_norm": norm((g.count,))}
               for sub in g.subs} for g in layout.groups(mc)]
    leaves = [(groups[gi][sub.name][role], g.count, leaf)
              for gi, g, sub, role, mod in layout.roles(mc)
              for leaf in mod.base_leaves(mc)]
    ks = jax.random.split(
        key, sum(leaf[3] == "fan_in" for *_, leaf in leaves) + 2)
    drawn = iter(ks)
    for node, count, (path, shape, dtype, rule) in leaves:
        *parents, name = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = _init(rule, drawn, (count,) + tuple(shape),
                           jnp.dtype(dtype))
    base = {"groups": groups, "final_norm": norm(())}
    emb = {"e": jax.random.normal(ks[-2], (vocab, d), dt) * 0.02}
    if mc["tie_embeddings"]:
        base["embed_tied"] = emb
    else:
        base["embed"] = emb
        base["head"] = {"e": jax.random.normal(ks[-1], (vocab, d), dt) * 0.02}
    return base


def lora_template(mc: dict) -> dict:
    """Shapes of one adapter in the program's LoRA tree layout: ``a`` is
    ``(L, *lead, r, in)`` and ``b`` is ``(L, *lead, out, r)``, float32."""
    r = mc["lora_rank"]
    return layout.nest({
        lin.path: {
            "a": jax.ShapeDtypeStruct((lin.count,) + lin.lead + (r, lin.k),
                                      jnp.float32),
            "b": jax.ShapeDtypeStruct((lin.count,) + lin.lead + (lin.m, r),
                                      jnp.float32)}
        for lin in layout.linears(mc)})


def fleet_codes(mc: dict, recipe: dict, seed: int,
                adapters) -> dict:
    """Canonical quantized storage of the adapters with these fleet indices:
    ``{path: {field: array (n_adapters, L, *lead, r, ...)}}``, keyed by the
    full path of each LoRA-targeted linear (``layout.linears``), with
    fields ``h`` ``(n, L, *lead)``; ``ah_codes``/``bh_codes``
    ``(n, L, *lead, r, G, group·bits/8)`` uint8,
    ``ah_scale``/``bh_scale``/``ah_zero``/``bh_zero`` ``(n, L, *lead, r,
    G)``; ``al_codes``/``bl_codes`` ``(…, r, G, group/8)`` and
    ``al_scale``/``bl_scale``. ``a*`` fields run over the layer's input
    features, ``b*`` over its outputs (B stored transposed, grouped along
    its output axis). Every row is generated at full rank; row ``j`` of a
    layer belongs to the high side when ``j < h``."""
    idx = jnp.asarray(np.asarray(adapters, np.int64), jnp.int32)
    fn = jax.jit(jax.vmap(functools.partial(_adapter, mc, recipe),
                          in_axes=(None, 0)))
    return fn(jax.random.fold_in(seed_key(seed), 1), idx)


def _adapter(mc, recipe, key, index):
    key = jax.random.fold_in(key, index)
    r = mc["lora_rank"]
    bits, group = recipe["bits_high"], recipe["group_size"]
    decay = jnp.exp(-SPECTRUM_DECAY * jnp.arange(r, dtype=jnp.float32))
    rtn_rms = np.sqrt(np.mean([(c - z) ** 2 for c in range(2 ** bits)
                               for z in (1, 2)]))
    out = {}
    for pi, lin in enumerate(layout.linears(mc)):
        kp = jax.random.fold_in(key, pi)
        ks = jax.random.split(kp, 12)
        layers = (lin.count,) + lin.lead
        fields = {"h": jax.random.randint(ks[0], layers, H_RANGE[0],
                                          H_RANGE[1] + 1, jnp.int32)}
        for si, (side, dim) in enumerate(zip("ab", (lin.k, lin.m))):
            g = min(group, dim)
            ng = dim // g
            shape = layers + (r, ng)
            jitter = lambda k: jax.random.uniform(k, shape, jnp.float32,
                                                  0.5, 1.5)
            comp = decay[:, None] * FACTOR_RMS
            o = 5 * si + 1
            fields[f"{side}h_codes"] = jax.random.bits(
                ks[o], shape + (g * bits // 8,), jnp.uint8)
            fields[f"{side}h_zero"] = jax.random.randint(
                ks[o + 1], shape, 1, 3, jnp.int32)
            fields[f"{side}h_scale"] = comp / rtn_rms * jitter(ks[o + 2])
            fields[f"{side}l_codes"] = jax.random.bits(
                ks[o + 3], shape + (g // 8,), jnp.uint8)
            fields[f"{side}l_scale"] = comp * jitter(ks[o + 4])
        out[lin.path] = fields
    return out
