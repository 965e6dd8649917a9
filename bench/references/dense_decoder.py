"""Plain float32 reference of a dense decoder with per-sequence LoRA, and its
lower-precision control.

The architecture as published for OLMo and InternLM2: token embedding;
per layer a pre-norm causal self-attention (grouped heads: query head ``j``
reads key/value head ``j // (n_heads / n_kv_heads)``, rotary position
embedding on the two halves of each head, ``theta`` from the
configuration) and a pre-norm SwiGLU feed-forward, each added to the
residual stream; a final norm; logits against the (tied or separate)
embedding table. ``norm`` is ``nonparam_ln`` (OLMo's LayerNorm without
scale or bias) or ``rmsnorm``. Every targeted linear adds its LoRA update
``(alpha / r) · (x A'ᵀ) B'ᵀ``, with ``A'`` and ``B'`` dequantized here from
the fleet's stored codes (``weights.fleet_codes``).

Everything runs in float32 with matrix products at ``HIGHEST`` precision,
layer by layer, so it fits beside nothing else on one chip. It imports
nothing of the program: the weights and codes come again from the seed.

``mode="fp8"`` is the control: the same forward with every base matrix
product computed in float8 (e4m3, a scale per tensor for weights and per row
for activations), the step below the configuration's bfloat16 that would
tempt a later change.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

import layout
from weights import base_params, fleet_codes

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _unpack(codes, bits):
    """Little-endian packed bytes ``(..., G, g·bits/8)`` → codes
    ``(..., G·g)`` as float32."""
    per = 8 // bits
    shifts = jnp.arange(per, dtype=jnp.uint8) * bits
    q = (codes[..., None] >> shifts) & ((1 << bits) - 1)
    return q.reshape(q.shape[:-3] + (-1,)).astype(jnp.float32)


def _groups(v, n):
    """Per-group values ``(..., G)`` repeated over the group's features."""
    return jnp.repeat(v, n // v.shape[-1], axis=-1)


def dequantize(f: dict, side: str, bits: int) -> jax.Array:
    """One factor of one path, ``(n, L, r, features)``: rows ``< h`` from
    the RTN high side (``scale · (code − zero)``), the rest from the binary
    low side (``scale · (2·bit − 1)``)."""
    hi = _unpack(f[f"{side}h_codes"], bits)
    n = hi.shape[-1]
    hi = _groups(f[f"{side}h_scale"], n) * (hi - _groups(
        f[f"{side}h_zero"].astype(jnp.float32), n))
    lo = _unpack(f[f"{side}l_codes"], 1)
    lo = _groups(f[f"{side}l_scale"], n) * (2.0 * lo - 1.0)
    rows = jnp.arange(hi.shape[-2])
    high = rows[None, None, :] < f["h"][:, :, None]
    return jnp.where(high[..., None], hi, lo)


def _f8(v, axis=None):
    """Round to float8 e4m3 with one scale per tensor (``axis=None``) or
    per slice along ``axis``."""
    s = jnp.max(jnp.abs(v), axis=axis, keepdims=axis is not None) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (v / s).astype(F8).astype(jnp.float32) * s


def _mm(x, w, mode):
    w = w.astype(jnp.float32)
    if mode == "fp8":
        w, x = _f8(w), _f8(x, axis=-1)
    return jnp.matmul(x, w, precision=HI)


def _norm(x, p, kind, eps):
    if kind == "nonparam_ln":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps)
    if kind == "rmsnorm":
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * p["w"]
    raise ValueError(f"reference has no norm {kind!r}")


def _rope(x, theta):
    """Rotary embedding over positions ``0..T-1``; ``x (B, T, H, dh)``."""
    t, dh = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    ang = np.arange(t, dtype=np.float64)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.lru_cache(maxsize=None)
def _layer_fn(mc_json: str, mode: str):
    mc = json.loads(mc_json)
    h, kvh, dh = mc["n_heads"], mc["n_kv_heads"], mc["head_dim"]
    scaling = mc["lora_alpha"] / mc["lora_rank"]
    eps = mc["norm_eps"]

    def lin(x, w, lora, name, layer):
        a = lora[name]["a"][:, layer]           # (B, r, in)
        bt = lora[name]["bt"][:, layer]         # (B, r, out)
        u = jnp.einsum("bti,bri->btr", x, a, precision=HI)
        u = jnp.einsum("btr,bro->bto", u, bt, precision=HI)
        return _mm(x, w[name]["w"][layer], mode) + scaling * u

    @jax.jit
    def layer_fn(x, sub, lora, layer):
        b, t, _ = x.shape
        mixer, ffn = sub["mixer"], sub["ffn"]
        norm_at = lambda p: jax.tree_util.tree_map(lambda v: v[layer], p)
        y = _norm(x, norm_at(sub["mixer_norm"]), mc["norm"], eps)
        la = lora["mixer"]
        q = lin(y, mixer, la, "wq", layer).reshape(b, t, kvh, h // kvh, dh)
        k = lin(y, mixer, la, "wk", layer).reshape(b, t, kvh, dh)
        v = lin(y, mixer, la, "wv", layer).reshape(b, t, kvh, dh)
        q = _rope(q.reshape(b, t, h, dh), mc["rope_theta"]).reshape(q.shape)
        k = _rope(k, mc["rope_theta"])
        s = jnp.einsum("btkgd,bskd->bkgts", q, k, precision=HI) / np.sqrt(dh)
        causal = np.tril(np.ones((t, t), bool))
        s = jnp.where(causal, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgts,bskd->btkgd", p, v, precision=HI)
        x = x + lin(o.reshape(b, t, h * dh), mixer, la, "wo", layer)
        y = _norm(x, norm_at(sub["ffn_norm"]), mc["norm"], eps)
        lf = lora["ffn"]
        g = lin(y, ffn, lf, "wg", layer)
        u = lin(y, ffn, lf, "wu", layer)
        return x + lin(jax.nn.silu(g) * u, ffn, lf, "wd", layer)

    return layer_fn


def _embed(base, mc):
    return base["embed_tied" if mc["tie_embeddings"] else "embed"]["e"]


def _head(base, mc):
    return base["embed_tied" if mc["tie_embeddings"] else "head"]["e"]


@functools.partial(jax.jit, static_argnames=("kind", "eps", "mode"))
def _logits(x, final_norm, head, where, *, kind, eps, mode):
    x = jnp.take_along_axis(x, where[..., None], axis=1)     # (B, P, d)
    x = _norm(x, final_norm, kind, eps)
    return _mm(x, head.T, mode)


def logits(mc: dict, base: dict, lora: dict, tokens: np.ndarray,
           where: np.ndarray, mode: str = "f32") -> jax.Array:
    """Float32 logits ``(B, P, V)`` at positions ``where (B, P)`` of the
    token rows ``tokens (B, T)``, sequence ``b`` adapted by ``lora``'s
    ``b``-th adapter."""
    layer_fn = _layer_fn(json.dumps(mc, sort_keys=True), mode)
    emb = _embed(base, mc).astype(jnp.float32)
    if mode == "fp8":
        emb = jax.jit(_f8)(emb)
    x = jnp.take(emb, jnp.asarray(tokens), axis=0)
    for gi, g in enumerate(layout.groups(mc)):
        for layer in range(g.count):
            for sub in g.subs:
                if (sub.mixer, sub.ffn) != ("attn", "dense"):
                    raise ValueError(f"the dense reference has no {sub}")
                x = layer_fn(x, base["groups"][gi][sub.name],
                             lora["groups"][gi][sub.name], jnp.int32(layer))
    return _logits(x, base["final_norm"], _head(base, mc),
                   jnp.asarray(where), kind=mc["norm"], eps=mc["norm_eps"],
                   mode=mode)


def adapters(mc: dict, recipe: dict, seed: int, indices) -> dict:
    """The fleet adapters at ``indices``, one row per sequence, dequantized
    into the program's LoRA tree layout: each linear's leaf is
    ``{"a": (n, L, r, in), "bt": (n, L, r, out)}``."""
    codes = fleet_codes(mc, recipe, seed, indices)
    bits = recipe["bits_high"]
    return layout.nest({p: {"a": dequantize(f, "a", bits),
                            "bt": dequantize(f, "b", bits)}
                        for p, f in codes.items()})


def logit_gaps(mc: dict, recipe: dict, seed: int, seqs, rows: int,
               length: int, positions: int, control: bool = False) -> dict:
    """Compare served greedy tokens with the reference.

    ``seqs`` holds up to ``rows`` ``(adapter_index, prompt, served)``
    triples; each row is ``prompt + served[:-1]`` right-padded to
    ``length`` tokens, and the reference's logits at the positions that
    predicted each served token (up to ``positions`` of them) are read.
    Rows and positions past the given ones are padding, so every call of a
    cell has one shape. ``gap`` is the widest margin by which a served token's logit
    lies below the reference's best there (0 where they agree). With
    ``control``, ``control_gap`` is the same margin for the tokens that the
    float8 control ranks first at those positions."""
    if len(seqs) > rows:
        raise ValueError(f"{len(seqs)} sequences exceed {rows} rows")
    base = base_params(mc, seed)
    lora = adapters(mc, recipe, seed, [s[0] for s in seqs]
                    + [seqs[0][0]] * (rows - len(seqs)))
    tokens = np.zeros((rows, length), np.int32)
    where = np.zeros((rows, positions), np.int32)
    served = np.zeros((rows, positions), np.int32)
    valid = np.zeros((rows, positions), bool)
    for i, (_, prompt, out) in enumerate(seqs):
        row = np.concatenate([prompt, out[:-1]]).astype(np.int32)
        if len(row) > length or len(out) > positions:
            raise ValueError(f"sequence of {len(row)} tokens exceeds the "
                             f"reference shape ({length}, {positions})")
        tokens[i, :len(row)] = row
        n = len(out)
        where[i, :n] = len(prompt) - 1 + np.arange(n)
        served[i, :n] = out
        valid[i, :n] = True
    ref = np.asarray(logits(mc, base, lora, tokens, where))
    best = ref.max(-1)
    pick = lambda tok: np.take_along_axis(ref, tok[..., None], -1)[..., 0]
    gaps = np.where(valid, best - pick(served), 0.0)
    out = {"gap": float(gaps.max()), "tokens": int(valid.sum()),
           "agree": float(np.mean((gaps == 0)[valid]))}
    if control:
        ctrl = np.asarray(logits(mc, base, lora, tokens, where, mode="fp8"))
        cg = np.where(valid, best - pick(ctrl.argmax(-1)), 0.0)
        out["control_gap"] = float(cg.max())
    return out
