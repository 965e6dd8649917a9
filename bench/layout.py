"""The layer groups a configuration file states, and the kinds of layer in
them.

A configuration file lists its groups under ``blocks``, in the form of the
program's ``BlockSpec``s: ``{"count": 16, "pattern": ["attn"], "ffn":
["dense"]}`` is 16 layers, each one sub-block of an ``attn`` mixer and a
``dense`` feed-forward. Sub-block ``j`` of a group pairs ``pattern[j]`` with
``ffn[j]``, and a group of ``count`` repeats holds ``count · len(pattern)``
layers. The program's trees mirror this: ``groups[g]["sub_j"]`` holds
``mixer``, ``mixer_norm``, ``ffn`` and ``ffn_norm``, each leaf stacked on a
leading axis of ``count``.

Each kind of layer is a module ``blocks/<kind>.py``, found by the name in
``pattern`` or ``ffn``. For one layer of its kind, at the widths of a
configuration dict ``mc``, it gives:

* ``base_leaves(mc)``: ``[(path, shape, dtype, init)]``, its base weights in
  a fixed draw order, ``path`` relative to the mixer or feed-forward node
  (``"wq/w"``), ``shape`` without the layer axis, ``init`` a rule of
  ``weights._init``;
* ``lora_linears(mc)``: ``{path: (in, out, lead)}``, its LoRA-targeted
  linears (``"wq"``), with ``lead`` the axes between the layer axis and the
  rank axis (the experts of a per-expert adapter), ``()`` for none;
* ``flops_per_token(mc)``: matrix-multiply FLOPs one token costs in it,
  LoRA included, counting only the experts that a token runs;
* ``attention_flops_per_key(mc)``: FLOPs of one query against one cached
  key (0 for a feed-forward kind).
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
from pathlib import Path

CODE = Path(__file__).resolve().parent
ROLES = ("mixer", "ffn")          # a sub-block's kinds, in draw order


def load(path: Path):
    """A module of the benchmark found by its file (``metrics/``,
    ``references/``, ``blocks/``)."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.cache
def kind(name: str):
    return load(CODE / "blocks" / f"{name}.py")


@dataclasses.dataclass(frozen=True)
class Sub:
    """Sub-block ``name`` of one group: its ``mixer`` and ``ffn`` kinds."""

    name: str
    mixer: str
    ffn: str


@dataclasses.dataclass(frozen=True)
class Group:
    count: int
    subs: tuple


def groups(mc: dict) -> list:
    """The configuration's layer groups, checked against its ``n_layers``."""
    out = []
    for g in mc["blocks"]:
        if len(g["pattern"]) != len(g["ffn"]):
            raise ValueError(f"group {g}: pattern and ffn differ in length")
        out.append(Group(g["count"], tuple(
            Sub(f"sub_{j}", m, f)
            for j, (m, f) in enumerate(zip(g["pattern"], g["ffn"])))))
    layers = sum(g.count * len(g.subs) for g in out)
    if layers != mc["n_layers"]:
        raise ValueError(f"blocks hold {layers} layers; n_layers is "
                         f"{mc['n_layers']}")
    return out


def roles(mc: dict):
    """``(group index, group, sub, role, kind module)`` of every mixer and
    feed-forward, in declared order."""
    for gi, g in enumerate(groups(mc)):
        for sub in g.subs:
            for role in ROLES:
                yield gi, g, sub, role, kind(getattr(sub, role))


@dataclasses.dataclass(frozen=True)
class Linear:
    """One LoRA-targeted linear: its full path in the program's LoRA tree,
    its features, and the leading axes of its stacked factors
    (``count`` layers, then ``lead``)."""

    path: str
    k: int
    m: int
    count: int
    lead: tuple


def linears(mc: dict) -> list:
    """Every LoRA-targeted linear of the model, in declared order: groups,
    sub-blocks, mixer then feed-forward, each kind's own order. Paths are
    those the program's ``iter_lora_linears`` gives
    (``/groups/0/sub_0/mixer/wq``)."""
    return [Linear(f"/groups/{gi}/{sub.name}/{role}/{rel}", k, m, g.count,
                   tuple(lead))
            for gi, g, sub, role, mod in roles(mc)
            for rel, (k, m, lead) in mod.lora_linears(mc).items()]


def nest(flat: dict) -> dict:
    """``{"/groups/0/sub_0/mixer/wq": v}`` as the program's tree
    ``{"groups": [{"sub_0": {"mixer": {"wq": v}}}]}``."""
    tree = {"groups": []}
    for path, v in flat.items():
        _, _, gi, *rest = path.split("/")
        gs = tree["groups"]
        gs.extend({} for _ in range(int(gi) + 1 - len(gs)))
        node = gs[int(gi)]
        for key in rest[:-1]:
            node = node.setdefault(key, {})
        node[rest[-1]] = v
    return tree
