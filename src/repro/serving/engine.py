"""Multi-LoRA serving engine (the paper's deployment scenario).

Components (full walkthrough in ``docs/serving.md``):

* :class:`AdapterStore` — holds many adapters *quantized* (LoRAQuant packed
  codes: the HBM-resident form) and exposes two serving forms:

  - **packed** (:meth:`AdapterStore.pack_batch`) — a device-resident lora
    tree whose leaves are :class:`repro.kernels.PackedLoRABatch` stacks of
    the requested adapters' codes. Decode reads these directly through the
    fused SGMV Pallas kernel; nothing is ever dequantized and no fp16 LoRA
    bytes exist.
  - **materialize** (:meth:`AdapterStore.materialize`) — dequantized fp LoRA
    trees through a byte-budgeted LRU; the portable reference path.

* :class:`MultiLoRAEngine` — a step-based **continuous-batching scheduler**
  (``mode="continuous"``, default): requests are admitted into free batch
  rows *mid-decode*, finished rows retire immediately, and per-row adapter
  segment ids are rebuilt every step so one fixed-shape decode program
  serves an arbitrarily churning mix of users straight from packed codes.
  Continuous mode reads those codes through the **paged adapter memory**
  (:class:`repro.serving.memory.AdapterMemoryManager`): a bounded pool of
  HBM slots (seg ids are slot ids) over a host-RAM tier holding every
  registered adapter, with admission-time page faults, one-step-ahead
  prefetch, pinning for live rows, and LRU eviction — HBM scales with the
  hot set, not the registry (see ``docs/adapter_memory.md``).
  ``mode="packed"`` keeps the static one-shot heterogeneous batch and
  ``mode="materialize"`` the S-LoRA-style per-adapter segment loop (fp tree
  swapped into the params per segment) as parity references.

Adapter onboarding is batched across *adapters* as well as layers:
``AdapterStore.register_many`` buckets every same-shape LoRA linear of every
uploaded adapter into one ``quantize_lora_stacks`` pipeline — one compiled
SVD dispatch plus one refine/quantize dispatch per distinct split ``h`` for
the whole upload batch.

Requests are plain dataclasses; generation is greedy. The engine is
synchronous by design — wrap ``engine.run()`` / ``engine.step()`` in your
RPC layer of choice.
"""

from __future__ import annotations

import collections
import dataclasses
import time
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    LoRAQuantConfig,
    QuantRecipe,
    QuantizedLoRA,
    quantize_lora,
    quantize_lora_stacks,
)
from repro.kernels import (
    PackedLoRABatch,
    PackedLoRABuckets,
    pack_adapter_layers,
    retile_packed,
    stack_packed_adapters,
)
from repro.serving.faults import (
    AdapterValidationError,
    DeadlineExceeded,
    FaultPlan,
    HostReadError,
    HostTransport,
    MemoryExhausted,
    PoisonedAdapter,
    QueueFull,
    RequestError,
    RequestStatus,
    UnknownAdapter,
    validate_lora_tree,
)
from repro.serving.telemetry import Telemetry, span


def iter_lora_linears(lora_tree) -> List[Tuple[str, Any]]:
    """Yield (path, leaf_dict) for every {'a','b'} LoRA linear in a tree."""
    out = []

    def walk(node, path):
        if isinstance(node, dict):
            if set(node.keys()) == {"a", "b"}:
                out.append((path, node))
                return
            for k, v in node.items():
                walk(v, f"{path}/{k}")
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(v, f"{path}/{i}")

    walk(lora_tree, "")
    return out


@dataclasses.dataclass
class QuantizedAdapter:
    """One user's adapter, LoRAQuant-compressed, layer-path keyed.

    Stacked layer dims (from scan) are quantized per-layer: a LoRA leaf pair
    a: (L, r, in), b: (L, out, r) becomes L independent QuantizedLoRA entries
    (the paper treats every layer's adapter separately). ``recipe`` is the
    per-adapter :class:`~repro.core.QuantRecipe` it was quantized under.
    """

    entries: Dict[str, List[QuantizedLoRA]]
    template: Any                       # lora tree of ShapeDtypeStruct-likes
    recipe: Optional[QuantRecipe] = None

    @property
    def signature(self) -> tuple:
        """Packed-layout signature (``recipe.layout_signature``): adapters
        sharing it stack into one SGMV bucket / one slot pool."""
        if self.recipe is not None:
            return self.recipe.layout_signature
        # adapters registered pre-quantized without a recipe: derive from
        # any entry's stored config
        q = next(q for qs in self.entries.values() for q in qs)
        return q.config.layout_signature

    def total_bits(self) -> int:
        return sum(q.total_bits() for qs in self.entries.values() for q in qs)

    def num_params(self) -> int:
        return sum(q.num_params() for qs in self.entries.values() for q in qs)

    def avg_bits(self) -> float:
        return self.total_bits() / max(self.num_params(), 1)


def _leaf_pairs(leaf) -> Tuple[np.ndarray, np.ndarray]:
    """One {'a','b'} leaf → flattened per-layer 3-D stacks (Ln, ·, ·)."""
    a, b = np.asarray(leaf["a"]), np.asarray(leaf["b"])
    if a.ndim == 2:
        a, b = a[None], b[None]
    a2 = a.reshape((-1,) + a.shape[-2:])
    b2 = b.reshape((-1,) + b.shape[-2:])
    return a2, b2


def quantize_adapter_tree(lora_tree, config: LoRAQuantConfig,
                          batched: bool = True) -> QuantizedAdapter:
    """Quantize every LoRA linear of an adapter tree.

    ``batched=True`` (default) buckets ALL paths' layer stacks by shape and
    runs each bucket through one vmapped pipeline (``quantize_lora_stacks``):
    one compiled SVD call per distinct leaf shape plus one refine+quantize
    call per distinct split index ``h``, instead of L-per-path independent
    Python pipelines — the onboarding-throughput path for the
    millions-of-uploaded-adapters scenario. ``batched=False`` keeps the
    per-layer loop as the reference (results match to float precision).
    """
    entries: Dict[str, List[QuantizedLoRA]] = {}
    if batched:
        order: List[str] = []
        stacks = []
        for path, leaf in iter_lora_linears(lora_tree):
            a2, b2 = _leaf_pairs(leaf)
            order.append(path)
            stacks.append((b2, a2))
        for path, qls in zip(order, quantize_lora_stacks(stacks, config)):
            entries[path] = qls
    else:
        for path, leaf in iter_lora_linears(lora_tree):
            a2, b2 = _leaf_pairs(leaf)
            entries[path] = [
                quantize_lora(jnp.asarray(b2[i]), jnp.asarray(a2[i]), config)
                for i in range(a2.shape[0])
            ]
    template = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                                      lora_tree)
    return QuantizedAdapter(entries=entries, template=template, recipe=config)


def dequantize_adapter(qa: QuantizedAdapter, like_tree) -> Any:
    """Materialize a fp LoRA tree shaped like ``like_tree``."""
    flat = {path: qs for path, qs in qa.entries.items()}

    def rebuild(node, path):
        if isinstance(node, dict):
            if set(node.keys()) == {"a", "b"}:
                qs = flat[path]
                bs, as_ = zip(*(q.materialize() for q in qs))
                # SVD reparameterization caps the factor rank at
                # min(out, r) (e.g. a 4-expert MoE router with rank-16
                # LoRA); zero-pad the rank dim back to the template —
                # zero components contribute nothing to BA.
                r = node["a"].shape[-2]
                bs = [jnp.pad(b_i, ((0, 0), (0, r - b_i.shape[1])))
                      for b_i in bs]
                as_ = [jnp.pad(a_i, ((0, r - a_i.shape[0]), (0, 0)))
                       for a_i in as_]
                a = jnp.stack(as_).reshape(node["a"].shape)
                b = jnp.stack(bs).reshape(node["b"].shape)
                return {"a": a.astype(node["a"].dtype),
                        "b": b.astype(node["b"].dtype)}
            return {k: rebuild(v, f"{path}/{k}") for k, v in node.items()}
        if isinstance(node, list):
            return [rebuild(v, f"{path}/{i}") for i, v in enumerate(node)]
        if isinstance(node, tuple):
            return tuple(rebuild(v, f"{path}/{i}") for i, v in enumerate(node))
        return node

    return rebuild(like_tree, "")


def _leaf_folds(template) -> Dict[str, int]:
    """Per-path fold factor: extra lead dims beyond the layer axis (MoE
    per-expert adapters ``(L, E, r, in)`` → E) that packing folds into the
    adapter axis of the SGMV stack. Plain ``(L, r, in)`` leaves fold 1."""
    folds: Dict[str, int] = {}
    for path, leaf in iter_lora_linears(template):
        shape = tuple(leaf["a"].shape)
        folds[path] = (int(np.prod(shape[1:-2], dtype=np.int64))
                       if len(shape) > 3 else 1)
    return folds


class AdapterStore:
    """Quantized-at-rest adapter registry with **per-adapter recipes**.

    The store holds only a *default* :class:`~repro.core.QuantRecipe`;
    every :meth:`register` / :meth:`register_many` call may override it per
    adapter, so one deployment serves a mixed-precision fleet (premium
    adapters at 3-4 bits, the long tail near 1 bit — ``docs/recipes.md``).
    Adapters whose recipes share a packed-layout signature stack into one
    SGMV bucket; :meth:`pack_batch` over mixed signatures builds
    :class:`~repro.kernels.PackedLoRABuckets` leaves (one dispatch per
    bucket per layer), while a uniform set keeps the single-stack fast
    path.

    Serving reads go through one of two forms:

    * :meth:`pack_batch` — packed device-resident stacks for the
      heterogeneous SGMV decode path (never dequantizes; per-adapter packed
      layouts are cached in ``self._packed``).
    * :meth:`materialize` — fp LoRA trees through a byte-budgeted LRU
      (``fp_cache_bytes``); only adapters actively decoding on the reference
      path pay fp16-equivalent residency.

    Re-registering an ``adapter_id`` invalidates both caches — a stale fp
    tree in the LRU would otherwise keep serving the pre-update adapter —
    and :meth:`unregister` removes an adapter outright (long-lived servers
    must be able to drop churned users instead of leaking them forever).
    Every mutation bumps a per-id version and a store-wide mutation counter;
    the paged memory tier (:class:`repro.serving.memory.AdapterMemoryManager`)
    reconciles against both instead of holding references into the store.

    ``hbm_budget_bytes`` caps the device-resident packed footprint of the
    *continuous* serving path: the memory manager derives its HBM slot count
    as ``hbm_budget_bytes // page_bytes`` (a page = one adapter's packed
    codes across all layers/paths). ``None`` means unbounded (all-resident).
    """

    def __init__(self, default_recipe: Optional[QuantRecipe] = None,
                 fp_cache_bytes: int = 1 << 30,
                 batched_quantize: bool = True,
                 hbm_budget_bytes: Optional[int] = None,
                 *, config: Optional[QuantRecipe] = None,
                 faults: Optional[FaultPlan] = None):
        if config is not None:
            warnings.warn(
                "AdapterStore(config=...) is deprecated; the store-wide "
                "config is now only the DEFAULT recipe — pass "
                "default_recipe=... (and per-adapter recipes to register)",
                DeprecationWarning, stacklevel=2)
            if default_recipe is not None:
                raise TypeError("pass either default_recipe or the "
                                "deprecated config=, not both")
            default_recipe = config
        self.default_recipe = (default_recipe if default_recipe is not None
                               else QuantRecipe())
        self.quantized: Dict[str, QuantizedAdapter] = {}
        self.fp_cache_bytes = fp_cache_bytes
        self.batched_quantize = batched_quantize
        self.hbm_budget_bytes = hbm_budget_bytes
        self._lru: "collections.OrderedDict[str, Any]" = collections.OrderedDict()
        self._packed: Dict[str, Dict[str, PackedLoRABatch]] = {}
        self._batch_cache: Dict[tuple, Any] = {}
        self._versions: Dict[str, int] = {}
        self._mutations: int = 0
        self.faults = faults               # onboarding fault injection
        self._integrity: Dict[str, Tuple[int, bool]] = {}   # aid -> (ver, ok)
        self.onboard_errors: Dict[str, str] = {}   # last register_many skips

    def _invalidate(self, adapter_id: str):
        self._lru.pop(adapter_id, None)
        self._packed.pop(adapter_id, None)
        self._batch_cache.clear()

    def _bump(self, adapter_id: str):
        self._mutations += 1
        self._versions[adapter_id] = self._mutations

    def version(self, adapter_id: str) -> Optional[int]:
        """Monotonic per-id registration epoch; ``None`` if unregistered."""
        return self._versions.get(adapter_id)

    def mutation_count(self) -> int:
        """Store-wide mutation counter (register / re-register / unregister
        all bump it) — a cheap change signal for external caches."""
        return self._mutations

    @property
    def config(self) -> QuantRecipe:
        """Deprecated alias of :attr:`default_recipe` (the store no longer
        has ONE config — recipes are per adapter)."""
        return self.default_recipe

    def recipe_of(self, adapter_id: str) -> QuantRecipe:
        """The recipe an adapter was actually quantized under. Adapters
        registered pre-quantized without one (``register_quantized``) fall
        back to their entries' stored config — NOT the store default, which
        may disagree with the codes actually resident."""
        qa = self.quantized[adapter_id]
        if qa.recipe is not None:
            return qa.recipe
        return next(q for qs in qa.entries.values() for q in qs).config

    def signature_of(self, adapter_id: str) -> tuple:
        """Packed-layout signature of one adapter (bucket / slot-pool key)."""
        return self.quantized[adapter_id].signature

    def register(self, adapter_id: str, lora_tree,
                 recipe: Optional[QuantRecipe] = None,
                 validate: bool = True) -> QuantizedAdapter:
        """Quantize and register one adapter under ``recipe`` (default: the
        store's :attr:`default_recipe`). Re-registering with a different
        recipe reconciles every cache tier exactly like a weight update —
        versions bump, packed layouts and pages rebuild.

        ``validate=True`` (default) screens the upload **before**
        quantization — NaN/Inf values, rank-mismatched factor shapes, and
        injected onboarding faults all raise
        :class:`~repro.serving.faults.AdapterValidationError` so a
        poisoned upload never enters the registry. ``validate=False`` is
        for trusted re-registration paths (and for tests exercising the
        downstream quarantine defenses)."""
        if validate:
            if self.faults is not None:
                self.faults.check_onboard(adapter_id)
            validate_lora_tree(lora_tree, adapter_id)
        qa = quantize_adapter_tree(lora_tree, recipe or self.default_recipe,
                                   batched=self.batched_quantize)
        self._invalidate(adapter_id)
        self.quantized[adapter_id] = qa
        self._bump(adapter_id)
        return qa

    def register_quantized(self, adapter_id: str, qa: QuantizedAdapter):
        self._invalidate(adapter_id)
        self.quantized[adapter_id] = qa
        self._bump(adapter_id)

    def unregister(self, adapter_id: str):
        """Drop an adapter: quantized entries, fp LRU entry, packed-layout
        and batch caches all go. Requests already decoding keep their codes
        — the paged tier marks the page *dead* and reaps it on the last
        unpin (deferred unregister, ``docs/robustness.md``); new requests
        for the id are REJECTED with
        :class:`~repro.serving.faults.UnknownAdapter`."""
        if adapter_id not in self.quantized:
            raise KeyError(f"adapter {adapter_id!r} is not registered")
        del self.quantized[adapter_id]
        self._invalidate(adapter_id)
        self._versions.pop(adapter_id, None)
        self._mutations += 1

    def register_many(self, trees: Dict[str, Any],
                      recipes: Optional[Dict[str, QuantRecipe]] = None,
                      validate: bool = True, on_error: str = "raise",
                      ) -> Dict[str, QuantizedAdapter]:
        """Onboard many uploaded adapters in one bucketed dispatch per
        recipe.

        Every same-shape LoRA linear across all trees *sharing one recipe*
        (layers × paths × adapters) lands in one ``quantize_lora_stacks``
        bucket: for N uploads of one architecture this is one compiled SVD
        call per distinct (recipe, leaf shape) — not N·paths — which is
        what bounds onboarding throughput at the many-users tier (ROADMAP:
        batched onboarding across adapters). ``recipes`` maps adapter ids
        to per-upload recipe overrides (missing ids use the default). Math
        per adapter is identical to :meth:`register`.

        ``validate=True`` screens every upload like :meth:`register`;
        ``on_error="raise"`` (default) aborts the whole batch on the first
        bad upload, ``on_error="skip"`` registers the healthy uploads and
        records the rejects in :attr:`onboard_errors` (id → message) —
        one poisoned tenant must not block the rest of the fleet.
        """
        if on_error not in ("raise", "skip"):
            raise ValueError(f"on_error must be 'raise' or 'skip', "
                             f"got {on_error!r}")
        recipes = recipes or {}
        self.onboard_errors = {}
        accepted = list(trees)
        if validate:
            accepted = []
            for adapter_id in trees:
                try:
                    if self.faults is not None:
                        self.faults.check_onboard(adapter_id)
                    validate_lora_tree(trees[adapter_id], adapter_id)
                except AdapterValidationError as e:
                    if on_error == "raise":
                        raise
                    self.onboard_errors[adapter_id] = str(e)
                else:
                    accepted.append(adapter_id)
        by_recipe: Dict[QuantRecipe, List[str]] = {}
        for adapter_id in accepted:
            rec = recipes.get(adapter_id, self.default_recipe)
            by_recipe.setdefault(rec, []).append(adapter_id)
        out: Dict[str, QuantizedAdapter] = {}
        for rec, adapter_ids in by_recipe.items():
            order: List[Tuple[str, str]] = []        # (adapter_id, path)
            stacks = []
            for adapter_id in adapter_ids:
                for path, leaf in iter_lora_linears(trees[adapter_id]):
                    a2, b2 = _leaf_pairs(leaf)
                    order.append((adapter_id, path))
                    stacks.append((b2, a2))
            results = quantize_lora_stacks(stacks, rec)
            for (adapter_id, path), qls in zip(order, results):
                qa = out.get(adapter_id)
                if qa is None:
                    template = jax.tree_util.tree_map(
                        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        trees[adapter_id])
                    qa = out[adapter_id] = QuantizedAdapter(
                        entries={}, template=template, recipe=rec)
                qa.entries[path] = qls
        for adapter_id in accepted:                  # preserve upload order
            self.register_quantized(adapter_id, out[adapter_id])
        return out

    def check_integrity(self, adapter_id: str) -> bool:
        """True iff the adapter's quantized entries are finite (float
        fields — scales/zeros; integer codes cannot encode NaN). Cached
        per registration version, so steady-state serving pays one scan
        per adapter per (re-)register, not per step."""
        ver = self._versions.get(adapter_id, -1)
        cached = self._integrity.get(adapter_id)
        if cached is not None and cached[0] == ver:
            return cached[1]
        ok = True
        qa = self.quantized[adapter_id]
        for qs in qa.entries.values():
            for q in qs:
                for leaf in jax.tree_util.tree_leaves(q):
                    arr = np.asarray(leaf)
                    if (np.issubdtype(arr.dtype, np.floating)
                            and not np.isfinite(arr).all()):
                        ok = False
                        break
                if not ok:
                    break
            if not ok:
                break
        self._integrity[adapter_id] = (ver, ok)
        return ok

    def _tree_bytes(self, tree) -> int:
        return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))

    def materialize(self, adapter_id: str, like_tree) -> Any:
        if adapter_id in self._lru:
            self._lru.move_to_end(adapter_id)
            return self._lru[adapter_id]
        tree = dequantize_adapter(self.quantized[adapter_id], like_tree)
        self._lru[adapter_id] = tree
        while (sum(self._tree_bytes(t) for t in self._lru.values())
               > self.fp_cache_bytes and len(self._lru) > 1):
            self._lru.popitem(last=False)
        return tree

    # ----- packed (serve-from-codes) form -----

    def packed_entries(self, adapter_id: str) -> Dict[str, PackedLoRABatch]:
        """Per-path packed kernel layouts ``(L, Rp, ·)`` for one adapter,
        built once from the quantized codes and cached device-resident."""
        if adapter_id not in self._packed:
            qa = self.quantized[adapter_id]
            folds = _leaf_folds(qa.template)
            self._packed[adapter_id] = {
                path: pack_adapter_layers(qs, fold=folds.get(path, 1))
                for path, qs in qa.entries.items()
            }
        return self._packed[adapter_id]

    def pack_batch(self, adapter_ids: Sequence[str], like_tree,
                   tile_t: int = 8) -> Any:
        """Build a lora tree for a heterogeneous batch over ``adapter_ids``:
        every {'a','b'} leaf becomes a :class:`PackedLoRABatch` stack
        ``(L, NA, Rp, ·)`` in adapter order — or, when the adapters'
        recipes span several packed-layout signatures, a
        :class:`PackedLoRABuckets` of one stack per signature with lookup
        tables from the batch-global adapter index to each bucket's local
        index. The tree mirrors ``like_tree`` so the model's layer scan
        consumes it unchanged; attach per-token segment ids at
        ``lora["seg"]`` (batch-global adapter index per flattened row).

        The stacked tree is cached per adapter-id tuple (a serving loop
        re-batching the same hot adapter set pays the ``jnp.stack`` cost
        once); any re-register invalidates the cache. ``like_tree`` only
        provides structure, so the cache key ignores it.
        """
        key = (tuple(adapter_ids), tile_t)
        cached = self._batch_cache.get(key)
        if cached is not None:
            return cached
        per = [self.packed_entries(a) for a in adapter_ids]
        sigs = [self.signature_of(a) for a in adapter_ids]
        buckets = sorted(set(sigs))
        na = len(adapter_ids)
        # per bucket: member positions in batch order + the global→local map
        members = [[i for i in range(na) if sigs[i] == sig]
                   for sig in buckets]
        luts = []
        for idx in members:
            lut = np.full((na,), -1, np.int32)
            lut[np.asarray(idx, np.int32)] = np.arange(len(idx),
                                                       dtype=np.int32)
            luts.append(lut)

        def rebuild(node, path):
            if isinstance(node, dict):
                if set(node.keys()) == {"a", "b"}:
                    shape = tuple(node["a"].shape)
                    if len(shape) < 3:
                        raise NotImplementedError(
                            f"packed serving needs stacked (L, ..., r, in) "
                            f"layer leaves; {path} has unscanned 2-D shape "
                            f"{shape} — serve it with mode='materialize'")
                    # extra lead dims (MoE experts) are folded into the
                    # adapter axis by the packed entries' ``fold`` meta
                    if len(buckets) == 1:       # uniform recipes: the exact
                        return stack_packed_adapters(   # single-stack path
                            [p[path] for p in per], tile_t=tile_t)
                    stacks = [stack_packed_adapters([per[i][path]
                                                     for i in idx],
                                                    tile_t=tile_t)
                              for idx in members]
                    n_layers = stacks[0].ah_codes.shape[0]
                    return PackedLoRABuckets(
                        buckets=tuple(stacks),
                        lookups=tuple(
                            jnp.broadcast_to(jnp.asarray(lut),
                                             (n_layers, na))
                            for lut in luts),
                        seg=None)
                return {k: rebuild(v, f"{path}/{k}") for k, v in node.items()}
            if isinstance(node, list):
                return [rebuild(v, f"{path}/{i}") for i, v in enumerate(node)]
            if isinstance(node, tuple):
                return tuple(rebuild(v, f"{path}/{i}") for i, v in enumerate(node))
            return node

        tree = rebuild(like_tree, "")
        self._batch_cache[key] = tree
        return tree

    # ----- accounting -----

    def resident_bits(self) -> int:
        return sum(qa.total_bits() for qa in self.quantized.values())

    def fp_resident_bytes(self) -> int:
        """Bytes of dequantized fp LoRA trees currently held by the LRU —
        0 whenever serving runs purely from packed codes."""
        return sum(self._tree_bytes(t) for t in self._lru.values())

    def packed_cache_bytes(self) -> int:
        """Bytes of device-resident packed layouts held by the *static*
        serving paths (per-adapter entries + stacked batch trees). The paged
        continuous path holds its pages in the memory manager instead and
        keeps these caches empty."""
        return (sum(self._tree_bytes(v) for v in self._packed.values())
                + sum(self._tree_bytes(v) for v in self._batch_cache.values()))

    def stats(self) -> Dict[str, float]:
        n = len(self.quantized)
        bits = self.resident_bits()
        params = sum(qa.num_params() for qa in self.quantized.values())
        return {
            "adapters": n,
            "recipes": len({qa.signature for qa in self.quantized.values()}),
            "avg_bits": bits / max(params, 1),
            "quantized_mb": bits / 8 / 1e6,
            "fp16_equiv_mb": params * 2 / 1e6,
            "fp_lru_mb": self.fp_resident_bytes() / 1e6,
            "packed_cache_mb": self.packed_cache_bytes() / 1e6,
            "hbm_budget_mb": (self.hbm_budget_bytes / 1e6
                              if self.hbm_budget_bytes is not None
                              else float("inf")),
        }

    def adapter_stats(self) -> Dict[str, Dict[str, Any]]:
        """Per-adapter serving stats: achieved ``avg_bits`` and the recipe
        name — the fleet view behind the store-wide average."""
        return {
            adapter_id: {"avg_bits": qa.avg_bits(),
                         "recipe": self.recipe_of(adapter_id).variant_name}
            for adapter_id, qa in self.quantized.items()
        }


@dataclasses.dataclass
class Request:
    """One generation request with its lifecycle state.

    ``status`` walks PENDING → RUNNING → DONE on the happy path; the
    terminal failure states (REJECTED / TIMED_OUT / FAILED) carry a
    structured ``error`` from the :mod:`repro.serving.faults` taxonomy and
    keep whatever tokens were produced (``docs/robustness.md``).
    ``deadline_ms`` is the total wall-clock budget from submit;
    ``ttft_deadline_ms`` bounds the wait for the *first* token — both are
    checked every scheduler step.
    """

    request_id: int
    adapter_id: str
    prompt: np.ndarray          # (T,) int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None        # retire early when this token appears
    deadline_ms: Optional[float] = None      # total budget (submit → done)
    ttft_deadline_ms: Optional[float] = None  # budget to the first token
    output: Optional[np.ndarray] = None
    # clock at which the first generated token reached the host; continuous
    # mode publishes it one read-back later, with the second token
    t_first: Optional[float] = None
    t_submit: Optional[float] = None    # wall clock of submit (deadline base)
    status: RequestStatus = RequestStatus.PENDING
    error: Optional[RequestError] = None


@dataclasses.dataclass
class _Row:
    """One live batch-row slot of the continuous scheduler."""

    req: Request
    start: int                  # left-pad count (first real cache index)
    prompt_len: int
    # tokens read back to the host so far; the newest one or two are still
    # on the device while the row's next decode runs
    emitted: List[int] = dataclasses.field(default_factory=list)
    decoded: int = 0            # decode steps dispatched for this row
    t_first: Optional[float] = None   # first token's arrival (unpublished)
    # NOTE the row does NOT cache its adapter's HBM slot id: the page is
    # pinned for the row's lifetime, but its GLOBAL id can shift (a pool
    # growth moves later pools' bases; a re-register with a new recipe
    # moves the page across pools), so decode re-reads memory.slot_of
    # every step.


def _greedy(out):
    """``(logits, caches)`` of a prefill or decode program to ``(tokens,
    caches)``: each row's argmax at its last position, as int32, inside the
    program, so that greedy tokens stay on the device."""
    logits, caches = out
    return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32), caches


def decode_step(decode, params, tokens, caches, pos, start):
    """The continuous scheduler's decode program (``jit_decode_step``): the
    model's decode program ``decode`` (a static argument) on each row's
    newest token, ``tokens: (B,)`` on the device, then the greedy argmax.
    Its output tokens are the next call's input, so the host never has to
    hold them before it dispatches the next step."""
    return _greedy(decode(params, tokens[:, None], caches, pos, start))


class MultiLoRAEngine:
    """Step-based continuous-batching scheduler over many users' adapters.

    ``mode="continuous"`` (default): the engine owns ``max_rows`` batch-row
    slots backed by one persistent decode cache. :meth:`step` advances every
    active row by one greedy decode step, admits pending requests into free
    rows mid-decode (bursts of equal padded length are prefilled as one
    batch — left-padded only to a ``seg_tile`` multiple — and their caches
    scattered into the rows' slices in one call),
    and retires rows in the step whose read-back delivers their last token
    (``max_new_tokens`` or ``eos_id``), freeing the slot for the next
    admission. Greedy tokens stay on the device: step n dispatches decode n
    before it reads back decode n−1's tokens, so the host's work hides
    under the device's. Per-row cache positions and
    validity masks make every row position-exact regardless of padding, so
    a request admitted mid-decode yields exactly the tokens of a solo run.
    Per-row adapter choice is a per-step rebuild of the SGMV segment ids
    (``lora["seg"]``) over the store-wide packed stack — row↔adapter
    swaps are free. :meth:`run` is a loop over :meth:`step`.

    ``mode="packed"``: the static reference — ALL pending requests as ONE
    heterogeneous left-padded batch, decoded to the longest request.

    ``mode="materialize"``: the S-LoRA-style per-adapter segment loop over
    dequantized fp trees (the portable reference).

    All three modes mask pad slots out of attention and use real (unpadded)
    rotary positions, so their outputs agree token-for-token with each
    other and with unpadded solo serving (attention architectures; see
    docs/serving.md for the recurrent-state caveat).

    **Adapter memory.** Continuous mode reads packed codes through a paged
    two-tier memory (:class:`repro.serving.memory.AdapterMemoryManager`):
    a fixed pool of HBM slots holds the hot adapters (row seg ids *are*
    slot ids), the full registry stays in host RAM as numpy, and admission
    faults pages in — with next-wave prefetch issued one step ahead so the
    transfer overlaps decode — while LRU eviction reclaims unpinned slots.
    ``hbm_slots`` (or ``store.hbm_budget_bytes``) bounds the pool;
    ``None`` keeps every registered adapter resident (the pool grows),
    which is the classic packed behavior. See ``docs/adapter_memory.md``.
    """

    def __init__(self, model, base_params, store: AdapterStore,
                 cache_capacity: int = 512, mode: str = "continuous",
                 seg_tile: int = 8,
                 max_rows: int = 8, hbm_slots: Optional[int] = None,
                 queue_limit: Optional[int] = None,
                 queue_policy: str = "reject",
                 hol_bypass: bool = True, stall_limit: int = 3,
                 default_deadline_ms: Optional[float] = None,
                 faults: Optional[FaultPlan] = None,
                 transport: Optional[HostTransport] = None,
                 telemetry: Optional[Telemetry] = None,
                 clock=None):
        if queue_policy not in ("reject", "shed_oldest"):
            raise ValueError(f"queue_policy must be 'reject' or "
                             f"'shed_oldest', got {queue_policy!r}")
        self.model = model
        self.params = base_params         # {"base", "lora"(template)}
        self.store = store
        self.capacity = cache_capacity
        self.mode = mode
        self.seg_tile = seg_tile
        self.max_rows = max_rows
        self.hbm_slots = hbm_slots
        self.queue_limit = queue_limit
        self.queue_policy = queue_policy
        self.hol_bypass = hol_bypass
        self.stall_limit = stall_limit
        self.default_deadline_ms = default_deadline_ms
        self.faults = faults
        self.transport = transport
        self.telemetry = telemetry
        # every timestamp the engine takes (deadlines, TTFT, traces) comes
        # from ONE injectable monotonic clock: a telemetry object's clock
        # by default, so trace timestamps and deadline sweeps agree, and a
        # ManualClock under test makes all of them deterministic
        if clock is not None:
            self.clock = clock
        elif telemetry is not None:
            self.clock = telemetry.clock
        else:
            self.clock = time.perf_counter
        self._wave = 0                    # admission-wave ordinal (telemetry)
        self._step_count = 0              # decode steps
        self._step_calls = 0              # step() calls that did work
        self.pending: List[Request] = []
        # adapters quarantined at fault time: id -> store version when
        # quarantined (a re-register bumps the version and auto-clears)
        self.quarantined: Dict[str, Optional[int]] = {}
        # requests terminated outside step() (queue shedding) — drained
        # into the next step's finished list so callers see every terminal
        self._terminated: List[Request] = []
        self._stalled_steps = 0
        self._rows: List[Optional[_Row]] = [None] * max_rows
        self._caches = None               # persistent (max_rows)-row caches
        self._tok = None                  # (max_rows,) newest token per row
        self._unread: List[int] = []      # rows whose newest token is unread
        self._memory = None               # paged adapter memory (lazy)
        self._dec_groups = None           # decode-retiled view of the pool
        self._dec_src = None              # the packed tree it was built from
        # the static modes' programs return logits ...
        self._prefill = jax.jit(
            lambda p, b: model.prefill(p, b, cache_capacity))
        self._decode = jax.jit(model.decode_step)
        # ... continuous mode's return greedy tokens, left on the device.
        # Its decode composes whatever program ``_decode`` holds, so a
        # program swapped in there (the benchmark's planted faults,
        # ``bench/faults.py``) reaches the served path too
        self._prefill_greedy = jax.jit(
            lambda p, b: _greedy(model.prefill(p, b, cache_capacity)))
        self._decode_greedy = jax.jit(decode_step, static_argnums=0)
        # scatter a group's prefilled cache rows and first tokens into the
        # persistent batch: cache leaves are (layer_count, B, ...), so row
        # indices land on axis 1 of every leaf
        self._scatter_rows = jax.jit(
            lambda g, r, idx, tok, first: (jax.tree_util.tree_map(
                lambda gg, rr: gg.at[:, idx].set(rr.astype(gg.dtype)), g, r),
                tok.at[idx].set(first)))

    # ----- request lifecycle -----

    def _finalize(self, req: Request, status: RequestStatus,
                  error: Optional[RequestError] = None) -> Request:
        """Move a request to a terminal state. Terminal requests always
        carry ``output`` (possibly empty) so callers never branch on
        ``None``; non-DONE terminals carry the structured ``error``.
        Every terminal transition flows through here — the single place
        the telemetry layer observes E2E latency and retire causes."""
        req.status = status
        req.error = error
        if req.output is None:
            req.output = np.zeros((0,), np.int32)
        if self.telemetry is not None:
            cause = error.kind if error is not None else "ok"
            self.telemetry.on_retire(req.request_id, status.name.lower(),
                                     cause, len(req.output))
        return req

    def _quarantine(self, adapter_id: str):
        self.quarantined[adapter_id] = self.store.version(adapter_id)

    def _is_quarantined(self, adapter_id: str) -> bool:
        """Quarantine is keyed to the registration version at fault time:
        a re-register (fixed upload) bumps the version and clears it."""
        if adapter_id not in self.quarantined:
            return False
        ver = self.store.version(adapter_id)
        if ver is not None and ver != self.quarantined[adapter_id]:
            del self.quarantined[adapter_id]     # re-registered: recovered
            return False
        return True

    @staticmethod
    def _queue_expired(req: Request,
                       now: float) -> Optional[DeadlineExceeded]:
        """Deadline check for a request still waiting in the queue (no
        tokens yet): both the TTFT and the total budget bound the wait."""
        if req.t_submit is None:
            return None
        waited_ms = (now - req.t_submit) * 1e3
        for name, budget in (("ttft", req.ttft_deadline_ms),
                             ("total", req.deadline_ms)):
            if budget is not None and waited_ms > budget:
                return DeadlineExceeded(
                    f"request {req.request_id}: {name} deadline "
                    f"({budget:g} ms) expired after {waited_ms:.1f} ms in "
                    f"queue", adapter_id=req.adapter_id)
        return None

    def _reject_now(self, req: Request) -> Optional[Request]:
        """Submit-time screening: unknown and quarantined adapters are
        terminal immediately (never enqueued)."""
        if self._is_quarantined(req.adapter_id):
            return self._finalize(req, RequestStatus.FAILED, PoisonedAdapter(
                f"request {req.request_id}: adapter {req.adapter_id!r} is "
                f"quarantined", adapter_id=req.adapter_id))
        if req.adapter_id not in self.store.quantized:
            return self._finalize(req, RequestStatus.REJECTED, UnknownAdapter(
                f"request {req.request_id}: adapter {req.adapter_id!r} is "
                f"not registered in the AdapterStore",
                adapter_id=req.adapter_id))
        return None

    def submit(self, req: Request) -> Request:
        """Enqueue a request, returning it with its (possibly already
        terminal) status.

        Screening happens **here**, not deep inside admission: an unknown
        or unregistered adapter id is REJECTED with
        :class:`~repro.serving.faults.UnknownAdapter`; a quarantined
        adapter FAILS with :class:`~repro.serving.faults.PoisonedAdapter`.
        With a bounded queue (``queue_limit``) the backpressure policy
        decides who pays: ``"reject"`` rejects the new arrival with
        :class:`~repro.serving.faults.QueueFull`; ``"shed_oldest"`` admits
        it and rejects the oldest still-queued request instead (the shed
        request is returned from the next :meth:`step`).
        """
        if req.t_submit is None:
            req.t_submit = self.clock()
        if req.deadline_ms is None:
            req.deadline_ms = self.default_deadline_ms
        if self.telemetry is not None:
            self.telemetry.on_submit(req.request_id, req.adapter_id)
        if self._reject_now(req) is not None:
            return req
        if (self.queue_limit is not None
                and len(self.pending) >= self.queue_limit):
            if self.queue_policy == "reject":
                return self._finalize(req, RequestStatus.REJECTED, QueueFull(
                    f"request {req.request_id}: pending queue full "
                    f"({self.queue_limit})", adapter_id=req.adapter_id))
            shed = self.pending.pop(0)           # shed_oldest
            self._terminated.append(self._finalize(
                shed, RequestStatus.REJECTED, QueueFull(
                    f"request {shed.request_id}: shed by newer arrival "
                    f"under shed_oldest backpressure",
                    adapter_id=shed.adapter_id)))
        req.status = RequestStatus.PENDING
        self.pending.append(req)
        return req

    def _segments(self, reqs: Sequence[Request]) -> Dict[str, List[Request]]:
        segs: Dict[str, List[Request]] = collections.defaultdict(list)
        for r in reqs:
            segs[r.adapter_id].append(r)
        return segs

    def _tmax(self, reqs: Sequence[Request]) -> int:
        t = max(len(r.prompt) for r in reqs)
        return -(-t // self.seg_tile) * self.seg_tile

    # ----- static reference paths (one batch, drained to completion) -----

    def _generate(self, params_prefill, params_decode,
                  reqs: Sequence[Request], tmax: int) -> None:
        """Shared static greedy loop: left-pad to ``tmax`` (position-exact:
        per-row ``start`` masks pad slots and shifts rotary positions),
        prefill once, decode to the longest request, slice each output."""
        toks = np.stack([
            np.pad(r.prompt, (tmax - len(r.prompt), 0))    # left-pad
            for r in reqs
        ]).astype(np.int32)
        starts = np.asarray([tmax - len(r.prompt) for r in reqs], np.int32)
        logits, caches = self._prefill(params_prefill,
                                       {"tokens": jnp.asarray(toks),
                                        "start": jnp.asarray(starts)})
        last = jnp.argmax(logits[:, -1, :], axis=-1)
        now = self.clock()
        for r in reqs:
            r.t_first = now
            r.status = RequestStatus.RUNNING
            if self.telemetry is not None:
                self.telemetry.on_first_token(r.request_id)
        n_new = max(r.max_new_tokens for r in reqs)
        outs = [last]
        start_arr = jnp.asarray(starts)
        b = len(reqs)
        for k in range(n_new - 1):
            pos = jnp.full((b,), tmax + k, jnp.int32)
            logits, caches = self._decode(
                params_decode, last[:, None], caches, pos, start_arr)
            last = jnp.argmax(logits[:, -1, :], axis=-1)
            outs.append(last)
        gen = np.stack([np.asarray(o) for o in outs], axis=1)  # (B, n_new)
        for i, r in enumerate(reqs):
            out = gen[i, : r.max_new_tokens].astype(np.int32)
            if r.eos_id is not None:
                hits = np.nonzero(out == r.eos_id)[0]
                if hits.size:
                    out = out[: hits[0] + 1]
            r.output = out
            self._finalize(r, RequestStatus.DONE)

    def _run_packed(self, reqs: List[Request]) -> List[Request]:
        """One heterogeneous batch: decode straight from packed codes."""
        ids = sorted({r.adapter_id for r in reqs})   # canonical → cache-stable
        aidx = np.asarray([ids.index(r.adapter_id) for r in reqs], np.int32)
        tmax = self._tmax(reqs)
        packed = self.store.pack_batch(ids, self.params["lora"],
                                       tile_t=self.seg_tile)
        # prefill: each padded prompt is tmax rows (a whole number of
        # seg_tile token tiles, all one adapter); decode: one row per
        # sequence, tile_t = 1.
        pre = {"base": self.params["base"],
               "lora": {"groups": packed["groups"],
                        "seg": jnp.repeat(jnp.asarray(aidx), tmax)}}
        dec = {"base": self.params["base"],
               "lora": {"groups": retile_packed(packed, 1)["groups"],
                        "seg": jnp.asarray(aidx)}}
        self._generate(pre, dec, reqs, tmax)
        return reqs

    def _run_materialize(self, reqs: List[Request]) -> List[Request]:
        """Reference segment loop over dequantized fp trees (LRU-cached)."""
        tmax = self._tmax(reqs)
        for adapter_id, seg_reqs in self._segments(reqs).items():
            lora = self.store.materialize(adapter_id, self.params["lora"])
            params = {"base": self.params["base"], "lora": lora}
            self._generate(params, params, seg_reqs, tmax)
        return reqs

    # ----- continuous scheduler -----

    @property
    def memory(self):
        """The paged adapter memory backing continuous mode (lazy: built on
        first use so static-mode engines never allocate a pool)."""
        if self._memory is None:
            from repro.serving.memory import AdapterMemoryManager

            self._memory = AdapterMemoryManager(
                self.store, self.params["lora"], num_slots=self.hbm_slots,
                tile_t=self.seg_tile,
                transport=self.transport, faults=self.faults,
                telemetry=self.telemetry)
        return self._memory

    def memory_stats(self) -> Dict[str, float]:
        """Hit/miss/swap/eviction counters and per-tier bytes of the paged
        adapter memory (empty dict before the first continuous step)."""
        return self._memory.stats() if self._memory is not None else {}

    def stats(self) -> Dict[str, Any]:
        """Scheduler counters as a thin view over the telemetry registry.

        Always carries the live scheduler state (``pending`` /
        ``active_rows`` / ``quarantined``); with a :class:`Telemetry`
        attached it adds submitted/step/wave/token totals, terminal counts
        by status, and p50/p95/p99 latency summaries for TTFT, E2E, and
        queue wait (``None``-valued percentiles when a histogram is
        empty). Without telemetry only the live state is reported —
        the engine keeps no shadow counters of its own.
        """
        out: Dict[str, Any] = {
            "pending": len(self.pending),
            "active_rows": self.active_rows,
            "quarantined": len(self.quarantined),
            "decode_steps": self._step_count,
            "admission_waves": self._wave,
        }
        if self.telemetry is None:
            return out
        reg = self.telemetry.registry
        out["submitted"] = int(reg.value("serving_requests_submitted_total"))
        out["tokens"] = int(reg.value("serving_tokens_total"))
        by_status: Dict[str, int] = {}
        by_cause: Dict[str, int] = {}
        for m in reg.series("serving_requests_total"):
            labels = dict(m.labels)
            s, c = labels.get("status", ""), labels.get("cause", "")
            by_status[s] = by_status.get(s, 0) + int(m.value)
            by_cause[c] = by_cause.get(c, 0) + int(m.value)
        out["finished"] = by_status
        out["retire_causes"] = by_cause
        out["latency"] = self.telemetry.latency_summary()
        return out

    def _tpad(self, req: Request) -> int:
        return max(self.seg_tile,
                   -(-len(req.prompt) // self.seg_tile) * self.seg_tile)

    def _admit_group(self, reqs: List[Request], rows: List[int],
                     slots: List[int]) -> Tuple[int, List[int], int, float]:
        """Prefill a group of same-padded-length requests as ONE batch
        (left-padded to a shared ``seg_tile`` multiple — the group's rows
        stay independent under the pad-mask contract) and scatter their
        cache rows and first tokens into the persistent batch in one call.
        The first tokens stay on the device: the step's read-back brings
        them to the host. Batching the admissions amortizes per-dispatch
        overhead when requests arrive in bursts; a lone arrival is simply a
        group of one. ``slots`` maps each request to its adapter's (already
        pinned) HBM slot — the SGMV segment id; a request whose page was
        faulted in this step is simply queued behind the swap-in by
        dispatch order. Returns the group's wave, request ids, padded
        length and dispatch time, for the prefill's telemetry."""
        tel = self.telemetry
        tpad = self._tpad(reqs[0])
        with span("engine.prefill", tel, rows=len(reqs), tpad=tpad):
            sidx = np.asarray(slots, np.int32)
            starts = np.asarray([tpad - len(r.prompt) for r in reqs],
                                np.int32)
            toks = np.stack([
                np.pad(np.asarray(r.prompt), (tpad - len(r.prompt), 0))
                for r in reqs
            ]).astype(np.int32)
            self._wave += 1
            if tel is not None:
                for req, row_idx in zip(reqs, rows):
                    tel.on_admit(req.request_id, self._wave, row_idx)
            t_pre = self.clock()
            # fetch the tree AFTER acquire()s: this step's swap-ins are in it
            packed = self.memory.serving_tree()
            pre = {"base": self.params["base"],
                   "lora": {"groups": packed["groups"],
                            "seg": jnp.asarray(np.repeat(sidx, tpad))}}
            firsts, grp_caches = self._prefill_greedy(
                pre, {"tokens": jnp.asarray(toks),
                      "start": jnp.asarray(starts)})
        with span("engine.scatter", tel, rows=len(rows)):
            self._caches, self._tok = self._scatter_rows(
                self._caches, grp_caches,
                jnp.asarray(np.asarray(rows, np.int32)), self._tok, firsts)
        for b, (req, row_idx) in enumerate(zip(reqs, rows)):
            req.status = RequestStatus.RUNNING
            self._rows[row_idx] = _Row(req=req, start=int(starts[b]),
                                       prompt_len=len(req.prompt))
        self._unread.extend(rows)
        return self._wave, [r.request_id for r in reqs], int(tpad), t_pre

    @staticmethod
    def _row_done(row: _Row) -> bool:
        r = row.req
        return (len(row.emitted) >= r.max_new_tokens
                or (r.eos_id is not None and row.emitted[-1:] == [r.eos_id]))

    def _needs_decode(self, row: _Row) -> bool:
        """Whether a live row takes part in the next decode: it has decodes
        left to dispatch (``max_new_tokens - 1``; the prefill gives the
        first token) and has not read back its EOS."""
        return (row.decoded < row.req.max_new_tokens - 1
                and not self._row_done(row))

    def _publish_first(self, row: _Row) -> None:
        """Make the row's first-token stamp visible on its request (and in
        telemetry) with the value it was taken at."""
        req = row.req
        if req.t_first is None and row.t_first is not None:
            req.t_first = row.t_first
            if self.telemetry is not None:
                self.telemetry.on_first_token(req.request_id, row.t_first)

    def _read_back(self, tok, rows: List[int]) -> float:
        """Wait for the device token array ``tok`` on the host and give each
        of ``rows`` its entry. A row's first token stamps its ``t_first``;
        the stamp is published with the row's second token (or at its
        retirement), so that a request's ``t_first`` becomes visible in the
        same :meth:`step` return as its first two tokens. Returns the clock
        at arrival (now, where there is nothing to read)."""
        if not rows:
            return self.clock()
        vals = np.asarray(tok)
        now = self.clock()
        for i in rows:
            row = self._rows[i]
            row.emitted.append(int(vals[i]))
            if len(row.emitted) == 1:
                row.t_first = now
            else:
                self._publish_first(row)
        return now

    def _retire(self, row_idx: int,
                status: RequestStatus = RequestStatus.DONE,
                error: Optional[RequestError] = None) -> Request:
        row = self._rows[row_idx]
        self._rows[row_idx] = None
        self.memory.unpin(row.req.adapter_id)   # slot becomes evictable
        if row_idx in self._unread:
            # a decode dispatched before this row's EOS was read back: its
            # token is never read
            self._unread.remove(row_idx)
            if self.telemetry is not None:
                self.telemetry.on_discarded_token()
        self._publish_first(row)
        # prefill always seeds one token; cap at the budget so degenerate
        # max_new_tokens <= 0 requests match the static modes' empty output.
        # Failure retirements keep the partial output produced so far.
        row.req.output = np.asarray(
            row.emitted[: max(row.req.max_new_tokens, 0)], np.int32)
        return self._finalize(row.req, status, error)

    def _prefetch_upcoming(self):
        """Stage the next admission wave's adapter pages one step ahead.
        Called after this step's decode view is built and before the decode
        dispatch, so the host→HBM copies overlap the decode compute."""
        upcoming: List[str] = []
        seen = set()
        for r in self.pending[: self.max_rows]:
            if (r.adapter_id not in seen
                    and r.adapter_id in self.store.quantized
                    and not self._is_quarantined(r.adapter_id)):
                seen.add(r.adapter_id)
                upcoming.append(r.adapter_id)
        if upcoming:
            self.memory.prefetch(upcoming)

    def _select_admissions(self, n_free: int,
                           finished: List[Request]) -> List[Request]:
        """Pick this step's admission group from the pending queue.

        FIFO over the queue with the failure contract applied per request:
        quarantined adapters FAIL, unregistered ones are REJECTED (neither
        consumes a row); requests padding to a different length than the
        group's anchor wait for the next wave (one prefill batch has ONE
        padded length). ``memory.acquire`` maps each admitted adapter to a
        pinned slot — a poisoned page quarantines the adapter and FAILS
        the request, a persistently failing host read REJECTS it with
        :class:`~repro.serving.faults.MemoryExhausted`, and an all-pinned
        pool stalls the wave: with ``hol_bypass`` requests for
        still-resident adapters may jump the stalled head (a residency hit
        pins an existing page and steals no slot), anyone else waits in
        order. The group's pages are all pinned on return; read slot ids
        *after* the whole group's acquires (a later acquire may grow a
        pool and shift earlier global ids).
        """
        mgr = self.memory
        group: List[Request] = []
        rest: List[Request] = []
        tpad0: Optional[int] = None
        stalled = False
        for k, r in enumerate(self.pending):
            if len(group) >= n_free:
                rest.extend(self.pending[k:])
                break
            if self._is_quarantined(r.adapter_id):
                finished.append(self._finalize(
                    r, RequestStatus.FAILED, PoisonedAdapter(
                        f"request {r.request_id}: adapter "
                        f"{r.adapter_id!r} is quarantined",
                        adapter_id=r.adapter_id)))
                continue
            if r.adapter_id not in self.store.quantized:
                finished.append(self._finalize(
                    r, RequestStatus.REJECTED, UnknownAdapter(
                        f"request {r.request_id}: adapter "
                        f"{r.adapter_id!r} is not registered in the "
                        f"AdapterStore", adapter_id=r.adapter_id)))
                continue
            if tpad0 is not None and self._tpad(r) != tpad0:
                rest.append(r)
                continue
            if stalled and not (self.hol_bypass
                                and mgr.resident(r.adapter_id)):
                rest.append(r)
                continue
            try:
                slot = mgr.acquire(r.adapter_id)
            except PoisonedAdapter as e:
                self._quarantine(r.adapter_id)
                finished.append(self._finalize(r, RequestStatus.FAILED, e))
                continue
            except HostReadError as e:
                finished.append(self._finalize(
                    r, RequestStatus.REJECTED, MemoryExhausted(
                        str(e), adapter_id=r.adapter_id)))
                continue
            if slot is None:
                stalled = True             # every slot pinned right now
                rest.append(r)
                continue
            if tpad0 is None:
                tpad0 = self._tpad(r)
            group.append(r)
        self.pending = rest
        return group

    def step(self) -> List[Request]:
        """Advance the continuous scheduler by one decode step.

        0. **Sweep**: requests shed at submit time drain into the finished
           list; queued requests past their TTFT/total deadline retire
           TIMED_OUT; adapters whose pages failed integrity at fault time
           are quarantined and their live rows retire FAILED (co-batched
           healthy rows are untouched — per-row seg ids isolate them);
           live rows past their total deadline retire TIMED_OUT with the
           partial output. A forced retirement first reads back the tokens
           still on the device (a blocking read), so it keeps every token
           decoded.
        1. **Admit**: move pending requests into free rows (FIFO with the
           failure contract — :meth:`_select_admissions`; bursts of equal
           padded length prefill as one batch → scatter of cache rows and
           first tokens, which stay on the device). When every slot is
           pinned by live rows the request stays pending — and if
           *nothing* is live to ever unpin (externally pinned pool),
           ``stall_limit`` fruitless steps reject the head with
           MemoryExhausted so admission can never deadlock.
        2. **Prep** and **prefetch**: per-row cache positions/validity and
           adapter **slot** ids as SGMV seg ids — all known on the host —
           for the rows with decodes left; other rows run fully masked.
           Next wave's pages are prefetched (swap-ins write fresh buffers,
           so the copies overlap the decode).
        3. **Decode** n: one greedy step for the whole fixed-shape batch
           (``jit_decode_step``), its input the device token array the
           previous decode returned, its argmax left on the device.
        4. **Read back** decode n−1's tokens and this step's first tokens
           (``engine.decode.sync``) while decode n runs: the host's
           read-back, and the next step's prep and dispatch, hide under the
           device's work.
        5. **Retire**: rows whose read-back delivered their last token
           (``max_new_tokens``, or ``eos_id`` — a decode already in flight
           for such a row is discarded) free their batch row, unpin their
           adapter slot, and their request (with ``output`` set, status
           DONE) is returned.

        So a request's tokens reach the host one step after they are
        decoded. Its ``t_first`` is published in the ``step()`` return that
        delivers its second token, holding the clock at which its first
        arrived; every later step delivers one token per live row.

        Returns the requests that reached a terminal state during this
        step, completion-ordered. The step and each of its phases run under
        a named :class:`~repro.serving.telemetry.span` (``engine.step``,
        ``engine.sweep``, ``engine.admit``, ``engine.decode``, ...; the
        catalogue is in ``docs/observability.md``).
        """
        finished: List[Request] = list(self._terminated)
        self._terminated = []
        if not self.pending and all(r is None for r in self._rows):
            return finished
        t_step = self.clock()
        self._step_calls += 1
        with span("engine.step", self.telemetry,
                  step=self._step_calls) as root:
            rows, admitted = self._step(finished, t_step)
            root.set(rows=rows, admitted=admitted, pending=len(self.pending))
        return finished

    def _step(self, finished: List[Request],
              t_step: float) -> Tuple[int, int]:
        """The body of :meth:`step`, under its ``engine.step`` span: appends
        the step's terminal requests to ``finished`` and returns the rows
        it decoded and the requests it admitted."""
        tel = self.telemetry
        mgr = self.memory
        with span("engine.sweep", tel) as sweep:
            n_done = len(finished)
            mgr.refresh()                  # reconcile store mutations
            now = self.clock()
            # queue-deadline sweep: expired waiters retire without a row
            still: List[Request] = []
            for r in self.pending:
                err = self._queue_expired(r, now)
                if err is not None:
                    finished.append(
                        self._finalize(r, RequestStatus.TIMED_OUT, err))
                else:
                    still.append(r)
            self.pending = still
            # poison sweep: the memory layer records integrity failures it
            # detects at page-read time; DRAIN them into quarantine,
            # skipping records whose adapter was re-registered since the
            # failure (a fixed upload must not be re-quarantined), and
            # evict their rows FAILED, leaving co-batched rows token-exact
            while mgr.poisoned:
                aid, ver = mgr.poisoned.popitem()
                if self.store.version(aid) == ver:
                    self.quarantined[aid] = ver
            forced = []
            for i in range(self.max_rows):
                row = self._rows[i]
                if row is None:
                    continue
                req = row.req
                if self._is_quarantined(req.adapter_id):
                    forced.append((i, RequestStatus.FAILED, PoisonedAdapter(
                        f"request {req.request_id}: adapter "
                        f"{req.adapter_id!r} was quarantined mid-decode",
                        adapter_id=req.adapter_id)))
                elif (req.deadline_ms is not None
                        and req.t_submit is not None
                        and (now - req.t_submit) * 1e3 > req.deadline_ms):
                    forced.append((i, RequestStatus.TIMED_OUT,
                                   DeadlineExceeded(
                        f"request {req.request_id}: total deadline "
                        f"({req.deadline_ms:g} ms) expired mid-decode",
                        adapter_id=req.adapter_id)))
            if forced and self._unread:
                # keep every token decoded: read the last decode's tokens
                # before the rows go (blocking, and rare)
                self._read_back(self._tok, self._unread)
                self._unread = []
            for i, status, err in forced:
                # a row whose last token that read delivered ends as it
                # would have at its own read-back
                if self._row_done(self._rows[i]):
                    finished.append(self._retire(i))
                else:
                    finished.append(self._retire(i, status, err))
            sweep.set(expired=len(finished) - n_done)
        if self._caches is None:
            self._caches = self.model.init_cache(self.max_rows, self.capacity)
            self._tok = jnp.zeros((self.max_rows,), jnp.int32)
        # the previous decode's tokens, still on the device: this step's
        # decode is dispatched before they are read
        carried = bool(self._unread)
        # admit FIFO, batching the leading run of equal padded lengths into
        # one prefill
        admitted = 0
        groups = []
        while self.pending:
            free = [i for i in range(self.max_rows) if self._rows[i] is None]
            if not free:
                break
            with span("engine.admit", tel) as admit:
                with span("engine.select", tel) as select:
                    group = self._select_admissions(len(free), finished)
                    select.set(picked=len(group))
                admit.set(rows=len(group),
                          tpad=self._tpad(group[0]) if group else 0)
                if not group:
                    break
                admitted += len(group)
                # global slot ids are read AFTER the whole group's
                # acquires: a later acquire may grow a pool and shift
                # earlier ids
                slots = [mgr.slot_of(r.adapter_id) for r in group]
                groups.append(self._admit_group(
                    group, free[:len(group)], slots))
        live = [i for i in range(self.max_rows) if self._rows[i] is not None]
        if not live:
            if self.pending and not admitted and not finished:
                # nothing live to ever unpin a slot (externally pinned
                # pool): bounded patience, then shed the head so run()
                # can never spin forever
                self._stalled_steps += 1
                if self._stalled_steps >= self.stall_limit:
                    head = self.pending.pop(0)
                    finished.append(self._finalize(
                        head, RequestStatus.REJECTED, MemoryExhausted(
                            f"request {head.request_id}: no HBM slot became "
                            f"available after {self._stalled_steps} stalled "
                            f"steps (pool fully pinned)",
                            adapter_id=head.adapter_id)))
                    self._stalled_steps = 0
            else:
                self._stalled_steps = 0
            self._prefetch_upcoming()
            return 0, admitted
        self._stalled_steps = 0
        active = [i for i in live if self._needs_decode(self._rows[i])]
        # what this step reads back: the tokens the previous decode left
        # and the first tokens scattered in since
        read, unread = self._tok, self._unread
        self._unread = []
        if active:
            with span("engine.decode.prep", tel) as prep:
                pos = np.zeros((self.max_rows,), np.int32)
                # rows not decoding: valid_start == capacity masks every
                # cache slot, so they decode garbage finitely (NEG_INF
                # masking) that nothing reads
                start = np.full((self.max_rows,), self.capacity, np.int32)
                seg = np.zeros((self.max_rows,), np.int32)
                for i in active:
                    row = self._rows[i]
                    pos[i] = row.start + row.prompt_len + row.decoded
                    row.decoded += 1
                    start[i] = row.start
                    # seg ids ARE (global) slot ids: the page is pinned at
                    # admission, but its global id can shift when an
                    # earlier recipe pool grows — read the current id every
                    # step (must happen BEFORE the prefetch below, which
                    # may grow pools)
                    seg[i] = mgr.slot_of(row.req.adapter_id)
                packed = mgr.serving_tree()
                # the tile_t=1 decode view of the slot pool is rebuilt only
                # when the pool changed (serving_tree caches until a
                # swap-in/growth dirties it, so object identity is the
                # change signal; keeping the strong reference in _dec_src
                # is what makes identity a safe key)
                retiled = self._dec_src is not packed
                if retiled:
                    self._dec_groups = retile_packed(packed, 1)["groups"]
                    self._dec_src = packed
                dec = {"base": self.params["base"],
                       "lora": {"groups": self._dec_groups,
                                "seg": jnp.asarray(seg)}}
                prep.set(retiled=int(retiled))
        # stage next wave AFTER building this step's view, BEFORE dispatch:
        # the swap-in copies and the decode below have no data dependency
        self._prefetch_upcoming()
        if active:
            with span("engine.decode", tel, overlapped=int(carried)):
                self._tok, self._caches = self._decode_greedy(
                    self._decode, dec, read, self._caches,
                    jnp.asarray(pos), jnp.asarray(start))
            self._unread = active
            self._step_count += 1
        with span("engine.decode.sync", tel, rows=len(unread)):
            t_read = self._read_back(read, unread)
        if tel is not None:
            for wave, ids, tpad, t_pre in groups:
                tel.on_prefill(wave, ids, tpad, t_read - t_pre)
            if active:
                tel.on_decode_step(
                    self._step_count, self.clock() - t_step, len(active),
                    self.max_rows, len(self.pending),
                    request_ids=[self._rows[i].req.request_id
                                 for i in active],
                    overlapped=carried)
        with span("engine.retire", tel) as retire:
            n_done = len(finished)
            for i in live:
                if self._row_done(self._rows[i]):
                    finished.append(self._retire(i))
            retire.set(retired=len(finished) - n_done)
        return len(active), admitted

    @property
    def active_rows(self) -> int:
        return sum(r is not None for r in self._rows)

    def _screen_static(self, reqs: List[Request],
                       done: List[Request]) -> List[Request]:
        """Apply the failure contract to a static (one-shot) batch before
        decoding: unknown adapters REJECT, quarantined adapters FAIL,
        already-expired deadlines TIME OUT — and, because the static paths
        read codes straight from the store (no paged-tier integrity hook),
        each adapter's codes are integrity-screened once here; poisoned
        ones are quarantined and their requests FAIL without touching the
        rest of the batch."""
        now = self.clock()
        healthy: List[Request] = []
        for r in reqs:
            if self._reject_now(r) is not None:
                done.append(r)
                continue
            err = self._queue_expired(r, now)
            if err is not None:
                done.append(self._finalize(r, RequestStatus.TIMED_OUT, err))
                continue
            healthy.append(r)
        for aid in sorted({r.adapter_id for r in healthy}):
            if not self.store.check_integrity(aid):
                self._quarantine(aid)
        out: List[Request] = []
        for r in healthy:
            if self._is_quarantined(r.adapter_id):
                done.append(self._finalize(
                    r, RequestStatus.FAILED, PoisonedAdapter(
                        f"request {r.request_id}: adapter "
                        f"{r.adapter_id!r} failed the integrity screen",
                        adapter_id=r.adapter_id)))
            else:
                out.append(r)
        return out

    def run(self, mode: Optional[str] = None) -> List[Request]:
        """Process all pending requests to a terminal state; returns them
        with ``output``/``status`` set (continuous mode returns completion
        order, static modes submission order — screened-out failures
        first)."""
        mode = mode or self.mode
        if mode not in ("continuous", "packed", "materialize"):
            raise ValueError(f"unknown serving mode {mode!r}")  # keep pending
        done: List[Request] = []
        if mode == "continuous":
            while self.pending or self.active_rows or self._terminated:
                done.extend(self.step())
            return done
        done.extend(self._terminated)      # queue-shed before a static run
        self._terminated = []
        if self.active_rows:
            # a static run must not strand requests mid-decode in the
            # scheduler's rows: drain them first (without admitting the
            # pending batch, which belongs to the static run)
            held, self.pending = self.pending, []
            while self.active_rows:
                done.extend(self.step())
            self.pending = held
        reqs, self.pending = self.pending, []
        reqs = self._screen_static(reqs, done)
        if not reqs:
            return done
        if mode == "packed":
            return done + self._run_packed(reqs)
        return done + self._run_materialize(reqs)
