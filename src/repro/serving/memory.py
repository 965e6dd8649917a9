"""Paged adapter memory: per-recipe HBM slot pools + host tier + prefetch.

Packed serving (``docs/packed_format.md``) made every registered adapter's
codes device-resident in one ever-growing ``(L, NA, Rp, ·)`` stack. That is
the right call while the store fits in HBM, but at the "millions of users"
tier the adapter stack — not the base model — becomes the HBM bottleneck.
This module bounds it: a budgeted set of HBM **slots** holds the *hot set*
of adapters, every registered adapter's packed codes live in a host-RAM
tier as numpy, and the continuous scheduler faults the long tail in on
demand (see ``docs/adapter_memory.md``).

With **per-adapter quantization recipes** (``docs/recipes.md``) pages are
no longer one size: a 4-bit premium adapter's page is ~2× a 2-bit one.
Slots therefore live in one pool **per packed-layout signature**
(``recipe.layout_signature``): inside a pool every page is a fixed-size
slice of that pool's persistent stacks, and a swap-in stays ONE
``dynamic_update_slice`` dispatch. Budget accounting uses each signature's
*real* ``page_bytes``; pools under a byte budget grow slot-by-slot against
a shared ledger and reclaim from each other's cold tails when it runs dry.

Key facts that make paging cheap:

* **Uniform pages per pool.** Zero-scale rank padding gives every adapter
  of one signature identical per-path leaf shapes ``(L, [fold,] Rp, ·)``,
  so a "page" is a fixed-size slice of its pool's slot stacks — no
  reallocation, no recompilation on a fault (the decode program's shapes
  are a function of the pool capacities, not of how many adapters exist).
* **Slot ids are segment ids.** The SGMV kernels index an arbitrary
  adapter axis via per-row segment ids. A row's seg id is the **global**
  slot id — the pool's base offset (pools concatenate in creation order)
  plus the local slot; with several pools the serving tree is a
  :class:`~repro.kernels.PackedLoRABuckets` whose per-pool lookups map
  global ids back to pool-local ones, so the kernels stay untouched.
* **Pinning.** A slot referenced by a live batch row is pinned (refcounted)
  and never evicted, so mid-decode rows keep reading stable codes while the
  unpinned remainder of the pools churns LRU.
* **Prefetch.** The engine issues swap-ins for the next admission wave
  *before* dispatching the current decode step; the copies have no data
  dependency on the in-flight step (functional update → fresh buffers), so
  host→HBM transfer overlaps decode compute.

The manager is policy + bookkeeping; it owns no kernel code.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import PackedLoRABatch, PackedLoRABuckets, pack_adapter_layers
from repro.kernels.quant_matmul.ops import (
    _PACKED_ARRAY_FIELDS as _ARRAY_FIELDS,
)
from repro.serving.faults import (
    FaultPlan,
    HostReadError,
    HostTransport,
    PoisonedAdapter,
    page_arrays_finite,
)
from repro.serving.telemetry import span

# page meta = everything that isn't a packed array, the late-attached seg,
# or a per-view knob — derived from the dataclass so a new field added to
# PackedLoRABatch cannot silently go un-copied
_META_FIELDS = tuple(
    f.name for f in dataclasses.fields(PackedLoRABatch)
    if f.name not in _ARRAY_FIELDS + ("seg", "tile_t"))


@jax.jit
def _page_write(pool, page, starts):
    """Write one adapter's whole page into a pool's persistent slot stacks
    at the (per-path, fold-scaled) columns in ``starts`` — the
    ``pool.at[slot].set`` of the design, batched over every leaf array so a
    swap-in is ONE dispatch, not #paths·#fields dispatches. The slot column
    is a traced operand: faulting into slot 0 and slot 7 share the
    executable, and a pool's shapes only change on growth, so there is
    exactly one compile per pool geometry. The update is functional (old
    buffers stay valid for any already-dispatched decode step, which is
    what lets prefetch overlap compute); on a real TPU deployment add
    ``donate_argnums=(0,)`` + drop the cached tree to alias in place —
    donation is a no-op warning on the CPU backend this container uses."""
    return jax.tree_util.tree_map(
        lambda pl, pg, st: jax.lax.dynamic_update_slice_in_dim(
            pl, jnp.asarray(pg, pl.dtype), st, axis=1),
        pool, page, starts)


@dataclasses.dataclass
class _HostPage:
    """One adapter's packed codes in the host tier: per path, per packed
    field, a numpy array ``(L, fold, Rp, ·)`` (fold == 1 for plain leaves).
    ``version`` is the AdapterStore epoch the page was built from and
    ``sig`` the recipe's packed-layout signature (its pool key)."""

    arrays: Dict[str, Dict[str, np.ndarray]]
    version: int
    nbytes: int
    sig: tuple


@dataclasses.dataclass
class _Pool:
    """One signature's HBM slot pool: persistent per-path stacks
    ``(L, capacity·fold, Rp, ·)`` plus the local slot-owner table."""

    sig: tuple
    arrays: Optional[Dict[str, Dict[str, jax.Array]]]   # None until cap > 0
    capacity: int
    owners: List[Optional[str]]
    page_bytes: int

    def nbytes(self) -> int:
        if self.arrays is None:
            return 0
        return sum(arr.size * arr.dtype.itemsize
                   for fields in self.arrays.values()
                   for arr in fields.values())


class AdapterMemoryManager:
    """Two-tier adapter memory for the continuous scheduler.

    * **HBM tier**: one :class:`_Pool` per recipe layout signature; global
      slot ids concatenate the pools in creation order (pool base + local
      slot) and ARE the decode seg ids.
    * **Host tier**: every registered adapter's packed codes as numpy
      (:class:`_HostPage`), built lazily per adapter and rebuilt when the
      store re-registers an id (weights *or* recipe).

    Capacity resolution: explicit ``num_slots`` bounds the TOTAL slot count
    across pools; ``store.hbm_budget_bytes`` bounds the total pool bytes
    using each signature's real ``page_bytes``; neither → growable
    (all-resident "budget = ∞"). A store whose adapters share one
    signature pre-allocates its single pool up front (the classic
    uniform-page behavior: ``budget // page_bytes`` slots); mixed-recipe
    stores grow pools slot-by-slot against the shared ledger and reclaim
    cold slots from other pools' tails when it runs dry.

    Eviction is LRU over resident, unpinned, unreserved slots. ``pin`` /
    ``unpin`` are refcounted per adapter id (one count per live batch row);
    ``prefetch`` reserves its slots until the next prefetch call so a page
    staged for the upcoming admission cannot be stolen by a later miss in
    the same window.
    """

    def __init__(self, store, like_tree, num_slots: Optional[int] = None,
                 tile_t: int = 8,
                 transport: Optional[HostTransport] = None,
                 faults: Optional[FaultPlan] = None,
                 verify_pages: bool = True,
                 telemetry=None):
        if num_slots is not None and num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        self.store = store
        self.like_tree = like_tree
        self.requested_slots = num_slots
        self.tile_t = tile_t
        self.faults = faults
        self.transport = (transport if transport is not None
                          else HostTransport(faults=faults))
        self.verify_pages = verify_pages

        self._leaf_info: Optional[List[Tuple[str, int, int]]] = None
        self._host: Dict[str, _HostPage] = {}
        self._pools: "collections.OrderedDict[tuple, _Pool]" = (
            collections.OrderedDict())
        self._page_bytes_by_sig: Dict[tuple, int] = {}
        self._meta_by_sig: Dict[tuple, Dict[str, Dict[str, Any]]] = {}
        # per-sig (tail shape, dtype) of every leaf field: lets pools
        # resize after their last host page is gone (deferred unregister)
        self._ref_by_sig: Dict[tuple, Dict[str, Dict[str, Tuple[tuple, Any]]]] = {}

        self._where: Dict[str, Tuple[tuple, int]] = {}   # aid -> (sig, local)
        self._slot_version: Dict[str, int] = {}
        self._pins: Dict[str, int] = {}
        self._reserved: Set[str] = set()
        self._lru: "collections.OrderedDict[str, None]" = collections.OrderedDict()
        # deferred unregister: ids whose store entry is gone but whose slot
        # is pinned by live rows — reaped on the last unpin
        self._dead: Set[str] = set()
        # ids whose page failed the integrity check, keyed to the store
        # version that failed — the engine drains this into its quarantine
        # set each step (version-keyed so a fixed re-upload is not
        # re-quarantined by a stale record)
        self.poisoned: Dict[str, Optional[int]] = {}

        self._tree = None                  # cached serving tree (dirty=None)
        self._seen_mutations = None
        self.telemetry = telemetry         # optional Telemetry facade
        self.hits = 0
        self.misses = 0
        self.swap_ins = 0
        self.swap_in_bytes = 0
        self.evictions = 0
        self.stale_serves = 0
        # per-pool (per recipe signature) breakdown of the counters above —
        # the residency-cliff instrument: a mixed-recipe fleet thrashing ONE
        # pool shows up here while the global hit rate still looks healthy
        self._per_pool: Dict[tuple, Dict[str, int]] = {}
        # prefetch outcomes (hit / staged / failed / no_slot): opportunistic
        # staging is separate from the admission hit-rate by design, so it
        # gets its own counters instead of polluting hits/misses
        self.prefetch_counts: Dict[str, int] = {
            "hit": 0, "staged": 0, "failed": 0, "no_slot": 0}

    # ----- telemetry plumbing -----

    @staticmethod
    def _sig_label(sig: tuple) -> str:
        """Stable label for one recipe-signature pool, e.g. ``2-64-1`` for
        (bits_high=2, group_size=64, bits_low=1)."""
        return "-".join(str(x) for x in sig)

    def _count(self, sig: tuple, key: str, n: int = 1):
        """Bump one per-pool counter and mirror it into the telemetry
        registry (``adapter_memory_<key>_total{pool=...}``) when attached."""
        pool = self._per_pool.setdefault(
            sig, {"hits": 0, "misses": 0, "swap_ins": 0,
                  "swap_in_bytes": 0, "evictions": 0})
        pool[key] += n
        if self.telemetry is not None:
            self.telemetry.registry.counter(
                f"adapter_memory_{key}_total",
                pool=self._sig_label(sig)).inc(n)

    def _count_prefetch(self, outcome: str):
        self.prefetch_counts[outcome] += 1
        if self.telemetry is not None:
            self.telemetry.registry.counter(
                "adapter_memory_prefetch_total",
                help="prefetch staging outcomes",
                outcome=outcome).inc()

    def _count_stale(self):
        self.stale_serves += 1
        if self.telemetry is not None:
            self.telemetry.registry.counter(
                "adapter_memory_stale_serves_total",
                help="degraded serves from a stale resident page").inc()

    # ----- layout -----

    def _leaves(self) -> List[Tuple[str, int, int]]:
        """``(path, L, fold)`` for every {'a','b'} leaf of the template.
        ``fold`` multiplies out extra lead dims (MoE experts) that packing
        folds into the adapter axis."""
        if self._leaf_info is None:
            from repro.serving.engine import _leaf_folds, iter_lora_linears

            folds = _leaf_folds(self.like_tree)   # one fold definition for
            info = []                             # pages AND packed entries
            for path, leaf in iter_lora_linears(self.like_tree):
                shape = tuple(np.shape(leaf["a"]))
                if len(shape) < 3:
                    raise NotImplementedError(
                        f"paged packed serving needs stacked (L, ..., r, in) "
                        f"leaves; {path} has shape {shape}")
                info.append((path, int(shape[0]), folds[path]))
            self._leaf_info = info
        return self._leaf_info

    def _sig_of(self, adapter_id: str) -> tuple:
        return self.store.signature_of(adapter_id)

    def _host_page(self, adapter_id: str) -> _HostPage:
        """Host-tier page for one adapter, (re)built from the store's
        quantized entries when absent or stale (weight OR recipe change).

        The build runs through the pluggable :class:`HostTransport`
        (timeout + bounded-backoff retry + fault injection) and the result
        is integrity-checked before it can reach a slot: a page with
        non-finite scales raises :class:`PoisonedAdapter` (and is recorded
        in :attr:`poisoned` for the engine's quarantine sweep), a
        persistently failing read raises :class:`HostReadError` for the
        caller's degradation ladder."""
        version = self.store.version(adapter_id)
        if version is None:
            raise KeyError(f"adapter {adapter_id!r} is not registered")
        page = self._host.get(adapter_id)
        if page is not None and page.version == version:
            return page
        qa = self.store.quantized[adapter_id]
        sig = self._sig_of(adapter_id)

        def build():
            arrays: Dict[str, Dict[str, np.ndarray]] = {}
            meta: Dict[str, Dict[str, Any]] = {}
            nbytes = 0
            for path, n_layers, fold in self._leaves():
                pb = pack_adapter_layers(qa.entries[path], fold=fold)
                meta[path] = {f: getattr(pb, f) for f in _META_FIELDS}
                fields = {}
                for f in _ARRAY_FIELDS:
                    arr = np.asarray(getattr(pb, f))
                    # normalize to an explicit fold axis: (L, fold, Rp, ·)
                    fields[f] = arr.reshape((n_layers, fold) + arr.shape[-2:])
                    nbytes += fields[f].nbytes
                arrays[path] = fields
            return arrays, meta, nbytes

        arrays, meta, nbytes = self.transport.read(adapter_id, build)
        if self.faults is not None:        # corruption models bad bytes at
            arrays = self.faults.corrupt_page(adapter_id, arrays)  # rest
        # layout facts are value-independent: record them even for a page
        # that fails the integrity check below, so pool geometry survives
        self._page_bytes_by_sig.setdefault(sig, nbytes)
        self._meta_by_sig.setdefault(sig, meta)
        self._ref_by_sig.setdefault(sig, {
            path: {f: (arr.shape[-2:], arr.dtype)
                   for f, arr in fields.items()}
            for path, fields in arrays.items()})
        if self.verify_pages and not page_arrays_finite(arrays):
            self.poisoned[adapter_id] = version
            raise PoisonedAdapter(
                f"adapter {adapter_id!r}: page integrity check failed "
                f"(non-finite scales)", adapter_id)
        self.poisoned.pop(adapter_id, None)
        page = _HostPage(arrays=arrays, version=version, nbytes=nbytes,
                         sig=sig)
        self._host[adapter_id] = page
        return page

    def page_bytes_of(self, adapter_id: str) -> int:
        """HBM bytes one slot of this adapter's signature pool occupies."""
        sig = self._sig_of(adapter_id)
        if sig not in self._page_bytes_by_sig:
            self._host_page(adapter_id)
        return self._page_bytes_by_sig[sig]

    def _sig_page_bytes(self, sig: tuple) -> int:
        """Page bytes for a signature, probing any registered adapter of
        that signature if not yet known. A probe that fails its read or
        integrity check must not poison an unrelated caller — try the next
        adapter of the signature instead."""
        if sig not in self._page_bytes_by_sig:
            for aid in list(self.store.quantized):
                if self._sig_of(aid) != sig:
                    continue
                try:
                    self._host_page(aid)
                except (HostReadError, PoisonedAdapter):
                    # layout facts may have been recorded anyway (poison);
                    # otherwise probe another adapter of the signature
                    if sig in self._page_bytes_by_sig:
                        break
                    continue
                break
        if sig not in self._page_bytes_by_sig:
            raise RuntimeError(f"no adapter of signature {sig} registered: "
                               "page size unknown")
        return self._page_bytes_by_sig[sig]

    @property
    def page_bytes(self) -> int:
        """HBM bytes one adapter slot occupies — only well-defined while
        every registered adapter shares one recipe signature; use
        :meth:`page_bytes_of` for mixed-recipe stores."""
        sigs = self._registered_sigs()
        if not sigs:
            raise RuntimeError("no adapter registered yet: page size "
                               "unknown")
        if len(sigs) > 1:
            raise RuntimeError("mixed recipe signatures: page size is "
                               "per-adapter (use page_bytes_of)")
        return self._sig_page_bytes(next(iter(sigs)))

    def _registered_sigs(self) -> Set[tuple]:
        return {qa.signature for qa in self.store.quantized.values()}

    # ----- ledger -----

    @property
    def _growable(self) -> bool:
        return (self.requested_slots is None
                and getattr(self.store, "hbm_budget_bytes", None) is None)

    def _cost(self, sig: tuple) -> int:
        """Ledger cost of one slot of ``sig``: a slot under ``num_slots``,
        its real page bytes under ``hbm_budget_bytes``."""
        if self.requested_slots is not None:
            return 1
        return self._sig_page_bytes(sig)

    def _limit(self) -> Optional[int]:
        if self.requested_slots is not None:
            return self.requested_slots
        budget = getattr(self.store, "hbm_budget_bytes", None)
        return None if budget is None else int(budget)

    def _used(self) -> int:
        if self.requested_slots is not None:
            return sum(p.capacity for p in self._pools.values())
        return sum(p.capacity * self._sig_page_bytes(p.sig)
                   for p in self._pools.values())

    def _headroom(self, sig: tuple, n: int = 1) -> bool:
        limit = self._limit()
        if limit is None:
            return True
        if self._used() == 0:
            return True            # progress guarantee: a first slot always
        return self._used() + n * self._cost(sig) <= limit

    # ----- pools -----

    def _pool(self, sig: tuple) -> _Pool:
        pool = self._pools.get(sig)
        if pool is not None:
            return pool
        page_bytes = self._sig_page_bytes(sig)
        pool = _Pool(sig=sig, arrays=None, capacity=0, owners=[],
                     page_bytes=page_bytes)
        self._pools[sig] = pool
        # classic uniform-page behavior: the first pool of a store whose
        # adapters all share one signature is pre-allocated to the full
        # allowance (num_slots, or max(1, budget // page_bytes)); growable
        # pools start at the current registry size of their signature
        sigs = self._registered_sigs()
        if self._growable:
            n = max(1, sum(1 for aid in self.store.quantized
                           if self._sig_of(aid) == sig))
            self._resize_pool(pool, n)
        elif len(self._pools) == 1 and sigs == {sig}:
            if self.requested_slots is not None:
                self._resize_pool(pool, self.requested_slots)
            else:
                budget = int(self.store.hbm_budget_bytes)
                self._resize_pool(pool, max(1, budget // max(page_bytes, 1)))
        return pool

    def _resize_pool(self, pool: _Pool, capacity: int):
        """(Re)allocate a pool's slot stacks at ``capacity`` slots,
        preserving resident pages (growth keeps local slot ids stable;
        shrink drops only freed tail slots)."""
        if capacity == pool.capacity:
            return
        if capacity == 0:
            pool.arrays = None
            pool.capacity = 0
            pool.owners = []
            self._tree = None
            return
        # field shapes come from the per-sig template recorded at the first
        # host-page build — NOT from a live host page, which may be gone
        # (deferred unregister keeps pinned slots after their host page)
        ref = self._ref_by_sig.get(pool.sig)
        assert ref is not None, "pool resize before any host page"
        old, old_cap = pool.arrays, pool.capacity
        arrays: Dict[str, Dict[str, jax.Array]] = {}
        for path, n_layers, fold in self._leaves():
            fields = {}
            for f in _ARRAY_FIELDS:
                tail, dtype = ref[path][f]
                shape = ((n_layers, capacity * fold) + tail)
                z = jnp.zeros(shape, dtype)
                if old is not None and old_cap:
                    keep = min(old_cap, capacity) * fold
                    z = z.at[:, :keep].set(old[path][f][:, :keep])
                fields[f] = z
            arrays[path] = fields
        pool.arrays = arrays
        pool.capacity = capacity
        if capacity > len(pool.owners):
            pool.owners.extend([None] * (capacity - len(pool.owners)))
        else:
            assert all(o is None for o in pool.owners[capacity:])
            del pool.owners[capacity:]
        self._tree = None

    def _base(self, sig: tuple) -> int:
        """Global slot id of the pool's local slot 0 (pools concatenate in
        creation order)."""
        base = 0
        for s, pool in self._pools.items():
            if s == sig:
                return base
            base += pool.capacity
        raise KeyError(sig)

    # ----- slot accounting -----

    @property
    def num_slots(self) -> int:
        """Total slot capacity across pools (ensures the default pool for a
        store that has registered adapters but no pool yet)."""
        self._ensure_default_pool()
        return sum(p.capacity for p in self._pools.values())

    def _ensure_default_pool(self):
        if self._pools or not self.store.quantized:
            if not self._pools and not self.store.quantized:
                raise RuntimeError("no adapter registered yet: page size "
                                   "unknown")
            return
        self._pool(self._sig_of(next(iter(self.store.quantized))))

    @property
    def _slot_owner(self) -> List[Optional[str]]:
        """Global owner table (concatenated pools, base order) — the
        slot-id view the engine's seg ids live in."""
        out: List[Optional[str]] = []
        for pool in self._pools.values():
            out.extend(pool.owners)
        return out

    def resident(self, adapter_id: str) -> bool:
        """True when the adapter's *current* codes occupy a slot (weight
        version AND recipe signature both current)."""
        loc = self._where.get(adapter_id)
        if loc is None:
            return False
        return (self._slot_version.get(adapter_id)
                == self.store.version(adapter_id)
                and loc[0] == self._sig_of(adapter_id))

    def slot_of(self, adapter_id: str) -> int:
        sig, local = self._where[adapter_id]
        return self._base(sig) + local

    def pin(self, adapter_id: str):
        self._pins[adapter_id] = self._pins.get(adapter_id, 0) + 1

    def unpin(self, adapter_id: str):
        n = self._pins.get(adapter_id, 0) - 1
        if n <= 0:
            self._pins.pop(adapter_id, None)
            if adapter_id in self._dead:
                # deferred unregister: the last live row just retired —
                # reap the slot and host page the store dropped earlier
                self._dead.discard(adapter_id)
                if adapter_id in self._where:
                    self._free_slot(adapter_id)
                self._host.pop(adapter_id, None)
        else:
            self._pins[adapter_id] = n

    def pinned(self, adapter_id: str) -> bool:
        return self._pins.get(adapter_id, 0) > 0

    def _free_slot(self, adapter_id: str):
        sig, local = self._where.pop(adapter_id)
        self._pools[sig].owners[local] = None
        self._slot_version.pop(adapter_id, None)
        self._lru.pop(adapter_id, None)
        self._reserved.discard(adapter_id)

    def _evictable(self, adapter_id: str) -> bool:
        return (not self.pinned(adapter_id)
                and adapter_id not in self._reserved)

    def _find_slot(self, sig: tuple) -> Optional[int]:
        """A local slot in ``sig``'s pool: free slot, else same-pool LRU
        victim, else growth within the ledger (reclaiming other pools'
        cold tail slots if the ledger is dry), else None."""
        pool = self._pool(sig)
        for slot, owner in enumerate(pool.owners):
            if owner is None:
                return slot
        for aid in self._lru:              # least-recent first
            loc = self._where.get(aid)
            if loc is None or loc[0] != sig or not self._evictable(aid):
                continue
            slot = loc[1]
            self._free_slot(aid)
            self.evictions += 1
            self._count(sig, "evictions")
            return slot
        if self._growable:
            slot = pool.capacity
            self._resize_pool(pool, max(2 * pool.capacity, 1))
            return slot
        if not self._headroom(sig):
            self._reclaim(sig)
        if self._headroom(sig):
            # geometric growth clamped to the ledger headroom: each realloc
            # copies the whole pool and retraces _page_write, so doubling
            # amortizes what +1-per-fault would make O(N^2)
            room = (self._limit() - self._used()) // self._cost(sig)
            slot = pool.capacity
            self._resize_pool(pool, min(max(2 * pool.capacity, 1),
                                        pool.capacity + max(int(room), 1)))
            return slot
        return None

    def _reclaim(self, need_sig: tuple):
        """Free ledger room for one ``need_sig`` slot by evicting cold
        pages in OTHER pools and shrinking those pools' tails (a freed
        middle slot is filled by migrating the tail's unpinned owner — a
        host-tier swap-in — so the tail can drop). Stops as soon as the
        ledger has headroom; pinned/reserved tails bound what's
        reclaimable."""
        for aid in list(self._lru):
            if self._headroom(need_sig):
                return
            loc = self._where.get(aid)
            if loc is None or loc[0] == need_sig or not self._evictable(aid):
                continue
            sig = loc[0]
            self._free_slot(aid)
            self.evictions += 1
            self._count(sig, "evictions")
            self._shrink_tail(self._pools[sig])
        # final pass: tails freed by earlier evictions in any order
        for pool in self._pools.values():
            if self._headroom(need_sig):
                return
            if pool.sig != need_sig:
                self._shrink_tail(pool)

    def _shrink_tail(self, pool: _Pool):
        """Drop the pool's trailing free slots (releasing their ledger
        cost). If the tail is held by an unpinned, unreserved owner while
        free slots sit below it, migrate that owner down (one host-tier
        swap-in) first. Migrations run on the owner table first; the
        arrays realloc ONCE at the final capacity."""
        cap = pool.capacity
        migrated = []
        while cap:
            owner = pool.owners[cap - 1]
            if owner is None:
                cap -= 1
                continue
            hole = next((i for i, o in enumerate(pool.owners[:cap - 1])
                         if o is None), None)
            if hole is None or not self._evictable(owner):
                break
            pool.owners[cap - 1] = None
            pool.owners[hole] = owner
            self._where[owner] = (pool.sig, hole)
            migrated.append((owner, hole))
            cap -= 1
        for owner, hole in migrated:       # data follows the owner table
            try:
                self._swap_in(owner, pool.sig, hole, migrate=True)
            except (HostReadError, PoisonedAdapter):
                # the migrating page cannot be re-read: drop it (it is
                # unpinned) instead of leaving stale bytes at the new slot;
                # a later acquire re-faults it and surfaces the error
                self._free_slot(owner)
                self.evictions += 1
                self._count(pool.sig, "evictions")
        if cap != pool.capacity:
            self._resize_pool(pool, cap)

    def _swap_in(self, adapter_id: str, sig: tuple, slot: int,
                 migrate: bool = False):
        """Issue the host→HBM copy of one page into ``sig``'s pool at local
        ``slot`` as ONE jitted dispatch over every leaf array. Functional
        update: the previous pool buffers stay valid for any
        already-dispatched step, the next-built tree reads the new ones."""
        with span("memory.swap_in", self.telemetry) as swap:
            page = self._host_page(adapter_id)
            swap.set(bytes=page.nbytes)
            pool = self._pools[sig]
            starts = {path: {f: jnp.int32(slot * fold) for f in _ARRAY_FIELDS}
                      for path, _, fold in self._leaves()}
            pool.arrays = _page_write(pool.arrays, page.arrays, starts)
        pool.owners[slot] = adapter_id
        self._where[adapter_id] = (sig, slot)
        self._slot_version[adapter_id] = page.version
        if not migrate:
            self._lru[adapter_id] = None
            self._lru.move_to_end(adapter_id)
        self.swap_ins += 1
        self.swap_in_bytes += page.nbytes
        self._count(sig, "swap_ins")
        self._count(sig, "swap_in_bytes", page.nbytes)
        self._tree = None

    # ----- engine-facing operations -----

    def acquire(self, adapter_id: str, pin: bool = True) -> Optional[int]:
        """Map an adapter to a resident slot for admission; returns the
        GLOBAL slot id (pool base + local — the decode seg id).

        Hit: touch LRU, pin, return the slot. Miss: claim a free/evictable
        slot in the adapter's signature pool, issue the swap-in (the
        admission that follows is queued behind it by dispatch order), pin,
        return the slot. Returns ``None`` when no slot can be claimed
        (everything pinned/reserved and the ledger is dry) — the caller
        leaves the request pending and retries next step.

        Failure contract (``docs/robustness.md``): a swap-in whose host
        read fails persistently (transport retry budget exhausted) falls
        back to a **stale-but-valid resident page** of the same adapter
        when one exists (counted in ``stale_serves``); otherwise
        :class:`HostReadError` propagates for the engine to reject the
        request. A page failing its integrity check raises
        :class:`PoisonedAdapter` (quarantine path) — never a stale serve,
        because poison is a property of the codes, not of the transport.

        Note the returned global id is only stable until another pool
        grows; the engine re-reads :meth:`slot_of` when building each
        step's seg ids.
        """
        with span("memory.acquire", self.telemetry) as acq:
            sig = self._sig_of(adapter_id)
            if self.resident(adapter_id):
                acq.set(hit=1)
                self.hits += 1
                self._count(sig, "hits")
                local = self._where[adapter_id][1]
            else:
                acq.set(hit=0)
                loc = self._where.get(adapter_id)
                stale_local = (loc[1] if loc is not None and loc[0] == sig
                               else None)
                if stale_local is not None:
                    local = stale_local        # resident but stale codes:
                else:                          # reload in place
                    if loc is not None:        # recipe changed pools
                        self._free_slot(adapter_id)
                    local = self._find_slot(sig)
                    if local is None:
                        return None            # retried next step — not
                self.misses += 1               # charged as a miss
                self._count(sig, "misses")
                try:
                    self._swap_in(adapter_id, sig, local)
                except HostReadError:
                    if stale_local is None:
                        raise
                    # degradation rung 1: the slot still holds the last
                    # good version of this adapter's codes — serve those
                    self._count_stale()
            self._lru[adapter_id] = None
            self._lru.move_to_end(adapter_id)
            self._reserved.discard(adapter_id)
            if pin:
                self.pin(adapter_id)
            return self._base(sig) + local

    def prefetch(self, adapter_ids: Sequence[str]):
        """Stage the next admission wave's pages one step ahead.

        Call *after* building this step's decode view and *before*
        dispatching it: the swap-ins write fresh buffers, so the in-flight
        decode (reading the old ones) and the transfers overlap. Staged
        slots are reserved — ineligible for eviction — until the next
        prefetch call re-derives the reservation set. Misses here are not
        charged to the hit-rate (only admission-time :meth:`acquire` is).
        """
        with span("memory.prefetch", self.telemetry) as pre:
            reserved: Set[str] = set()
            staged = 0
            for aid in adapter_ids:
                if self.store.version(aid) is None:
                    continue
                sig = self._sig_of(aid)
                if not self.resident(aid):
                    loc = self._where.get(aid)
                    if loc is not None and loc[0] == sig:
                        slot = loc[1]
                    else:
                        if loc is not None:
                            self._free_slot(aid)
                        self._reserved = reserved  # protect earlier stages
                        slot = self._find_slot(sig)
                        if slot is None:
                            self._count_prefetch("no_slot")
                            continue
                    try:
                        self._swap_in(aid, sig, slot)
                    except (HostReadError, PoisonedAdapter):
                        self._count_prefetch("failed")
                        continue   # prefetch is opportunistic: admission's
                    self._count_prefetch("staged")
                    staged += 1
                else:              # acquire surfaces the error properly
                    self._count_prefetch("hit")
                self._lru[aid] = None
                self._lru.move_to_end(aid)
                reserved.add(aid)
            self._reserved = reserved
            pre.set(staged=staged)

    def refresh(self):
        """Reconcile with store mutations (register / re-register with new
        weights OR a new recipe / unregister) since the last call.
        Unregistered adapters lose their host page immediately and their
        slot once unpinned (a live row keeps serving the codes already in
        its pinned slot until it retires); re-registered pinned adapters
        are reloaded — in place when the recipe signature is unchanged,
        into their new signature's pool otherwise — so active rows serve
        the newest weights, matching the pack-cache invalidation semantics
        of the all-resident path."""
        mutations = self.store.mutation_count()
        if mutations == self._seen_mutations:
            return
        self._seen_mutations = mutations
        for aid in list(self._where):
            version = self.store.version(aid)
            if version is None:
                self._host.pop(aid, None)
                if not self.pinned(aid):
                    self._free_slot(aid)
                    self._dead.discard(aid)
                else:
                    # deferred unregister: live rows keep reading the
                    # pinned page; :meth:`unpin` reaps it on the last row's
                    # retirement (never a dangling slot, never a freed page
                    # under a live row)
                    self._dead.add(aid)
            elif version != self._slot_version.get(aid):
                self._dead.discard(aid)        # re-registered while dying
                sig_now = self._sig_of(aid)
                sig_was = self._where[aid][0]
                if not self.pinned(aid):
                    self._free_slot(aid)
                elif sig_now == sig_was:
                    try:
                        self._swap_in(aid, sig_was, self._where[aid][1])
                    except (HostReadError, PoisonedAdapter):
                        # keep serving the pinned stale page; acquire /
                        # the engine's poison sweep handle the rest
                        self._count_stale()
                else:
                    # pinned page whose recipe moved pools: read the new
                    # page FIRST (a failed read must leave the old pool
                    # placement serving), then claim a slot in the new
                    # pool and release the old one
                    try:
                        self._host_page(aid)
                    except (HostReadError, PoisonedAdapter):
                        self._count_stale()
                        continue
                    local = self._find_slot(sig_now)
                    old_sig, old_local = self._where[aid]
                    if local is None:
                        raise RuntimeError(
                            f"adapter {aid!r} re-registered with a new "
                            f"recipe while pinned, but its new pool has no "
                            f"free slot")
                    self._pools[old_sig].owners[old_local] = None
                    self._where[aid] = (sig_now, local)
                    self._swap_in(aid, sig_now, local)
        for aid in list(self._host):
            if self.store.version(aid) is None:
                self._host.pop(aid, None)

    # ----- the device view -----

    def serving_tree(self):
        """The lora tree the engine feeds the model: ``like_tree`` mirrored
        with :class:`PackedLoRABatch` leaves over the slot stacks (one
        pool) or :class:`PackedLoRABuckets` leaves (one bucket per pool,
        lookups from global slot ids to pool-local ones). Rebuilt only
        after a swap-in / growth changed a pool (cheap dataclass
        construction; array buffers are shared, so an unchanged tree keeps
        its identity and the engine's retile cache stays warm)."""
        self._ensure_default_pool()
        if self._tree is not None:
            return self._tree

        live = [p for p in self._pools.values() if p.capacity > 0]
        total = sum(p.capacity for p in self._pools.values())
        luts = []
        for pool in live:
            lut = np.full((total,), -1, np.int32)
            base = self._base(pool.sig)
            lut[base:base + pool.capacity] = np.arange(pool.capacity,
                                                       dtype=np.int32)
            luts.append(jnp.asarray(lut))

        def leaf_of(pool: _Pool, path: str, n_layers: int):
            fields = dict(pool.arrays[path])
            meta = self._meta_by_sig[pool.sig][path]
            return PackedLoRABatch(**fields, seg=None, **meta,
                                   tile_t=self.tile_t)

        def rebuild(node, path):
            if isinstance(node, dict):
                if set(node.keys()) == {"a", "b"}:
                    n_layers = next(L for p, L, _ in self._leaves()
                                    if p == path)
                    if len(live) == 1 and total == live[0].capacity:
                        return leaf_of(live[0], path, n_layers)
                    return PackedLoRABuckets(
                        buckets=tuple(leaf_of(p, path, n_layers)
                                      for p in live),
                        lookups=tuple(
                            jnp.broadcast_to(lut, (n_layers, total))
                            for lut in luts),
                        seg=None)
                return {k: rebuild(v, f"{path}/{k}") for k, v in node.items()}
            if isinstance(node, list):
                return [rebuild(v, f"{path}/{i}") for i, v in enumerate(node)]
            if isinstance(node, tuple):
                return tuple(rebuild(v, f"{path}/{i}")
                             for i, v in enumerate(node))
            return node

        self._tree = rebuild(self.like_tree, "")
        return self._tree

    # ----- accounting -----

    def hbm_bytes(self) -> int:
        """Bytes of the HBM slot pools — a function of the slot capacities
        (each priced at its signature's real page bytes), not of how many
        adapters are registered."""
        return sum(p.nbytes() for p in self._pools.values())

    def host_bytes(self) -> int:
        return sum(p.nbytes for p in self._host.values())

    def stats(self) -> Dict[str, Any]:
        """Counters and per-tier bytes, plus a per-pool breakdown.

        ``hit_rate`` is ``None`` when no :meth:`acquire` lookups have
        happened yet — an idle pool must not read as a perfect one on a
        dashboard; ``lookups`` carries the denominator so callers can
        tell 0/0 from 100/100. ``per_pool`` keys each recipe signature's
        label (e.g. ``"2-64-1"``) to its own hits/misses/swap-in-bytes/
        evictions plus capacity and pin occupancy — the instrument for
        the mixed-recipe residency cliff (``docs/observability.md``).
        """
        lookups = self.hits + self.misses
        t = self.transport.stats()
        per_pool: Dict[str, Dict[str, Any]] = {}
        for sig, pool in self._pools.items():
            counts = self._per_pool.get(
                sig, {"hits": 0, "misses": 0, "swap_ins": 0,
                      "swap_in_bytes": 0, "evictions": 0})
            pl = counts["hits"] + counts["misses"]
            per_pool[self._sig_label(sig)] = {
                **counts,
                "lookups": pl,
                "hit_rate": counts["hits"] / pl if pl else None,
                "capacity": pool.capacity,
                "resident": sum(o is not None for o in pool.owners),
                "pinned": sum(1 for aid, (s, _) in self._where.items()
                              if s == sig and self.pinned(aid)),
                "page_bytes": pool.page_bytes,
            }
        if self.telemetry is not None:
            reg = self.telemetry.registry
            reg.gauge("adapter_memory_slots",
                      help="total HBM slot capacity").set(
                sum(p.capacity for p in self._pools.values()))
            reg.gauge("adapter_memory_resident",
                      help="resident pages").set(len(self._where))
            reg.gauge("adapter_memory_pinned",
                      help="pinned adapters").set(len(self._pins))
            reg.gauge("adapter_memory_hbm_bytes").set(self.hbm_bytes())
            reg.gauge("adapter_memory_host_bytes").set(self.host_bytes())
        return {
            "slots": sum(p.capacity for p in self._pools.values()),
            "pools": len(self._pools),
            "resident": len(self._where),
            "pinned": len(self._pins),
            "hits": self.hits,
            "misses": self.misses,
            "lookups": lookups,
            "hit_rate": self.hits / lookups if lookups else None,
            "swap_ins": self.swap_ins,
            "swap_in_bytes": self.swap_in_bytes,
            "evictions": self.evictions,
            "stale_serves": self.stale_serves,
            "prefetch": dict(self.prefetch_counts),
            "dead": len(self._dead),
            "poisoned": len(self.poisoned),
            "host_reads": t["reads"],
            "host_read_retries": t["retries"],
            "host_read_failures": t["failures"],
            "hbm_slot_mb": self.hbm_bytes() / 1e6,
            "host_tier_mb": self.host_bytes() / 1e6,
            "per_pool": per_pool,
        }
