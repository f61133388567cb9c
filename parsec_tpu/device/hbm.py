"""Bounded device-memory management for tile workloads.

Reference semantics: the CUDA device module reserves tiles against a
zone-malloc'd device heap, evicts cold copies through clean/dirty LRU
lists, and stages data in/out around kernel launches
(device_cuda_module.c:864-1179, device_gpu.h:115-136,
utils/zone_malloc.c). On TPU, XLA/PJRT owns physical HBM, so this layer
manages *logical residency*: which tiles live as device ``jax.Array``
and which are spilled to host numpy, with the
:class:`~..utils.zone_malloc.ZoneAllocator` as the byte-accounting
structure (same role as the reference's zone heap).

Two eviction policies:

- **plan-informed** (``next_use`` schedules): the compiled executors
  know every tile's future use waves from the
  :class:`~..compiled.wavefront.WavefrontPlan`, so eviction picks the
  resident tile whose next use is farthest away (Belady's optimal
  policy) — strictly better than LRU, and only possible because the
  dataflow plan is static. This is the TPU-first upgrade over the
  reference's runtime LRU.
- **LRU** (no schedule): the host-runtime path (TPUDevice) registers
  collection tiles as tasks write them; when over budget the
  least-recently-used tile is rewritten into its collection as host
  numpy, releasing the device buffer.

Spilling moves bytes across the host link — correct but slow, exactly
like the reference's eviction under memory pressure. A POTRF sized
beyond the budget completes instead of aborting (tests exercise this
with an artificially small budget on CPU).
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, Hashable, Optional, Tuple

import numpy as np

from ..utils import mca_param
from ..utils.debug import debug_verbose
from ..utils.zone_malloc import ZoneAllocator

mca_param.register("device.hbm_budget_mb", 0,
                   help="device-memory budget for tile residency "
                        "management (0 = unlimited, no spilling)")
mca_param.register("device.hbm_prefetch", 1,
                   help="prefetch next-wave tiles during segmented "
                        "execution (async device_put overlap)")


def _nbytes(value: Any) -> int:
    nb = getattr(value, "nbytes", None)
    if nb is not None:
        return int(nb)
    return int(np.asarray(value).nbytes)


class HBMManager:
    """Residency manager over a logical device heap.

    Entries are keyed by any hashable (tile coordinates, collection
    keys). Each entry holds EITHER a device value (resident, accounted
    in the zone) or a host value (spilled). ``ensure`` stages entries
    in, evicting under pressure; ``put`` registers newly produced
    device values (evicting others to make the budget hold).
    """

    def __init__(self, budget_bytes: int, unit: int = 4096,
                 device: Any = None):
        import jax
        self.jax = jax
        self.budget = budget_bytes
        self.unit = unit
        # the chip a host value is staged on where the caller names none
        # (``ensure(device=)``): a Context's first chip module's, which
        # need not be JAX's first device (a comm rank's own chip, a cap
        # on the modules); None = wherever JAX puts an uncommitted value
        self.home = device
        # the budget is PER CHIP: one zone per jax device tiles land on
        # (per-chip device modules stage copies onto their own chips —
        # a single global zone would not bound any real HBM)
        self._zones: Dict[Any, ZoneAllocator] = {}
        self._entries: Dict[Hashable, Dict[str, Any]] = {}
        self._lock = threading.RLock()
        self._clock = 0
        self._stage_dev = None       # placement guess for reserve-first
        self.stats = {"stage_in": 0, "spills": 0, "bytes_staged": 0,
                      "bytes_spilled": 0, "peak_bytes": 0,
                      # eviction-policy split: victims chosen by the
                      # plan's next-use schedule (Belady) vs the LRU
                      # fallback (no schedule info on the victim)
                      "evict_belady": 0, "evict_lru": 0,
                      # owner-computes reads served by the remote
                      # stage-in path (fetch_tiles: segmented fetch
                      # straight into an HBM slot, no host copy kept)
                      "remote_stage_in": 0}

    # ---------------------------------------------------------- internal
    def _zone_for(self, dev) -> ZoneAllocator:
        z = self._zones.get(dev)
        if z is None:
            z = self._zones[dev] = ZoneAllocator(self.budget,
                                                 unit=self.unit)
        return z

    def zone_of(self, dev) -> ZoneAllocator:
        """The zone of the chip ``dev`` (every chip has the budget)."""
        with self._lock:
            return self._zone_for(dev)

    @property
    def zone(self) -> ZoneAllocator:
        """The zone of the manager's own chip (``home``; without one,
        of the chip the last host value landed on, else JAX's first)."""
        return self.zone_of(self.home or self._stage_dev or
                            self.jax.devices()[0])

    def _account_alloc(self, nbytes: int, dev) -> Optional[int]:
        zone = self._zone_for(dev)
        off = zone.malloc(nbytes)
        if off is not None:
            used = zone.bytes_used()
            if used > self.stats["peak_bytes"]:
                self.stats["peak_bytes"] = used   # max per-chip usage
        return off

    def _evict_one(self, protect: Tuple[Hashable, ...], dev) -> bool:
        """Spill the best victim ON ``dev`` not in ``protect``.
        Plan-informed when next_use hints exist (farthest next use
        first; never-used-again tiles are ideal victims), LRU
        otherwise."""
        with self._lock:
            best_key, best_rank = None, None
            for key, e in self._entries.items():
                if e["offset"] is None or key in protect or \
                        e.get("device") != dev or e.get("pins", 0) > 0:
                    continue
                if self._given(e["value"]):
                    # given to a program that updates the tile where it
                    # lies (``Chore.donates``): the buffer is the next
                    # version's, in flight, and is tracked again under
                    # this key at its write-back. Not ours to spill
                    continue
                nu = e.get("next_use")
                # rank: (next_use descending, last_use ascending);
                # next_use None = no schedule info -> pure LRU term
                rank = ((nu if nu is not None else -1), -e["last_use"])
                if best_rank is None or rank > best_rank:
                    best_key, best_rank = key, rank
            if best_key is None:
                return False
            e = self._entries[best_key]
            spill_cb = e.get("spill")
            try:
                host = np.asarray(e["value"])   # D2H (the slow path)
            except RuntimeError:
                if not self._given(e["value"]):
                    raise
                # given to a program since the look above: the next one
                return self._evict_one((*protect, best_key), dev)
            if spill_cb is not None:
                spill_cb(best_key, host)
            e["value"] = host
            self._zone_for(dev).free(e["offset"])
            e["offset"] = None
            e["device"] = None
            self.stats["spills"] += 1
            self.stats["evict_belady" if e.get("next_use") is not None
                       else "evict_lru"] += 1
            self.stats["bytes_spilled"] += host.nbytes
            debug_verbose(3, "hbm", "spilled %r (%d bytes)", best_key,
                          host.nbytes)
            return True

    def _reserve(self, nbytes: int, protect: Tuple[Hashable, ...],
                 dev) -> int:
        off = self._account_alloc(nbytes, dev)
        while off is None:
            if not self._evict_one(protect, dev):
                zone = self._zone_for(dev)
                raise MemoryError(
                    f"HBM budget too small: cannot reserve {nbytes} "
                    f"bytes on {dev} (budget {zone.capacity}, in use "
                    f"{zone.bytes_used()}, all resident tiles pinned)")
            off = self._account_alloc(nbytes, dev)
        return off

    @staticmethod
    def _given(value) -> bool:
        """Was this tracked tile's buffer given to a program (a
        ``jax.Array`` deleted by donation)? A host value never is."""
        return getattr(value, "is_deleted", bool)()

    @staticmethod
    def _device_of(value) -> Any:
        return getattr(value, "device", None)

    # ------------------------------------------------------------ public
    def ensure(self, key: Hashable, value: Any = None,
               protect: Tuple[Hashable, ...] = (),
               next_use: Optional[int] = None,
               spill: Optional[Callable] = None,
               best_effort: bool = False, device: Any = None) -> Any:
        """Return the device-resident value for ``key``, staging it in
        (and evicting under pressure) if needed. ``value`` supplies the
        data on first sight; ``protect`` keys are not eviction
        candidates during this call (the current wave's working set).
        A host value is staged on ``device`` (the chip of the module
        that asks), else on the manager's ``home``, and accounted in
        that chip's zone.
        ``best_effort=True`` never evicts: if no free space remains the
        current (possibly host) value is returned unstaged — the
        prefetch contract."""
        with self._lock:
            self._clock += 1
            e = self._entries.get(key)
            if e is None:
                if value is None:
                    raise KeyError(f"unknown HBM entry {key!r}")
                e = {"value": value, "offset": None, "last_use": 0,
                     "next_use": next_use, "spill": spill,
                     "device": None}
                self._entries[key] = e
            if spill is not None:
                e["spill"] = spill
            if next_use is not None:
                e["next_use"] = next_use
            e["last_use"] = self._clock
            if e["offset"] is None:
                nb = _nbytes(e["value"])
                host_val = e["value"]
                if isinstance(host_val, self.jax.Array):
                    # already in HBM: account it where it lives
                    dev = self._device_of(host_val)
                    if best_effort:
                        off = self._account_alloc(nb, dev)
                        if off is None:
                            return host_val
                    else:
                        off = self._reserve(nb, protect, dev)
                    e["offset"], e["device"] = off, dev
                    return host_val
                # host value: probe free space on the GUESSED landing
                # device first (no eviction!) so a failed best_effort
                # probe costs zero transfers; eviction decisions are
                # only ever made against the device the value actually
                # lands on. The one-tile window between staging and
                # reservation is the only transient physical overshoot.
                target = device or self.home
                guess = target or self._stage_dev or self.jax.devices()[0]
                off = self._account_alloc(nb, guess)
                if off is None and best_effort:
                    return host_val            # no room: stay spilled
                try:
                    staged = self.jax.device_put(host_val, target)
                except Exception:
                    if off is not None:        # never leak the probe
                        self._zone_for(guess).free(off)
                    raise
                dev = self._device_of(staged)
                if dev != guess and off is not None:
                    self._zone_for(guess).free(off)
                    off = None
                if off is None:
                    off = self._account_alloc(nb, dev)
                if off is None:
                    if best_effort:
                        del staged             # actual chip full too
                        return host_val
                    off = self._reserve(nb, protect, dev)
                self._stage_dev = dev
                e["offset"], e["device"] = off, dev
                e["value"] = staged
                self.stats["stage_in"] += 1
                self.stats["bytes_staged"] += nb
            return e["value"]

    def put(self, key: Hashable, value: Any,
            protect: Tuple[Hashable, ...] = (),
            next_use: Optional[int] = None,
            spill: Optional[Callable] = None,
            pin: bool = False) -> None:
        """Register a device value just produced (already in HBM).

        ``pin=True`` marks the entry ineligible for eviction until
        :meth:`unpin` — callers that put a value and then publish it
        elsewhere (e.g. the runtime writing the tile into a collection
        after tracking it) close the window where an eviction's spill
        would race the publish (ADVICE round 2: the spill's host write
        could be overwritten by the device value, leaving the
        collection holding an unaccounted device array)."""
        with self._lock:
            self._clock += 1
            old = self._entries.get(key)
            if old is not None and old["offset"] is not None:
                self._zone_for(old.get("device")).free(old["offset"])
                old["offset"] = None    # _reserve may raise: never leave
                #                         a dangling offset to double-free
            nb = _nbytes(value)
            dev = self._device_of(value)
            try:
                off = self._reserve(nb, protect + (key,), dev)
            except MemoryError:
                # the value exceeds the whole budget: drop the entry
                # entirely — keeping the superseded old value would pin
                # a dead version and serve stale data
                self._entries.pop(key, None)
                raise
            self._entries[key] = {
                "value": value, "offset": off, "last_use": self._clock,
                # pins ACCUMULATE across re-puts: a second writer's
                # pinned put while the first is inside its track->write
                # window must not drop the first pin (native workers
                # complete concurrently)
                "pins": (old or {}).get("pins", 0) + (1 if pin else 0),
                "next_use": next_use, "device": dev,
                "spill": spill if spill is not None else
                (old or {}).get("spill")}

    def unpin(self, key: Hashable) -> None:
        """Release one :meth:`put` pin; no-op for unknown keys (the
        entry may have been dropped by a failed oversized put)."""
        with self._lock:
            e = self._entries.get(key)
            if e is not None and e.get("pins", 0) > 0:
                e["pins"] -= 1

    def register(self, key: Hashable, value: Any,
                 next_use: Optional[int] = None,
                 spill: Optional[Callable] = None) -> None:
        """Record an entry WITHOUT staging it: host values stay on host
        until first ``ensure`` (lazy stage-in); device values are
        accounted immediately (they already occupy HBM)."""
        with self._lock:
            if key in self._entries:
                return
            e = {"value": value, "offset": None, "last_use": 0,
                 "next_use": next_use, "spill": spill, "device": None}
            self._entries[key] = e
            if isinstance(value, self.jax.Array):
                dev = self._device_of(value)
                e["offset"] = self._reserve(_nbytes(value), (key,), dev)
                e["device"] = dev

    def fetch_tiles(self, dc, keys_owners, comm, scope: str = "",
                    next_use: Optional[int] = None,
                    protect: Tuple[Hashable, ...] = ()) -> list:
        """Owner-computes remote stage-in (ROADMAP item 1): resolve a
        batch of collection tiles into DEVICE residency, treating
        "remote chip" as a stage-in source. Local (or already-tracked)
        tiles stage from the collection; remote tiles issue ONE
        concurrent segmented fetch (``CommEngine.fetch_tiles(...,
        stage=True)`` — per-segment H2D on the comm thread) and are
        accounted straight into their HBM slots with the ``next_use``
        hint intact, instead of materializing a host copy first.

        Entries are keyed per ``scope`` (the gathering taskpool's name
        — the cross-rank registry identity), so a tile re-gathered
        across waves of one pool stays resident while a later pool can
        never read a stale cached version. The same dataflow-ordering
        contract as ``fetch_tile`` applies: the tile must be final on
        its owner when this is called (CTL-gather). Returns values in
        order."""
        import weakref
        pairs = list(keys_owners)
        my_rank = getattr(comm, "rank", 0)
        single = getattr(comm, "nb_ranks", 1) <= 1
        dc_ref = weakref.ref(dc)

        def _sweep_tag(_k, _host, dc_ref=dc_ref):
            # no write-back: a fetched INPUT tile spills by dropping to
            # host only. The dc weakref default is the context sweep's
            # liveness tag (_hbm_entry_dead) — entries die with their
            # collection.
            return None

        out: Dict[int, Any] = {}
        fetch_slots, fetch_pairs = [], []
        for i, (key, owner) in enumerate(pairs):
            k = tuple(key) if isinstance(key, (tuple, list)) else (key,)
            mkey = ("fetch", scope, id(dc), k)
            with self._lock:
                have = mkey in self._entries
            if have:
                out[i] = self.ensure(mkey, protect=protect,
                                     next_use=next_use)
            elif owner == my_rank or single:
                out[i] = self.ensure(mkey, value=dc.data_of(key),
                                     protect=protect, next_use=next_use,
                                     spill=_sweep_tag)
            else:
                fetch_slots.append((i, mkey))
                fetch_pairs.append((key, owner))
        if fetch_pairs:
            vals = comm.fetch_tiles(dc, fetch_pairs, scope=scope,
                                    stage=True)
            for (i, mkey), v in zip(fetch_slots, vals):
                out[i] = self.ensure(mkey, value=v, protect=protect,
                                     next_use=next_use, spill=_sweep_tag)
                with self._lock:
                    self.stats["remote_stage_in"] += 1
        return [out[i] for i in range(len(pairs))]

    def hint(self, key: Hashable, next_use: Optional[int] = None) -> None:
        """Refresh an entry's next-use hint + LRU stamp WITHOUT staging
        or evicting — the KV state layer's page-touch path (every page
        write/read advances its expected next use, so page-level Belady
        ranks cold prefix pages as victims ahead of hot ones). No-op
        for unknown keys."""
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                return
            self._clock += 1
            e["last_use"] = self._clock
            if next_use is not None:
                e["next_use"] = next_use

    def value(self, key: Hashable) -> Any:
        """Current value (device or spilled host) without staging."""
        with self._lock:
            return self._entries[key]["value"]

    def resident_bytes(self) -> int:
        with self._lock:
            return sum(z.bytes_used() for z in self._zones.values())

    def drop(self, key: Hashable) -> None:
        with self._lock:
            e = self._entries.pop(key, None)
            if e is not None and e["offset"] is not None:
                self._zone_for(e.get("device")).free(e["offset"])

    def sweep(self, dead: Callable[[Hashable, Dict[str, Any]], bool]
              ) -> int:
        """Drop every entry for which ``dead(key, entry)`` is true —
        e.g. tiles of garbage-collected collections. Returns the count
        dropped."""
        with self._lock:
            victims = [k for k, e in self._entries.items() if dead(k, e)]
            for k in victims:
                self.drop(k)
            return len(victims)


def track_collection_write(mgr: Optional[HBMManager], dc, key,
                           value) -> Optional[Hashable]:
    """Track a device-resident tile a task is about to write into its
    collection (pinned — see :meth:`HBMManager.put`); returns the
    manager key to :meth:`~HBMManager.unpin` AFTER the collection write,
    or None when the value is untracked (host value / over-budget).

    Shared by the host runtime (core.context complete_task) and the
    native executor so both completion paths enforce the budget the
    same way. The spill closure holds the collection weakly — dead
    collections' entries are swept when their taskpool terminates
    instead of being pinned forever."""
    import weakref
    if mgr is None or not isinstance(value, mgr.jax.Array):
        return None
    k = tuple(key) if isinstance(key, (tuple, list)) else (key,)
    dc_ref = weakref.ref(dc)

    def _spill(_k, host, dc_ref=dc_ref, key=key):
        target = dc_ref()
        if target is not None:
            target.write_tile(key, host)

    mkey = (id(dc), k)
    try:
        mgr.put(mkey, value, spill=_spill, pin=True)
    except MemoryError:
        from ..utils.debug import warning
        warning("hbm", "tile %r exceeds the device budget alone; "
                "left untracked", key)
        return None
    return mkey


def manager_from_mca(device: Any = None) -> Optional[HBMManager]:
    """Build an :class:`HBMManager` from the MCA budget param, or None
    when unlimited; ``device`` is the chip it stages host values on
    (``HBMManager.home``)."""
    mb = int(mca_param.get("device.hbm_budget_mb", 0))
    if mb <= 0:
        return None
    return HBMManager(mb * (1 << 20), device=device)
