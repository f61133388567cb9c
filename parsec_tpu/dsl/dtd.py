"""DTD: dynamic task discovery.

Reference: parsec/interfaces/dtd/insert_function.c (3,612 LoC) — tasks are
inserted at runtime with varargs flags (INPUT/OUTPUT/INOUT/VALUE/SCRATCH +
AFFINITY/..., insert_function.h:60-78); task classes are created lazily per
(function, argument-shape) (insert_function.c:1015); per-tile
``last_writer``/``last_user`` tracking orders accesses
(insert_function_internal.h:191-211, overlap_strategies.c); a sliding
window throttles insertion (insert_function.h:131-142).

TPU-first divergence: task bodies are functional (values in → new values
out), so WAR hazards vanish — a reader snapshots the version current at
*insert* time (program order), immutable arrays keep it valid, and a later
writer simply produces a new version. Only RAW (value flows from the
in-flight last writer) and WAW (writer chain) edges are materialized, which
strictly increases available parallelism versus the reference's read-list
serialization (overlap_strategies.c:38-120). Where NO reader was inserted
on a tile since its last writer, the next INOUT writer of a pure
accelerator body is the only holder of the incoming version and gives it
to its program, which updates the tile where it lies as upstream's kernels
do (``_Tile.readers``, ``insert_task``): a writer chain holds every tile
once, and a snapshot some reader holds is never given.

Distributed DTD (reference: every rank replays the same insertion
sequence; remote activations for undiscovered tasks are parked,
remote_dep_mpi.c:1935-1961; bcast restricted to star, remote_dep.c:543):
tasks are identified by their per-taskpool insertion sequence number
(identical on every rank). A task placed on another rank becomes a
*shell*: no body runs locally, but tile tracking is updated so the
dataflow crosses ranks correctly. Each tile carries a ``holder_rank`` —
the rank holding the version current at this point in program order,
updated identically on every rank during replay — so:

- a local reader whose version is held remotely counts one extra dep and
  receives the value as a remote activation (sent by the holder, which
  replays the same insert as a shell);
- a local completion delivers values to remote shells linked as
  successors (star fan-out);
- a shell read of a tile this rank holds (no writer in flight) triggers
  an eager push of the current version.

``flush()`` is collective in distributed mode: each rank quiesces its
local writers, pushes tiles it holds back to their owners
(parsec_dtd_data_flush analog), waits for acks, and barriers.

Usage::

    tp = dtd.Taskpool("gemm")
    ctx.add_taskpool(tp)
    tp.insert_task(body, dtd.TileArg(A, (i, k), dtd.INPUT),
                         dtd.TileArg(C, (i, j), dtd.INOUT),
                         dtd.ValueArg(alpha))
    ...
    tp.wait()
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..core.spans import SPAN_DTD_FLUSH, SPAN_INSERT, StageSpan
from ..core.task import Chore, DeviceType, Flow, FlowAccess, Task
from ..core.taskpool import DEPS_COUNTER, SuccessorRef, TaskClass
from ..core.taskpool import Taskpool as CoreTaskpool
from ..data.collection import DataCollection
from ..utils import mca_param

# access flags (insert_function.h:60-78 analog)
INPUT = FlowAccess.READ
OUTPUT = FlowAccess.WRITE
INOUT = FlowAccess.RW

_GOAL_UNSET = 1 << 40       # sentinel while an insert is still linking

# process-wide jit cache for pure=True bodies: (fn, argspec sig) →
# jitted woven callable. Keyed by the fn OBJECT (kept alive by the
# cache — no id-reuse aliasing), so module-level bodies compile once
# per process even across taskpools. It serves whoever calls a chore's
# hook as it is: the CPU device, the native engine's pump, and a TPU
# module where its own program table has no entry (inputs that share no
# signature). A TPU module otherwise never calls the woven ``_hook``: a
# lone task and a group run ``batch_body`` from its table, and the group
# of a body that declares a stacked form (``insert_task(stacked=)`` →
# ``Chore.batch_hook``) runs that hook over the stacked members. Not to
# be extended: it goes when the CPU device gets its programs from that
# table (D12)
_PURE_JIT_CACHE: Dict[Any, Callable] = {}
_PURE_JIT_LOCK = threading.Lock()

mca_param.register("dtd.window_size", 4096,
                   help="max in-flight inserted tasks before the inserter throttles")
mca_param.register("dtd.threshold_size", 2048,
                   help="inserter resumes below this many in-flight tasks")


@dataclass
class TileArg:
    """A data argument: tile ``key`` of ``collection`` with an access mode.
    ``affinity=True`` marks the argument whose owner rank places the task
    (PARSEC_AFFINITY analog)."""
    collection: DataCollection
    key: Tuple
    access: FlowAccess
    affinity: bool = False


@dataclass
class ValueArg:
    """Pass-by-value argument (PARSEC_VALUE analog)."""
    value: Any


@dataclass
class ScratchArg:
    """Per-task scratch allocation (PARSEC_SCRATCH analog): the body
    receives a fresh numpy buffer of ``shape``/``dtype``."""
    shape: Tuple[int, ...]
    dtype: Any = "float32"


class _Shell:
    """Placeholder for a task placed on another rank (the reference's
    remote shell task, insert_function.c distributed path)."""

    __slots__ = ("seq", "rank")

    def __init__(self, seq: int, rank: int):
        self.seq = seq
        self.rank = rank


class _Tile:
    """Per-(collection, key) tracking state (parsec_dtd_tile_t analog).

    ``holder_rank`` is the rank holding the version current at this point
    of the replayed insertion order (None = the collection owner).
    ``readers`` counts the READ-only arguments inserted on the tile
    since its last writer was (linked to a writer in flight or
    snapshotted alike; a remote shell's too): 0 says that the next
    writer's task is the only one that holds the current version."""

    __slots__ = ("collection", "key", "lock", "last_writer",
                 "last_writer_flow", "holder_rank", "flushed", "readers")

    def __init__(self, collection: DataCollection, key):
        self.collection = collection
        self.key = key
        self.lock = threading.Lock()
        # not-yet-complete writer: local Task or remote _Shell
        self.last_writer = None
        self.last_writer_flow: Optional[str] = None
        self.holder_rank: Optional[int] = None
        self.readers = 0
        # flush_tile() was called: the last writer's retire takes the
        # tile out of the bank, and a writer inserted later puts it back
        # (under ``lock``, as last_writer)
        self.flushed = False


class _TileBank:
    """parsec_dtd_tile_of analog: lazily materialized tracking tiles.

    A tile is tracked from its first use in an insert until it is
    flushed (``Taskpool.flush_tile`` / ``flush_all``): a flushed tile
    leaves the bank at once where no writer is in flight, else when its
    last writer retires, so a pool that flushes what it has finished
    with tracks a working set and not every tile it ever touched. A
    tile without a writer holds nothing a fresh one would not (its
    version is the collection's), so a later insert on it starts over.
    The blocking ``Taskpool.flush`` waits and takes nothing out.
    What is still tracked when the pool ends goes then
    (``Taskpool._let_go``): a finished pool holds no tile, and through
    it no collection. One thing of a tile outlives its tracking: that
    readers were inserted on its current version (``_Tile.readers``),
    whose snapshots a writer inserted after the flush must leave alone;
    the bank keeps the keys of such tiles (``_read``, a key and no
    tile) and a tile made anew for one starts as read. ``peak`` is the
    most tiles tracked at once, ``retired`` those a flush took out,
    ``dropped`` those the pool's end did."""

    def __init__(self) -> None:
        self._tiles: Dict[Tuple[int, Any], _Tile] = {}
        self._read: set = set()
        self._lock = threading.Lock()
        self.peak = 0
        self.retired = 0
        self.dropped = 0

    def tile_of(self, dc: DataCollection, key) -> _Tile:
        hkey = (dc.dc_id, tuple(key) if isinstance(key, (tuple, list)) else key)
        # insertion fast path: dict reads are GIL-atomic, so a hit costs
        # no lock (every tile arg of every insert lands here); the lock
        # only serializes first-touch materialization
        t = self._tiles.get(hkey)
        if t is None:
            with self._lock:
                t = self._tiles.get(hkey)
                if t is None:
                    t = _Tile(dc, hkey[1])
                    if hkey in self._read:
                        self._read.discard(hkey)
                        t.readers = 1
                    self._tiles[hkey] = t
                    self.peak = max(self.peak, len(self._tiles))
        if t.collection is not dc:
            # two live collections sharing one dc_id would silently
            # alias each other's writer tracking (values vanish);
            # dc_id is the wire identity, so it must be unique
            raise ValueError(
                f"distinct collections share dc_id={dc.dc_id}; "
                f"tile {hkey[1]} would alias "
                f"{getattr(t.collection, 'name', t.collection)!r} and "
                f"{getattr(dc, 'name', dc)!r} — give each collection "
                "a unique dc_id")
        return t

    def get(self, dc: DataCollection, key) -> Optional[_Tile]:
        """The tracked tile, or None: looking makes none."""
        return self._tiles.get(
            (dc.dc_id, tuple(key) if isinstance(key, (tuple, list)) else key))

    def all(self) -> List[_Tile]:
        with self._lock:
            return list(self._tiles.values())

    # the per-tile flush; a tile's lock is taken before the bank's
    def flush(self, tile: _Tile) -> None:
        """Stop tracking ``tile`` once nothing inserted so far writes
        it: now, or at its last writer's retire."""
        with tile.lock:
            tile.flushed = True
            if tile.last_writer is None:
                self.retire(tile)

    def retire(self, tile: _Tile) -> None:
        """Take a flushed ``tile`` out (the caller holds its lock and has
        seen no writer on it). It stays ``flushed``: an insert that
        looked it up before this and writes it after has to see that the
        bank no longer holds it."""
        with self._lock:
            hkey = (tile.collection.dc_id, tile.key)
            if self._tiles.get(hkey) is tile:
                del self._tiles[hkey]
                self.retired += 1
                if tile.readers:
                    self._read.add(hkey)

    def drop(self, collection: Optional[DataCollection] = None) -> None:
        """The pool has ended and every version is where it belongs:
        stop tracking what is left (of ``collection``, if given)."""
        with self._lock:
            keep = {} if collection is None else {
                k: t for k, t in self._tiles.items()
                if t.collection is not collection}
            self.dropped += len(self._tiles) - len(keep)
            self._tiles = keep
            if collection is None:
                self._read.clear()
            else:
                self._read = {k for k in self._read
                              if k[0] != collection.dc_id}

    def readopt(self, tile: _Tile) -> None:
        """A writer inserted after ``tile``'s flush (the caller holds its
        lock): the flush was of the uses before it, and the tile, which
        that flush may already have taken out, is tracked again."""
        tile.flushed = False
        with self._lock:
            hkey = (tile.collection.dc_id, tile.key)
            self._tiles.setdefault(hkey, tile)
            self._read.discard(hkey)

    def read_untracked(self, tile: _Tile) -> None:
        """A reader inserted on ``tile`` after its flush took it out
        (the caller holds its lock, through a handle it kept): the tile
        made anew for the key has to know."""
        with self._lock:
            self._read.add((tile.collection.dc_id, tile.key))


# a DTD class's callbacks (``_task_class_for``): functions of the task
def _written_tile(task: Task):
    """``(collection, key)`` of the tile ``task``'s placement among
    several chip modules follows: the argument marked ``affinity``, else
    the first it writes; None for a task that writes no tile."""
    where = task.dsl.get("affinity")
    if where is None and task.dsl.get("out_tiles"):
        tile = task.dsl["out_tiles"][0][0]
        where = (tile.collection, tile.key)
    return where


def _data_lookup(task: Task) -> None:
    """prepare_input analog: resolve aliased flows (same tile passed
    twice in one insert) from their primary flow's delivered value."""
    for alias, primary in task.dsl.get("aliases", {}).items():
        if alias not in task.data:
            task.data[alias] = task.data.get(primary)


def _iterate_successors(task: Task):
    return task.taskpool._iterate_successors(task)


class Taskpool(CoreTaskpool):
    """DTD taskpool (parsec_dtd_taskpool_new analog)."""

    def __init__(self, name: str = "dtd"):
        super().__init__(name=name)
        self.tiles = _TileBank()
        self._classes: Dict[Any, TaskClass] = {}
        self._class_lock = threading.Lock()
        self._goals: Dict[int, int] = {}
        self._tasks_by_seq: Dict[int, Task] = {}
        # Per-seq striped locks: goal publication + pending-finalize
        # (insert_task) and goal read + count (activate_dep) must be one
        # critical section *per seq* — a single global lock here would
        # serialize every dependency activation of every DTD task. Dict
        # accesses themselves are GIL-atomic; only the per-seq ordering
        # needs the lock.
        self._seq_locks = [threading.Lock() for _ in range(64)]
        self._inflight = 0
        self._inflight_cv = threading.Condition()
        self._throttle_waiters = 0   # completers notify only when an
        #                              inserter is actually parked
        self._window = int(mca_param.get("dtd.window_size", 4096))
        self._threshold = int(mca_param.get("dtd.threshold_size", 2048))
        self._closed = False
        # multi-tenant serving hooks (serving/runtime.py). ``admission``
        # is called with (taskpool, n_rows) BEFORE rows are inserted —
        # it applies the tenant's cross-pool window: park briefly for
        # backpressure, or raise AdmissionRejected when the tenant's
        # queue depth / HBM reservation is exceeded (explicit rejection
        # instead of unbounded parking). ``on_retire`` fires once per
        # admitted row leaving flight (local completion or remote-shell
        # handoff) so the tenant window drains.
        self.admission = None
        self.on_retire: Optional[Callable[["Taskpool"], None]] = None
        # per-stage overhead accounting (runtime.stage_timers /
        # profiling `overhead` module): wall time spent in insert_task
        # on the inserting thread(s)
        self.insert_s = 0.0
        self.insert_calls = 0
        # on the same flag, what the front end counts (the Python
        # engine): tile arguments linked to a writer still in flight at
        # insertion (``dtd_args_linked``: discovery runs ahead of
        # execution; across ranks a version held elsewhere counts here
        # too, it arrives as an activation as well) or read from the
        # collection because the writer had retired
        # (``dtd_args_snapshot``), and the inserter's parks in the
        # window (``dtd_window_waits``, ``dtd_window_wait_s``). Folded
        # into ``Context.dtd_counters`` when the pool ends, with the
        # bank's ``dtd_tiles_tracked_peak`` and ``dtd_tiles_flushed``
        self.counters: Dict[str, float] = {}
        # native dynamic-task engine (dsl/dtd_native.py): resolved once
        # at first insert per the runtime.native_dtd knob and the
        # instrumented-fallback rule; None = the Python engine below
        self._native = None
        self._native_checked = False
        # per-taskpool insertion sequence: the cross-rank task identity
        # (every rank replays the same sequence → same numbering)
        self._seq = 0
        # wire "class" used to address DTD activations rank-to-rank
        self._wire_tc = TaskClass("__dtd__", -1, params=("seq",), flows=[])
        self._wire_tc.make_key = lambda locals: ("dtd", locals[0])
        self._tc_by_name["__dtd__"] = self._wire_tc
        # collective pin: the reference restricts DTD broadcasts to the
        # star topology (remote_dep.c:543-551) — the data plane reads
        # this before comm.bcast_topology (collectives.resolve_topology)
        self.bcast_topology = "star"
        self._flush_lock = threading.Lock()
        self._flush_acks = 0
        self._flush_cv = threading.Condition(self._flush_lock)
        # count of remote activations that arrived BEFORE the local
        # replay discovered their task (parked against _GOAL_UNSET) —
        # observability for the remote_dep_mpi.c:1935-1961 analog
        # (incremented under the seq lock; GIL-atomic reads)
        self.parked_activations = 0
        # hold the taskpool open while the user is still inserting
        # (reference: DTD keeps a pending action until taskpool_wait)
        # _enqueue_counted: the +1 only happens when registration
        # completes (a broken-mesh refusal in taskpool_registered stops
        # add_taskpool BEFORE on_enqueue) — wait() must not decrement a
        # count that was never incremented (runtime_actions would go
        # negative and mask the peer-death diagnostic)
        self._enqueue_counted = False

        def _on_enqueue(tp):
            tp.addto_runtime_actions(1)
            tp._enqueue_counted = True

        self.on_enqueue = _on_enqueue

    def _seq_lock(self, stripe: int):
        """Seq-stripe lock, wrapped for acquisition-order reporting when
        the dfsan sanitizer is installed (analysis/dfsan.py); a bare
        Lock otherwise — the hot path pays one attribute read."""
        lock = self._seq_locks[stripe]
        ctx = self.context
        san = ctx.dfsan if ctx is not None else None
        if san is not None:
            return san.wrap_lock(lock, "dtd-seq", stripe)
        return lock

    # -- rank helpers ------------------------------------------------------
    @property
    def my_rank(self) -> int:
        return self.context.my_rank if self.context is not None else 0

    @property
    def nb_ranks(self) -> int:
        return self.context.nb_ranks if self.context is not None else 1

    def _on_terminated(self) -> None:
        # release an inserter blocked in the sliding-window throttle (the
        # pool may have aborted while insert_task was waiting for drain)
        with self._inflight_cv:
            self._closed = self._closed or (self.error is not None)
            self._inflight_cv.notify_all()
        eng = self._native
        ctx = self.context
        if eng is not None:
            if self.error is not None:
                # abort/cancel: release the native queues (queued tasks
                # drop at select time) and any natively-parked inserter
                eng.cancel()
            if ctx is not None:
                # fold the engine's counters into the context totals so
                # parsec_tasks_completed_total survives the pool; an
                # aborted pool with tasks still in flight keeps its
                # engine pumped until they drain (retiring state)
                ctx._ndtd_retire(eng)
        if not self._complete_evt.is_set():
            # before the waiter is let go: what it drops next is free
            self._let_go()
            if ctx is not None and (self.counters or self.insert_calls):
                ctx.fold_dtd_counters(dict(
                    self.counters, dtd_tiles_tracked_peak=self.tiles.peak,
                    dtd_tiles_flushed=self.tiles.retired,
                    dtd_tiles_dropped_at_end=self.tiles.dropped,
                    dtd_insert_s=self.insert_s,
                    dtd_insert_calls=self.insert_calls))
        super()._on_terminated()

    def _let_go(self) -> None:
        """The pool has ended: it refers to none of its user's data
        from here on. Every produced version is in its collection
        (``_iterate_successors`` step 1), so the bank's tiles, each of
        which holds its collection, hold nothing a fresh one would not.
        Across ranks a tile's current version may lie away from its
        owner until the collective :meth:`flush` that follows
        ``wait()`` has sent it home, and a late write-back finds its
        collection through the bank: there the bank stays while any
        tile is held away (every rank replays the same holders, so
        every rank decides alike), and what a flush has sent home goes
        when that flush is through (``_flush_distributed``), the rest
        with the pool. An aborted pool's tasks still drain: it keeps
        what they read until it goes itself."""
        if self.error is not None:
            return
        if self.nb_ranks > 1 and any(
                t.holder_rank is not None and
                t.holder_rank != t.collection.rank_of(t.key)
                for t in self.tiles.all()):
            return
        self.tiles.drop()
        self._tasks_by_seq.clear()
        self._goals.clear()
        self.pending.drop_values()

    # ------------------------------------------------------------- classes
    def _task_class_for(self, fn: Callable, shape: Tuple,
                        device: DeviceType, pure: bool = False,
                        stacked: Optional[Tuple] = None,
                        given: Optional[Tuple[str, ...]] = None
                        ) -> TaskClass:
        """Lazily create a task class per (fn, arg shape)
        (insert_function.c:1015 analog) and per set of flows its tasks
        give to their program (``given``: the chore's ``donates``, so
        tasks that give different flows never share a program or a
        launch). None asks for the class a row is inserted under, which
        gives the most it may: every INOUT tile argument of a pure body
        an accelerator may run, nothing of any other body;
        ``_insert_one`` moves a task whose tiles say otherwise to the
        variant that gives what they allow. Resolution is on the
        insertion hot path, so a cache hit is a lock-free dict read
        (GIL-atomic); the lock only serializes creation."""
        # flow names follow insert_task's tile-only numbering
        # (value/scratch args don't consume a flow slot)
        tiles = [access for kind, access in shape if kind == "tile"]
        inout = tuple(f"f{i}" for i, access in enumerate(tiles)
                      if access == FlowAccess.RW)
        if given is None:
            given = inout if pure and device & DeviceType.TPU else ()
        key = (fn, shape, device, pure, stacked, given)
        tc = self._classes.get(key)
        if tc is not None:
            return tc
        with self._class_lock:
            tc = self._classes.get(key)
            if tc is not None:
                return tc
            flows = [Flow(f"f{i}", access if access else FlowAccess.READ)
                     for i, access in enumerate(tiles)]
            tc = TaskClass(getattr(fn, "__name__", "dtd_task"),
                           len(self.task_classes), params=("seq",),
                           flows=flows, deps_mode=DEPS_COUNTER)
            # task identity is the insertion sequence number — identical on
            # every rank, so activations address tasks unambiguously
            tc.make_key = lambda locals: ("dtd", locals[0])
            # the pool holds its classes, so a class holds the pool's
            # table and plain functions that find the pool through the
            # task, never the pool or a bound method of it: a finished
            # pool that its user drops is freed there and then, by
            # reference count
            tc.deps_goal = lambda locals, _goals=self._goals: \
                _goals.get(locals[0], _GOAL_UNSET)
            tc.iterate_successors = _iterate_successors
            tc.data_lookup = _data_lookup
            tc.written_tile = _written_tile
            # what ``_insert_one`` needs to find a variant, the INOUT
            # flows it counts, and those this class's chore gives
            tc.dtd_variant_of = key[:5]
            tc.dtd_inout = inout
            tc.dtd_given = given

            if pure:
                # pure=True contract (insert_task): fn is a pure
                # function of its arguments, so the whole woven body is
                # jitted once per (argspec signature, arg shapes) and
                # every task of the class dispatches asynchronously as
                # ONE launch instead of one eager dispatch per op
                # (the reference's DTD bodies are BLAS/CUDA kernels,
                # i.e. pure by construction; impure Python bodies keep
                # the default eager path). The jit cache is process-wide
                # (keyed by fn identity + argspec signature) so repeated
                # taskpools over the same body compile once.
                jit_cache = _PURE_JIT_CACHE
                jit_lock = _PURE_JIT_LOCK

                def _spec_key(spec):
                    parts = []
                    for kind, payload in spec:
                        if kind == "tile":
                            parts.append(("tile",))
                        elif kind == "scratch":
                            parts.append(("scratch", tuple(payload[0]),
                                          str(payload[1])))
                        elif isinstance(payload, (int, float, str, bool,
                                                  type(None))):
                            parts.append(("value", payload))
                        else:
                            # unhashable payload: identity-keyed. The
                            # closure keeps the object alive (no id
                            # reuse), but the payload's CONTENTS are
                            # baked in at trace time — mutating an
                            # array payload in place between inserts
                            # would silently serve the stale compile.
                            # Contract (insert_task docstring): ValueArg
                            # payloads under pure=True are immutable.
                            parts.append(("value", id(payload)))
                    return tuple(parts)

                def _make_woven(spec, _fn=fn):
                    import jax.numpy as jnp

                    def woven(*fv, _spec=tuple(spec)):
                        args: List[Any] = []
                        it = iter(fv)
                        for (kind, payload) in _spec:
                            if kind == "tile":
                                args.append(next(it))
                            elif kind == "value":
                                args.append(payload)
                            else:
                                args.append(jnp.zeros(
                                    payload[0], dtype=payload[1]))
                        return _fn(*args)

                    return woven

                def _hook(task: Task, *flow_vals, _fn=fn):
                    import jax
                    from ..ops.tile_kernels import matmul_precision
                    spec = task.dsl["argspec"]
                    # the MXU precision knob is read at TRACE time by
                    # the tile kernels, so it must be part of the cache
                    # identity — otherwise a later precision change
                    # would silently keep serving the old compile
                    skey = (_fn, _spec_key(spec), matmul_precision())
                    # lock-free fast path (dict reads are GIL-atomic);
                    # the lock only serializes compile-on-miss
                    jf = jit_cache.get(skey)
                    if jf is not None:
                        return jf(*flow_vals)
                    with jit_lock:
                        jf = jit_cache.get(skey)
                        if jf is None:
                            jf = jax.jit(_make_woven(spec))
                            jit_cache[skey] = jf
                    return jf(*flow_vals)

                # the pure body for a device module's program table
                # (Chore.pure_body): tasks whose woven bodies are
                # identical — same argspec signature at the same
                # precision — share its programs, alone or several to a
                # launch, even though the hook itself reads per-task
                # metadata
                def _batch_sig(task: Task):
                    # fn identity is already in the group's key: one
                    # chore
                    from ..ops.tile_kernels import matmul_precision
                    return (_spec_key(task.dsl["argspec"]),
                            matmul_precision())

                def _batch_body(task: Task):
                    return _make_woven(task.dsl["argspec"])
            else:
                def _hook(task: Task, *flow_vals, _fn=fn):
                    args: List[Any] = []
                    it = iter(flow_vals)
                    for (kind, payload) in task.dsl["argspec"]:
                        if kind == "tile":
                            args.append(next(it))
                        elif kind == "value":
                            args.append(payload)
                        else:  # scratch
                            args.append(np.zeros(payload[0],
                                                 dtype=payload[1]))
                    return _fn(*args)

            if pure:
                # batchable=False: the hook reads its task and
                # self-jits; batch_sig/batch_body hand a device module
                # the woven body for its own programs, and a declared
                # stacked form goes where a PTG body's does
                hook, shared = stacked or (None, None)
                tc.add_chore(Chore(
                    device, _hook, batchable=False,
                    batch_sig=_batch_sig, batch_body=_batch_body,
                    batch_hook=hook, batch_hook_shared=None if hook is None
                    else tuple(f"f{i}" for i in shared),
                    donates=given or None))
            else:
                tc.add_chore(Chore(device, _hook, batchable=False))
            self.add_task_class(tc)
            self._classes[key] = tc
            return tc

    # ------------------------------------------------------------- insert
    def _placement(self, args) -> int:
        """Owner rank of the task: the AFFINITY tile's owner, else the
        first tile argument's owner, else round-robin by sequence
        (PARSEC_AFFINITY analog — deterministic across the replay)."""
        first = None
        for a in args:
            if isinstance(a, TileArg):
                if a.affinity:
                    return a.collection.rank_of(a.key)
                if first is None:
                    first = a
        if first is not None:
            return first.collection.rank_of(first.key)
        return self._seq % self.nb_ranks

    def insert_task(self, fn: Callable, *args, priority: int = 0,
                    device: DeviceType = DeviceType.ALL,
                    name: Optional[str] = None,
                    pure: bool = False,
                    stacked: Optional[Tuple] = None) -> Optional[Any]:
        """parsec_dtd_insert_task analog (insert_function.c:3488). In
        distributed mode every rank calls this with the identical sequence;
        returns the local Task (Python engine) or the task's insertion
        sequence number as an opaque int handle (native engine — no
        Python Task object exists there, by design), or None when the
        task is placed remotely (a shell — only tile tracking is
        updated here). Callers must treat the result as opaque
        not-None evidence; the ``name`` hint is display-only and unused
        by both engines.

        ``pure=True`` declares ``fn`` a pure function of its arguments:
        the body is jitted (per arg-shape/value signature) so device
        dispatch is asynchronous — the performance path for tile math
        (side-effecting Python bodies must keep the default). Non-scalar
        ``ValueArg`` payloads are baked into the compiled body at trace
        time and cached by object identity, so they must be treated as
        IMMUTABLE once inserted — mutating an array payload in place
        between inserts would silently serve the stale compile.

        **An INOUT tile is the pool's to overwrite**, as upstream, from
        this call until the tile is flushed or the pool ends: where no
        reader was inserted on the tile since its last writer (so this
        task is the only one that holds the incoming version), the tile
        appears once among the arguments, its version is on this rank
        and the body is ``pure`` and an accelerator's, the task GIVES
        the incoming version to its program (``Chore.donates``): a chip
        module writes the new version into the buffer the old one lies
        in, the launch holds nothing new, and the old ``jax.Array`` is
        deleted once the launch is made, the collection's own array
        (the version no task of this pool wrote) like any other. The
        collection holds the new version from the task's completion
        (``write_tile``); between the launch and that write its entry
        is a deleted array. So read a result from the collection after
        ``flush``/``wait``, never from an array kept aside, and keep a
        copy (NumPy, or ``jnp.copy``) of a version you want to see
        again. A reader inserted between two writers keeps its snapshot:
        the second writer sees that the tile was read and returns a new
        tile, exactly as before; a reader inserted after a giving writer
        gets that writer's output. An impure or CPU body, the same tile
        twice in one task, a version that arrives from another rank:
        nothing is given. The body returns its outputs in the order of
        its written arguments (the contract on ``Chore.donates``).

        ``stacked=(hook, shared)`` declares a pure body's stacked form,
        what ``batch_hook`` / ``batch_hook_shared`` are to a PTG body
        (``Chore``; an accelerator module reads both there): where ready
        tasks of this body leave as one launch, ``hook(*stacks)`` runs
        once in place of the body once a member. ``stacks`` are the tile
        arguments the body reads (INPUT, INOUT), in order, each with the
        members along a new first axis; it returns the new value(s) of
        the written tile(s) stacked likewise. ``shared`` gives the
        positions, among the tile arguments, of those the hook takes to
        hold ONE tile for the whole group (a column's TRSMs share their
        factor); a group that does not leaves as lone tasks. Value and
        scratch arguments do not reach the hook. A task alone, and every
        task on a module without launches of several, runs ``fn``."""
        if stacked is not None:
            stacked = self._stacked(stacked, pure)
        ctx = self.context
        if ctx is None or not ctx.stage_timers:
            return self._insert_task(fn, args, priority, device, pure,
                                     stacked)
        # the insert stage covers both engines: stage timers no longer
        # force the Python one (ISSUE 13)
        with StageSpan(SPAN_INSERT) as span:
            out = self._insert_task(fn, args, priority, device, pure,
                                    stacked)
        self.insert_s += span.seconds
        self.insert_calls += 1
        return out

    @staticmethod
    def _stacked(stacked, pure: bool) -> Tuple:
        """``(hook, shared)`` as the class cache keys it."""
        if not pure:
            raise ValueError("stacked= declares the stacked form of a "
                             "pure body: pass pure=True")
        hook, shared = stacked
        return hook, tuple(shared)

    def _insert_task(self, fn, args, priority, device, pure, stacked=None):
        self._check_insertable()
        if self.admission is not None:
            self.admission.admit(self, 1)
        eng = self._engine()
        if eng is not None:
            # native hot loop: returns the task's sequence number (the
            # opaque handle — native tasks have no Python Task object)
            return eng.insert_rows(fn, [args], priority, device, pure)[0]
        tc = self._task_class_for(fn, self._shape_of(args), device,
                                  pure=pure, stacked=stacked)
        task = self._insert_one(tc, args, priority, None, None)
        self._throttle()
        return task

    def insert_tasks(self, fn: Callable, rows, *, priority: int = 0,
                     priorities: Optional[List[int]] = None,
                     device: DeviceType = DeviceType.ALL,
                     pure: bool = False,
                     stacked: Optional[Tuple] = None
                     ) -> List[Optional[Any]]:
        """Batched :meth:`insert_task` — the insertion fast path. All
        ``rows`` (sequences of Tile/Value/Scratch args) are inserted with
        the same body, paying the per-insert lookup costs ONCE per batch
        where possible: one task-class resolution per distinct arg shape,
        a shared tile-handle cache, one ``schedule()`` call for every
        task that becomes ready during the batch, and one
        sliding-window check per batch tail (re-checked mid-batch so a
        batch larger than the window still throttles; any accumulated
        ready tasks are flushed to the scheduler BEFORE parking, or the
        drain the window waits for could never happen).

        Semantically identical to calling ``insert_task`` per row —
        program order, tile tracking, and the cross-rank replay sequence
        are unchanged. Returns one opaque handle per row: a ``Task``
        (Python engine) or an int seq (native engine), ``None`` for a
        remote shell.

        ``priorities`` (optional, one int per row) overrides
        ``priority`` per row — the KV state layer uses it to put a
        request's chunked-prefill rows on the wfq PREFILL lane
        (priority < 0, ``sched/fair.py``) while its decode rows keep
        the default lane, inside ONE batch (one admission check: a
        request's task graph is admitted all-or-nothing). Per-row
        priorities are a scheduling-lane hint consumed by the Python
        engine's schedulers; the native engine receives the scalar
        ``priority`` (lane-aware pools — wfq — never run native).
        ``stacked`` is :meth:`insert_task`'s, for every row."""
        if stacked is not None:
            stacked = self._stacked(stacked, pure)
        ctx = self.context
        if ctx is None or not ctx.stage_timers:
            return self._insert_tasks(fn, rows, priority, priorities,
                                      device, pure, stacked)
        # one span per CALL, however many rows it inserts
        with StageSpan(SPAN_INSERT) as span:
            out = self._insert_tasks(fn, rows, priority, priorities,
                                     device, pure, stacked)
        self.insert_s += span.seconds
        self.insert_calls += len(out)
        return out

    def _insert_tasks(self, fn, rows, priority, priorities, device, pure,
                      stacked=None):
        self._check_insertable()
        rows = list(rows)
        out: List[Optional[Task]] = []
        if not rows:
            return out
        if priorities is not None:
            priorities = list(priorities)
            if len(priorities) != len(rows):
                raise ValueError(
                    f"priorities ({len(priorities)}) must match rows "
                    f"({len(rows)})")
        if self.admission is not None:
            self.admission.admit(self, len(rows))
        eng = self._engine()
        if eng is not None:
            return eng.insert_rows(fn, rows, priority, device, pure)
        shape0 = self._shape_of(rows[0])
        tc0 = self._task_class_for(fn, shape0, device, pure=pure,
                                   stacked=stacked)
        ready: List[Task] = []
        tile_cache: Dict[Any, _Tile] = {}
        for i, args in enumerate(rows):
            if self.error is not None:
                # the pool failed mid-batch (poison body, peer death):
                # flush what is already ready, then surface the abort to
                # the inserter instead of feeding a dead pool
                if ready:
                    self.context.schedule(None, ready)
                self._check_insertable()
            shape = self._shape_of(args)
            tc = tc0 if shape == shape0 else \
                self._task_class_for(fn, shape, device, pure=pure,
                                     stacked=stacked)
            out.append(self._insert_one(
                tc, args,
                priorities[i] if priorities is not None else priority,
                ready, tile_cache))
            if len(ready) >= 512:
                # chunked flush: keep the workers fed while a long batch
                # is still inserting (one schedule() per chunk, not per
                # task)
                self.context.schedule(None, ready)
                ready = []
            if self._inflight >= self._window:   # lock-free pre-check
                if ready:
                    self.context.schedule(None, ready)
                    ready = []
                self._throttle()
        if ready:
            self.context.schedule(None, ready)
        return out

    # -- insertion internals ----------------------------------------------
    def _engine(self):
        """The native dynamic-task engine, or None (the Python path).
        Resolved ONCE at first insert — the observers the fallback rule
        checks are installed before work starts; a pool never switches
        engines mid-flight (the tile tracking marks differ). A raising
        resolution (forced runtime.native_dtd=1 without a toolchain) is
        deliberately NOT cached: every retried insert must keep raising
        rather than silently proceeding on the Python engine."""
        if self._native_checked:
            return self._native
        from . import dtd_native
        eng = dtd_native.engine_for(self)   # may raise (forced mode)
        self._native = eng
        self._native_checked = True
        return eng

    def _check_insertable(self) -> None:
        if self.error is not None:
            raise RuntimeError(
                f"taskpool {self.name} aborted: {self.error}") from self.error
        if self._closed:
            raise RuntimeError("taskpool already drained by wait()")
        if self.context is None:
            raise RuntimeError("add_taskpool(tp) before insert_task")
        if not self.context._started:
            # reference: the context must be started before DTD insertion
            # (insert_function.c checks the same and the sliding window
            # would deadlock otherwise)
            self.context.start()

    @staticmethod
    def _shape_of(args) -> Tuple:
        return tuple(
            ("tile", a.access) if isinstance(a, TileArg)
            else ("value", None) if isinstance(a, ValueArg)
            else ("scratch", None)
            for a in args)

    def _tile_of_cached(self, dc, key, cache) -> _Tile:
        if cache is None:
            return self.tiles.tile_of(dc, key)
        hkey = (dc.dc_id, tuple(key) if isinstance(key, (tuple, list))
                else key)
        t = cache.get(hkey)
        if t is None:
            t = cache[hkey] = self.tiles.tile_of(dc, key)
        return t

    def _throttle(self) -> None:
        """Sliding-window inserter throttle. The pre-check is lock-free
        (GIL-atomic int read) so an un-throttled insert never touches the
        condition variable here.

        Failure wakeup: an abort (poison body, peer death) sets
        ``_closed`` and notifies under this CV (``_on_terminated``), so
        a parked inserter is released EVENT-DRIVEN — and then raises the
        pool's error instead of silently resuming inserts into a dead
        pool. Waiter registration and the completer's notify share the
        CV lock, so no wakeup can be lost; the residual timeout is a
        belt-and-braces bound, not the exit mechanism."""
        if self._inflight < self._window:
            return
        t0 = time.perf_counter()
        with self._inflight_cv:
            if self._inflight < self._window:
                return
            self._throttle_waiters += 1
            try:
                while self._inflight > self._threshold and not self._closed:
                    self._inflight_cv.wait(timeout=0.25)
            finally:
                self._throttle_waiters -= 1
        if self.context.stage_timers:
            self._count(dtd_window_waits=1,
                        dtd_window_wait_s=time.perf_counter() - t0)
        if self.error is not None:
            raise RuntimeError(
                f"taskpool {self.name} aborted: {self.error}") from self.error

    def _count(self, **more) -> None:
        """Add to the front end's counters (stage timers on; the
        inserter's thread)."""
        c = self.counters
        for name, n in more.items():
            c[name] = c.get(name, 0) + n

    def _insert_one(self, tc: TaskClass, args, priority: int,
                    ready_out: Optional[List[Task]],
                    tile_cache: Optional[Dict]) -> Optional[Task]:
        """One insert under an already-resolved task class. With
        ``ready_out`` set (batch mode), tasks that become ready are
        appended there instead of being scheduled immediately."""
        seq = self._seq
        self._seq += 1
        my_rank = self.my_rank
        if self.nb_ranks > 1:
            target_rank = self._placement(args)
            if target_rank != my_rank:
                self._insert_shell(seq, target_rank, args, priority)
                if self.on_retire is not None:
                    # a shell never enters local flight: retire the
                    # admitted row now so the tenant window drains
                    self.on_retire(self)
                return None

        task = Task(self, tc, (seq,), priority=priority)
        task.dsl.update(argspec=[], out_tiles=[], succ=[], done=False,
                        lock=threading.Lock(), affinity=None, aliases={})

        # register before linking so a racing writer completion can route
        # activations to this task
        with self._seq_lock(seq & 63):
            self._goals[seq] = _GOAL_UNSET
            self._tasks_by_seq[seq] = task
        with self._inflight_cv:
            self._inflight += 1
        self.addto_nb_tasks(1)

        goal = 0
        flow_i = 0
        seen_tiles: Dict[Any, str] = {}   # tile → primary flow of THIS task
        # the INOUT flows the class gives that this task has to keep:
        # the incoming version has another reader, or is not here
        may_give = tc.dtd_given
        kept: set = set()
        for a in args:
            if isinstance(a, ValueArg):
                task.dsl["argspec"].append(("value", a.value))
                continue
            if isinstance(a, ScratchArg):
                task.dsl["argspec"].append(("scratch", (a.shape, a.dtype)))
                continue
            tile = self._tile_of_cached(a.collection, a.key, tile_cache)
            fname = f"f{flow_i}"
            flow_i += 1
            task.dsl["argspec"].append(("tile", None))
            if a.affinity:
                task.dsl["affinity"] = (a.collection, a.key)
            writes = a.access & FlowAccess.WRITE
            primary = seen_tiles.get(tile)
            if primary is not None:
                # same tile passed twice in one insert: alias the flow to
                # the first occurrence instead of linking the task as its
                # own predecessor (which would deadlock); resolved by
                # _data_lookup just before execution
                task.dsl["aliases"][fname] = primary
            else:
                seen_tiles[tile] = fname
                with tile.lock:
                    if not writes:
                        tile.readers += 1
                        if tile.flushed and tile.last_writer is None:
                            self.tiles.read_untracked(tile)
                    elif tile.readers and fname in may_give:
                        kept.add(fname)
                    writer = tile.last_writer
                    # capture the writer's flow ATOMICALLY with the
                    # writer: the completer clears both under this lock
                    # (retire, step 1) BEFORE publishing done (step 2),
                    # so re-reading it later could yield None for a
                    # writer whose done flag we still observe False —
                    # the successor would then receive a None value
                    writer_flow = tile.last_writer_flow
                    holder = tile.holder_rank
                if holder is None:
                    holder = a.collection.rank_of(a.key)
                if holder != my_rank and fname in may_give:
                    kept.add(fname)
                linked = False
                if isinstance(writer, Task):
                    with writer.dsl["lock"]:
                        if not writer.dsl["done"]:
                            ref = SuccessorRef(task_class=tc,
                                               locals=task.locals,
                                               flow_name=fname, value=None,
                                               priority=priority)
                            ref.src_flow = writer_flow
                            writer.dsl["succ"].append(ref)
                            goal += 1
                            linked = True
                elif isinstance(writer, _Shell):
                    # in-flight remote writer: its rank replays this insert
                    # and will deliver the value at completion
                    goal += 1
                    linked = True
                if not linked:
                    if holder == my_rank:
                        san = self.context.dfsan
                        if san is not None:
                            # sync read: the tile-lock + retire protocol
                            # orders this snapshot after the last commit
                            # (write_tile happens-before last_writer is
                            # cleared), so join the tile's write clock
                            # into this task instead of race-checking —
                            # also what keeps a LATER write by this task
                            # WAW-ordered after a retired writer that
                            # left no dep edge behind
                            san.observe_read(task, a.collection, a.key,
                                             sync=True)
                        # current version is local: snapshot the
                        # program-order value now (immutable arrays keep
                        # the snapshot valid); stage-through so one H2D
                        # serves every reader (Context.stage_read)
                        task.data[fname] = self.context.stage_read(
                            a.collection, a.key,
                            a.collection.data_of(a.key))
                    else:
                        # version held remotely: the holder replays this
                        # insert as a shell and pushes the value eagerly
                        goal += 1
            if writes:
                with tile.lock:
                    tile.last_writer = task
                    tile.last_writer_flow = fname
                    tile.holder_rank = my_rank
                    tile.readers = 0
                    if tile.flushed:
                        self.tiles.readopt(tile)
                task.dsl["out_tiles"].append((tile, fname))

        if may_give:
            for alias, primary in task.dsl["aliases"].items():
                kept.update((alias, primary))   # the tile twice in a task
            if kept:
                # not this class's to launch: the variant that gives
                # what this task may (the goal is unset, so nothing has
                # looked at the task's class yet)
                tc = task.task_class = self._task_class_for(
                    *tc.dtd_variant_of,
                    given=tuple(f for f in may_give if f not in kept))
        if self.context.stage_timers:
            self._count(dtd_args_linked=goal,
                        dtd_args_snapshot=len(seen_tiles) - goal,
                        dtd_args_given=len(tc.dtd_given),
                        dtd_args_kept=len(tc.dtd_inout) - len(tc.dtd_given))
        # Finalize the goal; racing activations may already have counted.
        # The lock must span both the goal publication AND the finalize
        # check: activate_dep reads the goal and counts under the same
        # lock, so an activation can never count against a stale
        # _GOAL_UNSET after we finalized (that interleaving left the
        # entry uncompletable forever — a lost-wakeup hang).
        with self._seq_lock(seq & 63):
            self._goals[seq] = goal
            ent = None if goal == 0 else self.pending.finalize(
                tc.make_key(task.locals), goal, DEPS_COUNTER)
        ready = None
        if goal == 0:
            ready = task
        elif ent is not None:
            task.data.update(ent["data"])
            task.priority = max(task.priority, ent["priority"])
            ready = task
        if ready is not None:
            if ready_out is not None:
                ready_out.append(ready)     # batch: one schedule() at flush
            else:
                self.context.schedule(None, [ready])
        return task

    def _insert_shell(self, seq: int, target_rank: int, args,
                      priority: int) -> None:
        """Replay a remotely-placed insert: update tile tracking and feed
        the remote task any version this rank holds (star topology — the
        reference restricts DTD collectives to star, remote_dep.c:543)."""
        my_rank = self.my_rank
        flow_i = 0
        seen: set = set()
        for a in args:
            if not isinstance(a, TileArg):
                continue
            tile = self.tiles.tile_of(a.collection, a.key)
            fname = f"f{flow_i}"
            flow_i += 1
            if tile in seen:
                continue
            seen.add(tile)
            with tile.lock:
                writer = tile.last_writer
                # atomic with the writer — see the local-insert path
                writer_flow = tile.last_writer_flow
                holder = tile.holder_rank
                if not a.access & FlowAccess.WRITE:
                    tile.readers += 1   # its version may be sent later
            if holder is None:
                holder = a.collection.rank_of(a.key)
            if a.access & FlowAccess.READ and not (a.access & FlowAccess.CTL):
                if isinstance(writer, Task):
                    # local in-flight writer feeds the remote task
                    sent = False
                    with writer.dsl["lock"]:
                        if not writer.dsl["done"]:
                            writer.dsl["succ"].append(
                                ("remote", target_rank, seq, fname,
                                 writer_flow, priority))
                            sent = True
                    if not sent and holder == my_rank:
                        self._send_value(target_rank, seq, fname,
                                         a.collection.data_of(a.key),
                                         priority)
                elif writer is None and holder == my_rank:
                    # quiescent version held here: eager push (PULLIN)
                    self._send_value(target_rank, seq, fname,
                                     a.collection.data_of(a.key), priority)
                # else: another rank holds/produces it — not our edge
            if a.access & FlowAccess.WRITE:
                with tile.lock:
                    tile.last_writer = _Shell(seq, target_rank)
                    tile.last_writer_flow = fname
                    tile.holder_rank = target_rank
                    tile.readers = 0

    def _send_value(self, target_rank: int, seq: int, fname: str,
                    value, priority: int = 0) -> None:
        """Ship one input value of remote task ``seq`` (eager activation)."""
        import types as _types
        ref = SuccessorRef(task_class=self._wire_tc, locals=(seq,),
                           flow_name=fname, value=value, dep_index=0,
                           priority=priority)
        # eager pushes have no producing task: the wire span parents to
        # the submission root (prof empty -> _span_attach falls back)
        shim = _types.SimpleNamespace(taskpool=self, prof={})
        self.context.comm.remote_dep_activate(shim, ref, target_rank)

    # ----------------------------------------------------- class callbacks
    def _iterate_successors(self, task: Task):
        ctx = self.context
        san = ctx.dfsan if ctx is not None else None
        # 1) write produced versions back and retire the writer slot, so
        #    late-inserted readers snapshot the new value
        for tile, fname in task.dsl["out_tiles"]:
            if fname in task.output:
                if san is not None:
                    # stamp BEFORE the commit and the retire: an insert
                    # that observes last_writer cleared is guaranteed to
                    # find this write already clocked (sync-read join)
                    san.observe_write(task, tile.collection, tile.key)
                tile.collection.write_tile(tile.key, task.output[fname])
            with tile.lock:
                if tile.last_writer is task:
                    tile.last_writer = None
                    tile.last_writer_flow = None
                    if tile.flushed:
                        self.tiles.retire(tile)
        # 2) only then mark done and deliver the linked successors
        with task.dsl["lock"]:
            task.dsl["done"] = True
            succ = list(task.dsl["succ"])
            task.dsl["succ"].clear()
        refs: List[SuccessorRef] = []
        # remote shell deliveries grouped per (rank, produced value):
        # one packed activation per rank carries the payload ONCE even
        # when several shells on that rank read it (star fan-out from
        # the producer — the DTD collective pin, remote_dep.c:543-551)
        rsends: Dict[tuple, List[SuccessorRef]] = {}
        for ref in succ:
            if isinstance(ref, tuple):      # remote shell successor
                _, rank, seq, dst_fname, src_flow, prio = ref
                value = task.output.get(src_flow, task.data.get(src_flow)) \
                    if src_flow is not None else None
                rsends.setdefault((rank, id(value)), []).append(
                    SuccessorRef(task_class=self._wire_tc, locals=(seq,),
                                 flow_name=dst_fname, value=value,
                                 dep_index=0, priority=prio))
                continue
            src_flow = getattr(ref, "src_flow", None)
            if src_flow is not None and src_flow in task.output:
                ref.value = task.output[src_flow]
            elif src_flow is not None:
                ref.value = task.data.get(src_flow)
            refs.append(ref)
        if rsends:
            import types as _types
            # prof rides along so the wire hop's span is parented to
            # THIS completing task (profiling/spans.py)
            shim = _types.SimpleNamespace(taskpool=self, prof=task.prof)
            for (rank, _vid), wire_refs in rsends.items():
                self.context.comm.remote_dep_activate_multi(
                    shim, rank, wire_refs)
        seq = task.locals[0]
        with self._seq_lock(seq & 63):
            self._goals.pop(seq, None)
            self._tasks_by_seq.pop(seq, None)
        with self._inflight_cv:
            self._inflight -= 1
            # notify only when an inserter is actually parked in the
            # window throttle (or the pool is draining) — notify_all per
            # completion is pure overhead on the release hot path; the
            # waiter registers under this CV before waiting, so the
            # conditional notify cannot lose a wakeup
            if self._throttle_waiters or self._closed:
                self._inflight_cv.notify_all()
        if self.on_retire is not None:
            self.on_retire(self)
        return refs

    # -------------------------------------------------------------- drain
    def activate_dep(self, ref: SuccessorRef) -> Optional[Task]:
        """DTD successors already exist at activation time — count down on
        the pre-built task instead of constructing a new one. Activations
        for a not-yet-inserted task (remote values racing the replay)
        accumulate in the pending table against the _GOAL_UNSET sentinel
        until insert_task finalizes the goal — the parked-undiscovered-task
        protocol (remote_dep_mpi.c:1935-1961)."""
        seq = ref.locals[0]
        with self._seq_lock(seq & 63):
            return self._activate_one_locked(ref)

    def _activate_one_locked(self, ref: SuccessorRef) -> Optional[Task]:
        """One dep activation; the caller holds ``ref``'s seq-stripe
        lock. The single copy shared by the scalar and batched paths:
        goal read + count must be one critical section against
        insert_task's goal publication + finalize (see there)."""
        seq = ref.locals[0]
        goal = self._goals.get(seq, _GOAL_UNSET)
        if goal == _GOAL_UNSET:
            # activation raced ahead of local discovery — the
            # parked-undiscovered-task path (stress tests assert
            # this actually fires at 4 ranks)
            self.parked_activations += 1
        task = self._tasks_by_seq.get(seq)
        ent = self.pending.update(("dtd", seq),
                                  ref.flow_name, ref.value, ref.dep_index,
                                  goal, DEPS_COUNTER, ref.priority)
        if ent is None:
            return None
        if task is None:
            raise RuntimeError(f"DTD successor seq={seq} vanished")
        task.data.update(ent["data"])
        task.priority = max(task.priority, ent["priority"])
        return task

    def activate_deps(self, refs) -> List[Task]:
        """Batched :meth:`activate_dep`: group a
        completed task's successor refs by seq-lock stripe so each stripe
        is locked once per completion instead of once per dep. The
        per-seq critical section is `_activate_one_locked`, shared with
        the scalar path — only lock acquisitions are coalesced."""
        if len(refs) == 1:
            task = self.activate_dep(refs[0])
            return [task] if task is not None else []
        by_stripe: Dict[int, List] = {}
        for ref in refs:
            by_stripe.setdefault(ref.locals[0] & 63, []).append(ref)
        out: List[Task] = []
        for stripe, group in by_stripe.items():
            with self._seq_lock(stripe):
                for ref in group:
                    task = self._activate_one_locked(ref)
                    if task is not None:
                        out.append(task)
        return out

    def wait(self, context=None, timeout: Optional[float] = None) -> bool:
        """parsec_dtd_taskpool_wait analog: drain all inserted tasks.
        Idempotent — only the first call releases the enqueue-time runtime
        action; later calls just join. False where ``timeout`` seconds
        passed first."""
        with self._inflight_cv:
            first = not self._closed
            self._closed = True
            self._inflight_cv.notify_all()
        if self._native is not None:
            # native pools never tick nb_tasks (per-task monitor traffic
            # is exactly the overhead the engine removes): drain the
            # engine's inflight count FIRST, so releasing the enqueue
            # action below is what fires termdet
            self._native.drain()
        if first and self._enqueue_counted:
            self.addto_runtime_actions(-1)
        return self.wait_completed(timeout)

    def flush_tile(self, collection: DataCollection, key) -> None:
        """parsec_dtd_data_flush analog, as upstream inserts it: the
        program is done with this tile. Returns without waiting. The
        tile's tracking ends once nothing inserted so far writes it
        (``_TileBank``): at once, or when its last writer retires, which
        has written the tile's last version to ``collection`` by then.
        An insert on the tile after that reads the collection's current
        version and tracks the tile anew; a writer inserted after the
        flush cancels what is left of it. A tile never inserted is not
        tracked and stays so. Across ranks the tracking is the replay's
        state (``holder_rank``) and nothing is taken out: the collective
        :meth:`flush` is what sends versions home there."""
        self._flushing(self._end_tracking, collection, key)

    def flush_all(self, collection: Optional[DataCollection] = None
                  ) -> None:
        """parsec_dtd_data_flush_all analog: :meth:`flush_tile` of every
        tile tracked now (of ``collection``, if given). Returns without
        waiting; ``wait()`` is what drains the pool."""
        self._flushing(self._end_tracking, collection)

    def _flushing(self, flush: Callable, *args) -> None:
        """One flush call, under its span where the stage timers are
        on: what the inserter's thread pays for it."""
        ctx = self.context
        if ctx is not None and ctx.stage_timers:
            with StageSpan(SPAN_DTD_FLUSH):
                flush(*args)
        else:
            flush(*args)

    def _end_tracking(self, collection, key=None) -> None:
        if self.nb_ranks > 1:
            return
        if key is not None:
            tiles = [self.tiles.get(collection, key)]
        else:
            tiles = [t for t in self.tiles.all()
                     if collection is None or t.collection is collection]
        for tile in tiles:
            if tile is not None:
                self.tiles.flush(tile)

    def flush(self, collection: Optional[DataCollection] = None,
              timeout: float = 60.0) -> None:
        """The blocking flush: wait until no in-flight LOCAL writer
        remains for the collection's tiles (produced versions are
        written back at completion, so afterwards ``data_of`` is
        current). It polls every tracked tile a millisecond apart, so it
        is for a program that has to READ the collection in the middle
        of a pool that stays open (the examples, the tests, a
        distributed pool's hand-back), once, not once a tile: inside an
        insertion loop it would make the inserter wait for the
        execution it is there to run ahead of, and :meth:`flush_tile` /
        :meth:`flush_all` are the forms for that. It ends no tile's
        tracking. In distributed mode this is a COLLECTIVE: after the
        local quiesce, each rank pushes the tiles it holds back to their
        owners, waits for the owners' acks, and barriers."""
        self._flushing(self._flush_wait, collection, timeout)

    def _flush_wait(self, collection, timeout: float) -> None:
        from .dtd_native import _NativeWriter
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.error is not None:
                # a task body failed — its tile writes can never quiesce;
                # surface the abort instead of spinning to the timeout
                raise RuntimeError(
                    f"taskpool {self.name} aborted: {self.error}") \
                    from self.error
            busy = False
            for tile in self.tiles.all():
                if collection is not None and tile.collection is not collection:
                    continue
                with tile.lock:
                    if isinstance(tile.last_writer, (Task, _NativeWriter)):
                        busy = True
                        break
            if not busy:
                break
            time.sleep(0.001)
        else:
            raise TimeoutError("DTD flush timed out")
        if self.nb_ranks > 1:
            self._flush_distributed(collection, timeout)

    def _flush_distributed(self, collection, timeout: float) -> None:
        from ..comm.engine import AMTag
        comm = self.context.comm
        my_rank = self.my_rank
        sent = 0
        for tile in self.tiles.all():
            if collection is not None and tile.collection is not collection:
                continue
            owner = tile.collection.rank_of(tile.key)
            with tile.lock:
                holder = tile.holder_rank
            if holder == my_rank and owner != my_rank:
                # writeback to the owner (parsec_dtd_data_flush); device
                # values snapshot to host HERE (worker thread) so the
                # comm thread never pays a D2H sync mid-progress
                value = tile.collection.data_of(tile.key)
                to_wire = getattr(comm, "wire_value", None)
                if to_wire is not None:
                    value = to_wire(value)
                comm.send_am(
                    AMTag.DTD_CONTROL, owner,
                    {"taskpool": self.name, "op": "flush",
                     "dc_id": tile.collection.dc_id, "key": tile.key,
                     "value": value,
                     "src": my_rank})
                sent += 1
        with self._flush_cv:
            deadline = time.monotonic() + timeout
            while self._flush_acks < sent:
                left = deadline - time.monotonic()
                if left <= 0:
                    raise TimeoutError("DTD distributed flush: acks missing")
                self._flush_cv.wait(timeout=min(0.05, left))
            self._flush_acks -= sent
        comm.sync()
        if self.completed and self.error is None:
            # past the barrier every rank has its acks: what this flush
            # sent home is at home everywhere, and a pool that has ended
            # has no more use for its tracking (``_let_go``)
            self.tiles.drop(collection)

    def _on_dtd_control(self, src: int, msg: Dict) -> None:
        """Handle DTD control AMs (flush writebacks + acks); invoked by
        the comm engine's DTD_CONTROL dispatcher."""
        from ..comm.engine import AMTag
        if msg["op"] == "flush":
            dc = next((t.collection for t in self.tiles.all()
                       if t.collection.dc_id == msg["dc_id"]), None)
            if dc is not None:
                dc.write_tile(msg["key"], msg["value"])
                tile = self.tiles.tile_of(dc, msg["key"])
                with tile.lock:
                    tile.holder_rank = self.my_rank
            self.context.comm.send_am(
                AMTag.DTD_CONTROL, src,
                {"taskpool": self.name, "op": "flush_ack"})
        elif msg["op"] == "flush_ack":
            with self._flush_cv:
                self._flush_acks += 1
                self._flush_cv.notify_all()
