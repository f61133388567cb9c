"""Native DTD engine: the insert→release hot loop behind the C ABI.

PaRSEC's entire dynamic-task core is native C — insertion
(insert_function.c), the dependency hash table (parsec.c:1503-1649), the
scheduler queues (mca/sched/*) and the worker progress loop
(scheduling.c:537-676) — precisely so per-task overhead stays in the
microseconds. This module is the TPU build's equivalent: it drives the
``pdtd_*`` engine in ``_native/core.cpp`` so that insert, dependency
countdown, select, steal, and release all run in C++ with the GIL
released, and Python is entered only to run task bodies. A body
registered with :func:`register_native_body` (a no-op) lets null tasks
complete entirely inside the native pump — the shape of the classic
tasks/s scheduling microbenchmark.

Engine selection (``runtime.native_dtd``, resolved once per taskpool at
first insert):

- ``auto`` (default): native when the library builds AND the pool is
  eligible; silently the Python path otherwise.
- ``1``: same eligibility rules, but an unavailable toolchain is a hard
  error instead of a silent fallback.
- ``0``: always the Python path.

Eligibility — the **instrumented-fallback rule** (ISSUE 13 moved the
line; ISSUE 14 moved it again for dfsan: observation must never change
which engine runs, PaRSEC's PINS/profiling contract): a pool stays on
the (instrumented) Python engine only when one of these holds, with
the reason stated per row:

- distributed (``nb_ranks > 1``) — replay/shell semantics are Python;
- a **semantically-intrusive** observer with no native source is
  live: the Grapher (records every dep edge as it is released), the
  debug-history EXE ring (expects an EXE mark per task), or a
  per-task PINS sampler with no native equivalent (alperf — per-task
  rusage deltas; counters — per-task counter snapshots;
  iterators_checker — walks each task's iterator state; and the
  straggler watchdog when no live Trace feeds it ring records);
- the context scheduler does not opt in (``native_dtd_capable`` — the
  lfq/ll/ltq/lhq/gd families do; ``wfq`` keeps Python pools so its
  weighted-fair arbitration and ``pool_stats`` observe every task, and
  the PRIORITY-policy modules — llp, pbq, ap, ip, spq — likewise,
  since the native LIFO/steal queues would discard their ordering key);
- a non-CPU device is registered (bodies would route through device
  managers the native pump bypasses).

What does NOT disqualify anymore: a live :class:`~parsec_tpu.
profiling.trace.Trace` (the engine records begin/end/queue-wait spans
into its own per-worker binary event rings — ``pdtd_obs_*`` — which
the trace expands byte-compatibly at dump/scrape time), the always-on
metrics registry, ``runtime.stage_timers`` (stage totals read from the
engine's C++ atomics at scrape), scrape-only PINS modules (``tenant``
— native completions folded per tenant at scrape — and ``overhead``),
and — since ISSUE 14 — the **dfsan race sanitizer** for local DTD
pools: the engine captures insert-time access manifests (tile keys +
modes + linked-pred edges, resolved while the inserter already holds
the tile locks) and enables the event rings, and dfsan replays the
pool at FOLD time over the frozen ring snapshots + manifests
(``DataflowSanitizer.replay_native_pool``) — same happens-before
model, same race reports, bitwise-identical per-tile version digests,
at ring-record cost per task instead of a Python hot loop. The C
lock-discipline recorder (``pdtd_lockdbg_enable``, scraped through
``pdtd_stats``) feeds dfsan's lock-order inversion detector at the
same fold. The ring capacity knob is
``profiling.native_ring_events``.

Serving hooks do NOT force a fallback: ``Taskpool.admission`` runs on
the inserting thread as usual, and a pool with ``on_retire`` simply
marks every task Python-bodied so the tenant window drains exactly once
per completion. ``Taskpool.cancel`` is honored at select time inside
the native pump.

Program-order semantics are preserved exactly (the functional-WAR
guarantee of dsl/dtd.py): the two-phase insert (``pdtd_insert`` links
against in-flight writers, ``pdtd_arm`` makes the batch runnable) lets
the inserter snapshot the committed tile version whenever the linked
writer turns out to have already completed — at that instant no other
writer of the tile can be in flight, because all later writers are in
the still-unarmed batch.
"""

from __future__ import annotations

import ctypes
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from .. import _native
from ..core.task import FlowAccess
from ..utils import mca_param
from ..utils.debug import warning

mca_param.register(
    "runtime.native_dtd", "auto",
    help="run single-rank DTD pools on the native C++ engine: auto "
         "(when the library builds and no per-task observer is live) "
         "| 1 (error if the toolchain is missing) | 0 (Python path)")

# staging-ring row capacity: one pdtd_insert call per ring fill; the
# native side reports the high-water mark as ring_highwater
_RING = 1024
_MAX_PREDS_INIT = 64
# Python-bodied tasks fetched per pump call: one GIL round-trip (and
# one batched completion) per _PUMP_BATCH bodies instead of two ctypes
# calls per task — at 4 workers the per-task calls convoyed on the GIL
_PUMP_BATCH = 32

# fns registered as native no-op bodies: zero-arg, returns None — tasks
# inserted with one of these (and no per-task retire hook) complete
# entirely inside the native pump, never re-entering Python
_NATIVE_BODIES: set = set()


def register_native_body(fn: Callable) -> Callable:
    """Declare ``fn`` a no-op body (zero arguments, returns ``None``):
    tasks inserted with it skip Python entirely on the native engine.
    Returns ``fn`` so it can be used as a decorator."""
    _NATIVE_BODIES.add(fn)
    return fn


def is_native_body(fn: Callable) -> bool:
    return fn in _NATIVE_BODIES


class _NativeWriter:
    """In-flight-writer marker parked in ``_Tile.last_writer`` by the
    native engine (the Python engine parks the Task object there).
    ``dtd.Taskpool.flush`` treats it as busy like a Task."""

    __slots__ = ("seq",)

    def __init__(self, seq: int):
        self.seq = seq


class _Shim:
    """Just enough of a Task for the DTD chore hooks, which only read
    ``task.dsl['argspec']`` (both the eager and the pure/jit hook)."""

    __slots__ = ("dsl",)

    def __init__(self, argspec):
        self.dsl = {"argspec": argspec}


def resolve_mode() -> str:
    """'off' | 'auto' | 'force' from the runtime.native_dtd MCA param."""
    v = str(mca_param.get("runtime.native_dtd", "auto")).lower()
    if v in ("0", "off", "false", "no"):
        return "off"
    if v in ("1", "on", "true", "force", "yes"):
        return "force"
    return "auto"


def engine_for(tp) -> Optional["NativeDTD"]:
    """Build the native engine for ``tp`` if it is eligible (see module
    docstring), else None. Raises when ``runtime.native_dtd=1`` is
    forced but the library cannot be built/loaded — a silent fallback
    would misreport every rate the caller measures."""
    mode = resolve_mode()
    if mode == "off":
        return None
    lib = _native.load()
    if lib is None:
        if mode == "force":
            raise RuntimeError(
                "runtime.native_dtd=1 but the native core is "
                f"unavailable: {_native.build_error()} — install g++ "
                "or set runtime.native_dtd=0/auto")
        return None
    ctx = tp.context
    if ctx is None or tp.nb_ranks > 1:
        return None
    # instrumented-fallback rule (the ISSUE 13 line, ISSUE 14 moved
    # dfsan off it): only SEMANTICALLY-INTRUSIVE observers with no
    # native source keep the pool on the Python path. A live Trace
    # records through the engine's own event rings, the metrics
    # registry and stage timers read C++ atomics at scrape, scrape-only
    # PINS callbacks are registered native_ok, and dfsan replays the
    # pool from ring snapshots + insert manifests at fold — see the
    # module docstring for the exact residual list.
    if ctx.grapher is not None:
        return None
    if ctx.pins.needs_python_engine(trace_live=ctx.trace is not None):
        return None
    from ..utils import debug_history
    if debug_history.enabled():     # EXE-mark ring expects every task
        return None
    if not getattr(ctx.scheduler, "native_dtd_capable", False):
        return None
    # a REAL accelerator module registered: bodies must route through
    # the device managers (async dispatch, batching, per-device load)
    # — the native pump runs them inline on the worker thread, which is
    # only equivalent when every device executes on the host anyway
    # (virtual CPU-platform modules). Tests that pin the device-manager
    # plane itself (load splitting across modules) set
    # runtime.native_dtd=0 explicitly.
    if any(getattr(d, "platform", "cpu") != "cpu"
           for d in ctx.devices.devices):
        return None
    return NativeDTD(tp, lib)


class NativeDTD:
    """Per-taskpool driver of the native ``pdtd_*`` engine."""

    def __init__(self, tp, lib):
        # the pool holds its engine (``tp._native``, which says which
        # engine ran long after the end); the engine holds the pool
        # while the workers pump it and lets go once it is folded
        # (``release_refs``), so a finished pool and its engine are no
        # cycle. ``tp`` reads through the weak half: whoever reaches a
        # folded engine came through its pool
        self._tp_held = tp
        self._tp_ref = weakref.ref(tp)
        self.lib = lib
        ctx = tp.context
        self.nworkers = ctx.nb_cores
        # per-worker plifo capacity sized to the inserter window (ready
        # tasks are bounded by inflight <= window; 2x slack across the
        # round-robin spread) — a fixed large capacity was pure per-pool
        # allocation churn on the serving admission path. Overspill goes
        # to the engine's locked overflow dequeue.
        qcap = max(1024, 2 * tp._window // max(1, self.nworkers))
        self._e = lib.pdtd_new(self.nworkers, qcap)
        if not self._e:
            raise MemoryError("pdtd_new failed")
        # per-python-task state, keyed by seq: (hook, out_flow_names,
        # argspec, resolvers, out_tiles, n_lpreds)
        self.rows: Dict[int, tuple] = {}
        # retained outputs of completed writers, keyed by seq — dropped
        # by the native refcount (pdtd_complete's drop list)
        self.outputs: Dict[int, Dict[str, Any]] = {}
        # staging ring: reusable arrays, one native call per fill
        self._prio = np.zeros(_RING, np.int32)
        self._flags = np.zeros(_RING, np.uint8)
        self._npreds = np.zeros(_RING, np.uint32)
        self._preds = np.zeros(_RING * 4, np.uint32)
        self._linked = np.zeros(_RING * 4, np.uint8)
        # per-worker pump/complete scratch (workers never share a slot)
        self._tidbuf = [(ctypes.c_uint32 * _PUMP_BATCH)()
                        for _ in range(self.nworkers)]
        self._ranbuf = [ctypes.c_int() for _ in range(self.nworkers)]
        self._batchbuf = [(ctypes.c_uint32 * _PUMP_BATCH)()
                          for _ in range(self.nworkers)]
        # per-task body begin/end stamps for the event rings (obs only)
        self._t01buf = [(ctypes.c_uint64 * (2 * _PUMP_BATCH))()
                        for _ in range(self.nworkers)]
        self._infobuf = [(ctypes.c_int32 * 2)()
                         for _ in range(self.nworkers)]
        self._dropbuf = [(ctypes.c_uint32 * _MAX_PREDS_INIT)()
                         for _ in range(self.nworkers)]
        # class-info cache: (fn, shape, device, pure) -> (hook,
        # out_flow_names); resolution goes through the taskpool's
        # task-class cache so pure=True bodies share the process-wide
        # jit cache with the Python engine
        self._class_info: Dict[Any, tuple] = {}
        self._lock = threading.Lock()       # insert-side ring guard
        self._unarmed = None    # (first, n) between pdtd_insert and arm
        self._cancelled = False
        # set when the pool terminated with tasks still in flight (an
        # abort): the workers keep pumping this engine and fold it into
        # the context totals once the last task drains
        self.retiring = False
        # in-engine observability plane (ISSUE 13): when a live Trace
        # is installed — or the dfsan sanitizer needs the rings as its
        # completion evidence (ISSUE 14) — enable the per-worker binary
        # event rings so the pool KEEPS the native engine under
        # observation — records carry seq/class/worker/t0/t1/queue-wait/
        # span and are expanded to the PR 9 event shape at scrape time
        # by the trace's NativeRingAdapter. class_names is the
        # insert-side id→name table the expansion (and the dfsan
        # replay's task labels) reads; the rid rides at the pool level
        # (tp.trace_rid — the serving Submission's deterministic id).
        self.class_names: List[str] = []
        self._cls_by_fn: Dict[Any, int] = {}
        self._obs = False
        self._obs_adapter = None
        self._obs_cap = 0
        self.obs_offset_s = 0.0
        # ring-fed dfsan (ISSUE 14): insert-time access manifests +
        # fold-time replay keep the race sanitizer live on the native
        # engine — see replay_native_pool in analysis/dfsan.py
        self._dfsan = getattr(ctx, "dfsan", None)
        if self._dfsan is not None:
            self._dfsan_manifest: Optional[Dict[int, tuple]] = {}
            self._dfsan_commits: Dict[int, tuple] = {}
            self._dfsan_violations: List[tuple] = []
            if hasattr(lib, "pdtd_lockdbg_enable"):
                # C lock-discipline recorder: acquisition pairs scraped
                # via pdtd_stats feed dfsan's inversion detector at fold
                lib.pdtd_lockdbg_enable(self._e)
        else:
            self._dfsan_manifest = None
        tr = ctx.trace
        if (tr is not None or self._dfsan is not None) and \
                hasattr(lib, "pdtd_obs_enable"):
            from ..profiling import spans as spans_mod
            cap = max(64, int(mca_param.get(
                "profiling.native_ring_events", 16384)))
            if lib.pdtd_obs_enable(
                    self._e, spans_mod.native_span_base(ctx.my_rank),
                    cap) == 0:
                # clock handshake: exact offset from the engine's
                # monotonic-ns domain to time.perf_counter (no
                # assumption that the two share an epoch)
                self.obs_offset_s = (time.perf_counter() -
                                     lib.pdtd_obs_now() / 1e9)
                self._obs = True
                self._obs_cap = cap
                if tr is not None:
                    from ..profiling.trace import NativeRingAdapter
                    self._obs_adapter = NativeRingAdapter(self)
                    tr.add_native_source(self._obs_adapter)
        ctx._ndtd_register(self)

    # -------------------------------------------------------------- insert
    def _cls_id(self, fn) -> int:
        """Insert-side class id for the event rings: the expansion maps
        it back to the task-class name (``fn.__name__`` — the same name
        the Python engine's task class carries, so span trees match
        across engines). One dict hit per insert chunk."""
        cid = self._cls_by_fn.get(fn)
        if cid is None:
            cid = len(self.class_names)
            self.class_names.append(getattr(fn, "__name__", "dtd_task"))
            self._cls_by_fn[fn] = cid
        return cid

    def _class_for(self, fn, shape, device, pure):
        key = (fn, shape, device, pure)
        info = self._class_info.get(key)
        if info is None:
            # given=(): this engine counts no readers, so its tasks
            # give no tile to a program
            tc = self.tp._task_class_for(fn, shape, device, pure=pure,
                                         given=())
            hook = tc.incarnations[0].hook if tc.incarnations else None
            # flow-access layout captured ONCE PER CLASS (ISSUE 14):
            # the dfsan replay's dynamic access-mode check reads it to
            # flag bodies that returned values for READ/CTL flows
            info = (hook, tuple(f.name for f in tc.output_flows),
                    tc.name,
                    {f.name: (int(f.access), bool(f.is_ctl))
                     for f in tc.flows})
            self._class_info[key] = info
        return info

    def insert_rows(self, fn, rows, priority, device, pure) -> List[int]:
        """Batched insert through the native engine; returns the task
        sequence numbers (the opaque per-task handles — native tasks
        have no Python Task object)."""
        out: List[int] = []
        n = len(rows)
        for start in range(0, n, _RING):
            out.extend(self._insert_chunk(
                fn, rows[start:start + _RING], priority, device, pure))
            self._throttle()
        return out

    def _insert_chunk(self, fn, rows, priority, device, pure) -> List[int]:
        with self._lock:
            try:
                return self._insert_chunk_locked(fn, rows, priority,
                                                 device, pure)
            except BaseException as exc:
                # a raise mid-chunk (stage_read failure, bad argspec)
                # leaves registered-but-unarmed tasks and/or a bumped
                # tp._seq behind — unrecoverable for this pool. Abort it
                # so wait()-ers get the error instead of hanging, and
                # arm whatever the engine registered so the cancelled
                # tasks drain through the drop path.
                pending = self._unarmed
                if pending is not None:
                    self._unarmed = None
                    self.lib.pdtd_arm(self._e, pending[0], pending[1])
                self.tp.abort(exc)
                raise

    def _insert_chunk_locked(self, fn, rows, priority, device,
                             pure) -> List[int]:
        from .dtd import ScratchArg, ValueArg
        tp = self.tp
        ctx = tp.context
        lib = self.lib
        native_ok = (fn in _NATIVE_BODIES and tp.on_retire is None)
        n = len(rows)
        tile_cache: Dict[Any, Any] = {}
        prio_a, flags_a, npreds_a = self._prio, self._flags, self._npreds
        preds_a, linked_a = self._preds, self._linked
        seqs: List[int] = []
        # pending[(row_i)] = per-row python-side record
        pend: List[Optional[tuple]] = []
        # dfsan access manifests (ISSUE 14), one list per tile-bearing
        # row: ("sync", dc, key) — program-order snapshot read (the
        # tile-lock/retire protocol orders it; replayed as a sync
        # join); ("link", dc, key, slot, pred_seq) — resolved against
        # linked_out in pass 2 to an HB edge or a sync read; ("write",
        # dc, key, fname) — committed-or-not decided at completion.
        # Entry order mirrors the Python engine's observation order
        # exactly (reads at insert, writes at commit, arg order).
        cap = self._dfsan_manifest is not None
        mans: List[Optional[list]] = []
        pi = 0
        max_lp = 0
        for args in rows:
            seq = tp._seq
            tp._seq += 1
            seqs.append(seq)
            i = len(pend)
            spec: List[tuple] = []
            resolvers: List[tuple] = []
            out_tiles: List[tuple] = []
            man: Optional[list] = [] if cap else None
            seen: Dict[Any, int] = {}       # tile -> primary flow idx
            flow_i = 0
            row_np = 0
            for a in args:
                if isinstance(a, ValueArg):
                    spec.append(("value", a.value))
                    continue
                if isinstance(a, ScratchArg):
                    spec.append(("scratch", (a.shape, a.dtype)))
                    continue
                tile = tp._tile_of_cached(a.collection, a.key,
                                          tile_cache)
                fname = f"f{flow_i}"
                idx = flow_i
                flow_i += 1
                spec.append(("tile", None))
                primary = seen.get(tile)
                if primary is not None:
                    # same tile twice in one insert: alias to the
                    # first occurrence (no self-link)
                    resolvers.append((2, primary))
                else:
                    seen[tile] = idx
                    with tile.lock:
                        writer = tile.last_writer
                        writer_flow = tile.last_writer_flow
                    if isinstance(writer, _NativeWriter):
                        if pi >= len(preds_a):
                            preds_a = self._grow_preds(pi + n)
                            linked_a = self._linked
                        preds_a[pi] = writer.seq
                        # snap-vs-link decided by pdtd_insert's
                        # linked_out (slot pi) in pass 2
                        resolvers.append(
                            (1, writer.seq, writer_flow, tile, pi))
                        if cap:
                            man.append(("link", a.collection, a.key,
                                        pi, writer.seq))
                        pi += 1
                        row_np += 1
                    else:
                        # no writer in flight: snapshot the current
                        # version NOW (program order; stage-through
                        # like the Python engine)
                        resolvers.append((0, ctx.stage_read(
                            a.collection, a.key,
                            a.collection.data_of(a.key))))
                        if cap:
                            man.append(("sync", a.collection, a.key))
                if a.access & FlowAccess.WRITE:
                    with tile.lock:
                        tile.last_writer = _NativeWriter(seq)
                        tile.last_writer_flow = fname
                        if tile.flushed:
                            tp.tiles.readopt(tile)
                    out_tiles.append((tile, fname, idx))
                    if cap:
                        man.append(("write", a.collection, a.key,
                                    fname))
            needs_python = not (native_ok and not spec)
            flags_a[i] = 1 if needs_python else 0
            prio_a[i] = priority
            npreds_a[i] = row_np
            max_lp = max(max_lp, row_np)
            pend.append((spec, resolvers, out_tiles)
                        if needs_python else None)
            if cap:
                mans.append(man if man else None)
        if max_lp > _MAX_PREDS_INIT and \
                max_lp > len(self._dropbuf[0]):
            self._dropbuf = [(ctypes.c_uint32 * (2 * max_lp))()
                             for _ in range(self.nworkers)]
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        i32p = ctypes.POINTER(ctypes.c_int32)
        cid = self._cls_id(fn)
        first = lib.pdtd_insert(
            self._e, n, prio_a.ctypes.data_as(i32p),
            flags_a.ctypes.data_as(u8p),
            npreds_a.ctypes.data_as(u32p),
            preds_a.ctypes.data_as(u32p),
            linked_a.ctypes.data_as(u8p),
            cid)
        if first < 0:
            raise RuntimeError(
                f"pdtd_insert failed (rc={first}): task table "
                "exhausted or inconsistent predecessor ids")
        # registered but not yet runnable: _insert_chunk's except path
        # arms this range so an abort still drains the engine
        self._unarmed = (int(first), n)
        if first != seqs[0]:
            raise RuntimeError(
                f"native DTD id drift: table at {first}, pool seq "
                f"at {seqs[0]} — mixed-engine insertion?")
        # pass 2: resolve snap-vs-link from linked_out, attach the
        # python-side rows, THEN arm the batch (a task must not be
        # runnable before its resolvers exist)
        hook_info = None
        for i, rec in enumerate(pend):
            if rec is None:
                continue
            spec, resolvers, out_tiles = rec
            n_lp = 0
            for j, r in enumerate(resolvers):
                if r[0] != 1:
                    continue
                if linked_a[r[4]]:
                    resolvers[j] = (1, r[1], r[2])
                    n_lp += 1
                else:
                    # writer already completed and committed: the
                    # collection holds exactly its version (every
                    # later writer is in this still-unarmed batch)
                    tile = r[3]
                    resolvers[j] = (0, ctx.stage_read(
                        tile.collection, tile.key,
                        tile.collection.data_of(tile.key)))
            if hook_info is None:
                hook_info = {}
            shape = tp._shape_of(rows[i])
            info = hook_info.get(shape)
            if info is None:
                info = hook_info[shape] = self._class_for(
                    fn, shape, device, pure)
            self.rows[seqs[i]] = (info, tuple(spec), resolvers,
                                  out_tiles, n_lp)
        if cap:
            # resolve the manifests' snap-vs-link against linked_out
            # (same rule as the resolvers above) and freeze them for
            # the fold-time dfsan replay
            manifest = self._dfsan_manifest
            for i, man in enumerate(mans):
                if man is None:
                    continue
                for j, m in enumerate(man):
                    if m[0] == "link":
                        man[j] = ("edge", m[4]) if linked_a[m[3]] \
                            else ("sync", m[1], m[2])
                manifest[seqs[i]] = (cid, tuple(man))
        self._unarmed = None
        lib.pdtd_arm(self._e, first, n)
        evt = ctx._work_evt
        if not evt.is_set():
            evt.set()
        return seqs

    def _grow_preds(self, need: int) -> np.ndarray:
        cap = max(2 * len(self._preds), need)
        self._preds = np.resize(self._preds, cap)
        self._linked = np.zeros(cap, np.uint8)
        return self._preds

    def _throttle(self) -> None:
        """Sliding-window inserter park off the GIL (the pdtd cv): the
        same window/threshold contract as the Python engine, released
        event-driven on drain and on abort/cancel."""
        tp = self.tp
        lib = self.lib
        if lib.pdtd_inflight(self._e) < tp._window:
            return
        while not tp._closed and tp.error is None:
            left = lib.pdtd_wait_below(self._e, tp._threshold, 250)
            if left <= tp._threshold or self._cancelled:
                break
        if tp.error is not None:
            raise RuntimeError(
                f"taskpool {tp.name} aborted: {tp.error}") from tp.error

    # ---------------------------------------------------------------- pump
    def pump(self, es) -> bool:
        """Worker-side progress: drain native-bodied ready tasks inside
        the C ABI call (GIL released), run Python-bodied ones here in
        batches of up to _PUMP_BATCH per GIL round-trip. Returns True
        when any task was completed."""
        lib = self.lib
        w = es.th_id if es.th_id < self.nworkers else 0
        tids = self._tidbuf[w]
        rann = self._ranbuf[w]
        ran = False
        while True:
            n = lib.pdtd_pump_batch(self._e, w, tids, _PUMP_BATCH,
                                    ctypes.byref(rann))
            if rann.value:
                ran = True
            if n == 0:
                if self.retiring and lib.pdtd_inflight(self._e) == 0:
                    # aborted pool fully drained: fold the counters now
                    self.tp.context._ndtd_unregister(self)
                return ran
            ran = True
            self._run_batch(tids, n, w)

    def _obs_ns(self, t: float) -> int:
        """perf_counter seconds → the engine's monotonic-ns domain
        (inverse of the enable-time clock handshake)."""
        return int((t - self.obs_offset_s) * 1e9)

    def _run_batch(self, tids, n: int, w: int) -> None:
        """Run up to _PUMP_BATCH Python bodies. Tasks with no tile
        traffic (no retained outputs, no consumed predecessors — the
        null-task and serving shapes) complete through ONE batched
        native call; tile-bearing tasks take the full individual path
        (write-back, retained outputs, drop reporting). With the event
        rings live, per-task body begin/end stamps ride the completion
        call — the batch's single completion instant would otherwise
        smear the whole batch's makespan over every task's span."""
        tp = self.tp
        rows = self.rows
        obs = self._obs
        # (seq, tc_name, t0_ns, t1_ns) batch-completable
        done: List[tuple] = []
        try:
            for i in range(n):
                seq = tids[i]
                row = rows.pop(seq, None)
                if row is None:
                    done.append((seq, "dtd_task", 0, 0))
                    continue
                info, spec, resolvers, out_tiles, n_lp = row
                if out_tiles or n_lp:
                    self._run_full(seq, info, spec, resolvers,
                                   out_tiles, n_lp, w)
                    continue
                hook = info[0]
                vals = self._resolve(resolvers)
                tb = time.perf_counter() if obs else 0.0
                result = hook(_Shim(spec), *vals) \
                    if hook is not None else None
                te = time.perf_counter() if obs else 0.0
                self._normalize(result, info[1], seq)   # validate-only:
                # no output flow can exist without an out tile
                if type(result) is dict and \
                        self._dfsan_manifest is not None:
                    # dynamic access-mode check (dfsan): a dict return
                    # may target a declared READ flow — record for the
                    # fold-time replay's access-violation report
                    self._dfsan_check_modes(seq, info, result)
                done.append((seq, info[2],
                             self._obs_ns(tb) if obs else 0,
                             self._obs_ns(te) if obs else 0))
        except BaseException as exc:  # noqa: BLE001 — worker must survive
            self._flush_batch(done, w)
            self._fail(seq, exc, w)
            # account the popped-but-unrun remainder so the engine still
            # drains (the pool is aborted; their bodies never run)
            rest = [(tids[j], "dtd_task", 0, 0) for j in range(i + 1, n)]
            for s, _, _, _ in rest:
                rows.pop(s, None)
            self._flush_batch(rest, w, retire=False)
            return
        self._flush_batch(done, w)

    def _flush_batch(self, done: List[tuple], w: int,
                     retire: bool = True) -> None:
        if not done:
            return
        tp = self.tp
        # retire hooks + lineage BEFORE the native completion: wait()'s
        # drain returns when the engine's inflight hits zero, and the
        # Python engine guarantees every on_retire happened-before wait
        # returns (the tenant-window accounting tests rely on it). The
        # finally keeps the completion unconditional — a raising retire
        # hook must not strand popped tasks (inflight would never drain)
        try:
            if retire and tp.on_retire is not None:
                for _ in done:
                    tp.on_retire(tp)
            if tp.context._track_completed:
                add = tp.completed_tasks.add
                for s, nm, _tb, _te in done:
                    add((nm, (s,)))
        finally:
            arr = self._batchbuf[w]
            t01 = None
            if self._obs:
                t01 = self._t01buf[w]
                for j, (s, _nm, tb, te) in enumerate(done):
                    arr[j] = s
                    t01[2 * j] = tb
                    t01[2 * j + 1] = te
            else:
                for j, (s, _nm, _tb, _te) in enumerate(done):
                    arr[j] = s
            newly = self.lib.pdtd_complete_batch(self._e, w, arr,
                                                 len(done), t01)
            if newly:
                evt = tp.context._work_evt
                if not evt.is_set():
                    evt.set()

    def _resolve(self, resolvers) -> List[Any]:
        vals = [None] * len(resolvers)
        outputs = self.outputs
        for i, r in enumerate(resolvers):
            k = r[0]
            if k == 0:
                vals[i] = r[1]
            elif k == 1:
                out = outputs.get(r[1])
                vals[i] = None if out is None else out.get(r[2])
            else:                               # alias of an earlier flow
                vals[i] = vals[r[1]]
        return vals

    def _run_full(self, seq: int, info, spec, resolvers, out_tiles,
                  n_lp: int, w: int) -> None:
        """Individual path for tile-bearing tasks: body, write-back +
        writer-marker retire (write BEFORE clear, the Python engine's
        retire protocol), retained outputs for linked readers, native
        completion with drop reporting."""
        tp = self.tp
        hook, out_flows, tc_name = info[0], info[1], info[2]
        obs = self._obs
        t0ns = t1ns = 0
        try:
            vals = self._resolve(resolvers)
            tb = time.perf_counter() if obs else 0.0
            result = hook(_Shim(spec), *vals) if hook is not None \
                else None
            if obs:
                t0ns = self._obs_ns(tb)
                t1ns = self._obs_ns(time.perf_counter())
            outs = self._normalize(result, out_flows, seq)
            if self._dfsan_manifest is not None:
                if out_tiles:
                    # committed-output evidence for the dfsan replay:
                    # only flows the body actually produced stamp a
                    # write (the Python engine's observe_write rule)
                    self._dfsan_commits[seq] = tuple(
                        f for (_t, f, _i) in out_tiles if f in outs)
                if type(result) is dict:
                    self._dfsan_check_modes(seq, info, result)
            if out_tiles:
                # retained per-flow value for linked readers: the
                # produced output, else the input that flowed through
                # (INOUT chain semantics)
                retained: Dict[str, Any] = {}
                for (tile, fname, idx) in out_tiles:
                    v = outs.get(fname, vals[idx] if idx < len(vals)
                                 else None)
                    retained[fname] = v
                    if fname in outs:
                        tile.collection.write_tile(tile.key, outs[fname])
                    with tile.lock:
                        lw = tile.last_writer
                        if isinstance(lw, _NativeWriter) and \
                                lw.seq == seq:
                            tile.last_writer = None
                            tile.last_writer_flow = None
                            if tile.flushed:    # flush_tile's retire
                                tp.tiles.retire(tile)
                self.outputs[seq] = retained
        except BaseException as exc:  # noqa: BLE001 — worker must survive
            self._fail(seq, exc, w)
            return
        # retire before the native completion — see _flush_batch; the
        # finally keeps the completion unconditional on a raising hook
        try:
            if tp.on_retire is not None:
                tp.on_retire(tp)
            if tp.context._track_completed:
                tp.completed_tasks.add((tc_name, (seq,)))
        finally:
            self._complete(seq, w, n_lp, drop_own=not out_tiles,
                           t0ns=t0ns, t1ns=t1ns)

    def _dfsan_check_modes(self, seq: int, info, result: dict) -> None:
        """Dynamic access-mode capture (ISSUE 14): a dict return whose
        key names a declared non-WRITE flow is the violation dfsan's
        ``_release_begin`` flags on the Python engine — recorded here
        (class-level flow layout, captured once per class in
        ``_class_for``) and reported at the fold-time replay."""
        flows = info[3]
        for name in result:
            fa = flows.get(name)
            if fa is None:
                continue
            access, is_ctl = fa
            if is_ctl or not (access & FlowAccess.WRITE):
                self._dfsan_violations.append(
                    (seq, info[2], name, access))

    def _complete(self, seq: int, w: int, n_lp: int,
                  drop_own: bool, t0ns: int = 0, t1ns: int = 0) -> None:
        lib = self.lib
        info = self._infobuf[w]
        drops = self._dropbuf[w] if n_lp else None
        nd = lib.pdtd_complete(self._e, w, seq, drops,
                               n_lp, info, t0ns, t1ns)
        if nd > 0:
            outputs = self.outputs
            for i in range(min(nd, n_lp)):
                outputs.pop(drops[i], None)
        if not drop_own and info[1] == 0:
            # no linked reader will ever consume these outputs
            self.outputs.pop(seq, None)
        if info[0]:
            evt = self.tp.context._work_evt
            if not evt.is_set():
                evt.set()

    def _fail(self, seq: int, exc: BaseException, w: int) -> None:
        """A Python body raised: abort the pool (which cancels this
        engine via _on_terminated), then account the failed task so the
        engine still drains."""
        tp = self.tp
        warning("scheduling", "native DTD task seq=%d of %s raised: %s",
                seq, tp.name, exc)
        import traceback
        traceback.print_exc()
        tp.abort(exc)
        self._complete(seq, w, 0, drop_own=True)

    # ----------------------------------------------------- drain / cancel
    def drain(self) -> None:
        """Block until every inserted task left flight (wait()); exits
        early when the pool aborted (cancel() already released the
        queued tasks)."""
        lib = self.lib
        tp = self.tp
        while lib.pdtd_inflight(self._e) > 0:
            if tp.error is not None:
                return
            lib.pdtd_wait_below(self._e, 0, 250)

    def cancel(self) -> None:
        self._cancelled = True
        self.lib.pdtd_cancel(self._e)

    @property
    def tp(self):
        return self._tp_ref()

    def inflight(self) -> int:
        return int(self.lib.pdtd_inflight(self._e))

    def release_refs(self) -> None:
        """Drop retained per-task state once the engine is FOLDED (the
        pool terminated AND inflight hit zero — no body can resolve a
        value anymore). The abort path completes failed/unrun tasks
        without drop reporting, so without this sweep an aborted pool's
        retained tile outputs would stay pinned until the pool object
        itself is collected."""
        self.rows.clear()
        self.outputs.clear()
        self._tp_held = None
        if self._dfsan_manifest is not None:
            self._dfsan_manifest.clear()
            self._dfsan_commits.clear()
            del self._dfsan_violations[:]

    # ------------------------------------------------------------- observe
    def stats(self) -> Dict[str, int]:
        buf = (ctypes.c_uint64 * len(_native.PDTD_STAT_KEYS))()
        self.lib.pdtd_stats(self._e, buf)
        return {k: int(v) for k, v in zip(_native.PDTD_STAT_KEYS, buf)}

    def obs_drain(self) -> List[np.ndarray]:
        """Snapshot every worker's event ring (non-consuming): one
        structured array per non-empty ring, dtype
        ``_native.obs_dtype()``. One memcpy per ring — no per-event
        Python work; the trace adapter expands lazily at dump time."""
        if not self._obs:
            return []
        lib = self.lib
        cap = self._obs_cap
        dt = _native.obs_dtype()
        vp = ctypes.c_void_p
        out: List[np.ndarray] = []
        for w in range(self.nworkers):
            buf = np.empty(cap, dt)
            n = lib.pdtd_obs_drain(self._e, w,
                                   buf.ctypes.data_as(vp), cap)
            if n > 0:
                out.append(buf[:n].copy())
        return out

    def obs_dropped(self) -> int:
        """Records lost to in-engine ring wraps."""
        return self.stats().get("obs_dropped", 0) if self._obs else 0

    def obs_retire(self) -> None:
        """Pool folded (terminated AND drained): freeze the adapter's
        snapshot, feed ring-fed PINS modules (the straggler watchdog's
        native path), run the dfsan replay over the frozen rings +
        insert manifests (ISSUE 14 — before the context's termination
        barrier advances the sanitizer base on the clean path; an
        aborted pool folds after its barrier, so the replay seeds from
        the pre-barrier base snapshot ``_ndtd_retire`` stashed on the
        engine), and free the C ring memory — a persistent serving
        context must not pin one ring set per retired pool."""
        ad = self._obs_adapter
        if ad is not None:
            ad.snapshot()
            ctx = self.tp.context
            if ctx is not None:
                for mod in getattr(ctx, "pins_modules", ()):
                    feed = getattr(mod, "observe_native_rings", None)
                    if feed is not None:
                        try:
                            feed(ad.raw_arrays(), self.class_names)
                        except Exception:  # noqa: BLE001 — observer
                            pass
        san = self._dfsan
        if san is not None:
            try:
                san.replay_native_pool(self)
            except Exception as exc:  # noqa: BLE001 — an observer
                # failure must not sink the serving fold, but a silent
                # one would fake a clean race report: be loud
                warning("analysis",
                        "dfsan native replay of %s failed: %s",
                        self.tp.name, exc)
        if self._obs:
            self._obs = False
            self.lib.pdtd_obs_disable(self._e)

    # ------------------------------------------------------------- helpers
    @staticmethod
    def _normalize(result, out_flows, seq) -> Dict[str, Any]:
        """Body result → output-flow dict: the ONE shared contract
        (core.task.normalize_outputs — also the device layer's), so
        engine choice never changes what a return value means."""
        from ..core.task import normalize_outputs
        return normalize_outputs(result, out_flows,
                                 f"dtd task seq={seq}")

    def __del__(self):
        e = getattr(self, "_e", None)
        lib = getattr(self, "lib", None)
        if e and lib is not None:
            try:
                lib.pdtd_free(e)
            except (AttributeError, TypeError, OSError):
                pass        # interpreter teardown: the OS reclaims it
        try:
            self._e = None
        except Exception:  # noqa: BLE001 — __del__ must never raise
            pass
