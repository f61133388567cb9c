"""Execution context and worker scheduling loop.

Reference mapping:
- ``parsec_init`` (parsec.c:384-924): builds the context — vpmap, execution
  streams (one per core), scheduler selection, device registration — and
  spawns worker threads that block on a barrier until work arrives.
- ``parsec_context_add_taskpool`` (scheduling.c:678-727): installs the
  default termdet, runs the taskpool's startup hook to seed
  no-predecessor tasks, schedules them.
- ``parsec_context_start/test/wait`` (scheduling.c:750-808).
- ``__parsec_context_wait`` (scheduling.c:537-676): the hot worker loop —
  select → prepare input → execute chore → complete → release deps, with
  exponential backoff when starved.
- ``__parsec_task_progress`` (scheduling.c:472-535) incl. the AGAIN path
  (priority demotion + reschedule) and ASYNC (device completes later).
- Release path ``parsec_release_dep_fct`` (parsec.c:1783-1921): successors
  counted down via the taskpool's pending table; ready tasks pushed as a
  priority-sorted ring; the best one is kept as the stream's bypass
  ``next_task`` (scheduling.c:346-398).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import weakref

from jax.profiler import TraceAnnotation

from .future import DataCopyFuture
from .reshape import resolve_reshape
from .spans import (SPAN_DISPATCH, SPAN_DTD_FLUSH,  # noqa: F401
                    SPAN_EXEC, SPAN_INSERT, SPAN_PARK, SPAN_PTG_STARTUP,
                    SPAN_PTG_UNFOLD, SPAN_RELEASE, SPAN_SELECT, SPAN_TURN,
                    StageSpan)
from .task import (GROUP_SIZES, GROUP_TAKE, Chore, DeviceType, HookReturn,
                   Task, TaskStatus)
from .taskpool import DataRef, SuccessorRef, Taskpool
from ..utils import debug_history, mca_param
from ..utils.debug import debug_verbose, warning
from .. import termdet as termdet_mod

mca_param.register("runtime.nb_cores", 0, help="worker streams (0 = os.cpu_count())")
mca_param.register("runtime.stage_reads", "auto",
                   help="stage-through collection reads to the "
                        "accelerator: auto (when a non-CPU device is "
                        "registered) | 1 | 0")
mca_param.register("runtime.backoff_min_us", 50, help="starvation backoff floor")
mca_param.register("runtime.backoff_max_us", 2000, help="starvation backoff ceiling")
mca_param.register("runtime.bypass_chain", 1,
                   help="keep a completing task's best ready successor "
                        "in the stream's bypass slot (never queued); "
                        "0 = all ready tasks go through the scheduler")
mca_param.register("runtime.stage_timers", 0,
                   help="accumulate per-stage runtime-overhead timers "
                        "(select/dispatch/release on the streams, insert "
                        "on DTD taskpools) — the taskrate bench's "
                        "overhead breakdown; off by default (hot path)")
mca_param.register("vpmap", "flat",
                   help="virtual-process map: flat | nb:SIZE | "
                        "list:0,0,1,... | file:PATH")
mca_param.register("profiling.dot", "",
                   help="capture the executed DAG to this .dot file at "
                        "fini (--dot flag, parsec.c:589-607 analog)")
mca_param.register("runtime.lineage", 1,
                   help="record (class, coords) of every completed task "
                        "on its taskpool (Taskpool.completed_tasks) — "
                        "the survivors' lineage input for fault "
                        "recovery (data/recovery.py); 0 disables")
mca_param.register("runtime.ckpt_interval", 0,
                   help="checkpoint the registered collections every N "
                        "completed taskpools at quiesce points (see "
                        "Context.enable_checkpoints); 0 = only the "
                        "seconds-based trigger (or off)")
mca_param.register("runtime.ckpt_interval_s", 0.0,
                   help="also checkpoint when this many seconds passed "
                        "since the last save, checked at quiesce "
                        "points; 0 = only the taskpool-count trigger")
mca_param.register("runtime.ckpt_dir", "",
                   help="default directory for Context.enable_checkpoints")


class ExecutionStream:
    """Per-worker execution stream (reference parsec_execution_stream_t)."""

    __slots__ = ("context", "th_id", "vp_id", "sched_obj", "next_task",
                 "thread", "stats", "_vp_peers", "_steal_order", "infos")

    def __init__(self, context: "Context", th_id: int, vp_id: int):
        from ..utils.info import InfoArray, per_stream_infos
        self.context = context
        self.th_id = th_id
        self.vp_id = vp_id
        self.sched_obj = None
        self.next_task: Optional[Task] = None   # priority bypass slot
        self.thread: Optional[threading.Thread] = None
        self.stats = {"executed": 0, "selected": 0, "starved": 0,
                      "stolen": 0,
                      # per-stage overhead timers (runtime.stage_timers)
                      "select_s": 0.0, "select_calls": 0,
                      "dispatch_s": 0.0, "release_s": 0.0,
                      # of release_s: a closed-form front end evaluating
                      # the completed task's successor list
                      "unfold_s": 0.0,
                      # waiting for a module's turn, a ready task in hand
                      "turn_s": 0.0,
                      # why _take_group stopped taking (_class: on a
                      # task that cannot be grouped), and the bins its
                      # takes launched (stage timers on)
                      "group_end_limit": 0, "group_end_empty": 0,
                      "group_end_class": 0, "group_bins": 0,
                      # takes that ended on a task whose tile lies on
                      # another chip (several chip modules; as above)
                      "group_end_module": 0,
                      # with several chip modules, always: the tasks
                      # this worker launched whose written tile is
                      # advised to a module, and those of them that ran
                      # on that module (Context._count_advised)
                      "tasks_advised": 0, "tasks_on_advised": 0,
                      # activations that carried ONE element of a ranged
                      # flow's list (Out(scatter=True)), always
                      "ranged_scatters": 0}
        self._vp_peers = None        # cached steal orders (sched/base.py)
        self._steal_order = None
        # extensible per-stream info slots (parsec_internal.h:688-702)
        self.infos = InfoArray(per_stream_infos, self)


def _parse_vpmap(nb_cores: int) -> List[int]:
    """Return vp_id per stream (reference vpmap.c:162-368; spec grammar
    in utils/vpmap.py: flat | nb:SIZE | list:... | file:PATH)."""
    from ..utils import vpmap
    return vpmap.parse(str(mca_param.get("vpmap", "flat")), nb_cores)


class Context:
    """The runtime context (parsec_context_t analog)."""

    def __init__(self, nb_cores: Optional[int] = None,
                 scheduler: Optional[str] = None,
                 comm=None):
        from .. import device as device_mod
        from .. import sched as sched_mod
        from ..profiling import pins as pins_mod

        if nb_cores is None or nb_cores <= 0:
            nb_cores = int(mca_param.get("runtime.nb_cores", 0)) or \
                min(os.cpu_count() or 1, 8)
        self.nb_cores = nb_cores
        self.comm = comm            # comm engine (None = single process)
        self.my_rank = comm.rank if comm is not None else 0

        vp_ids = _parse_vpmap(nb_cores)
        self.streams = [ExecutionStream(self, i, vp_ids[i])
                        for i in range(nb_cores)]
        # context-level counters (tasks completed by device managers,
        # which have no owning stream — ASYNC contract)
        self.stats: Dict[str, int] = {"device_completed": 0}

        self.scheduler = sched_mod.new_scheduler(scheduler)
        self.scheduler.install(self)
        for es in self.streams:
            self.scheduler.flow_init(es)

        # release-path knob, resolved once per context (the hot loops
        # read attributes, not the MCA registry); lowercase so
        # set(..., False) / "OFF" disable like "0" does
        self._bypass_chain = str(mca_param.get(
            "runtime.bypass_chain", 1)).lower() not in ("0", "off", "false")
        # data-plane broadcast enable (comm.bcast, registered by
        # comm.collectives); resolved once like the release knob
        self._comm_bcast = str(mca_param.get(
            "comm.bcast", 1)).lower() not in ("0", "off", "false")
        # per-stage overhead timers and spans (select/park/dispatch/
        # release on the streams, exec in the device module, insert on
        # DTD taskpools). ``stage_timers`` is the one per-site test: on
        # when asked for (the MCA param, or the profiling `overhead`
        # PINS module through set_stage_timers) or while a profiler
        # session is live (add_taskpool looks, once per pool)
        self.stage_timers_asked = str(mca_param.get(
            "runtime.stage_timers", 0)).lower() not in ("0", "off",
                                                        "false", "")
        self._profiler_live = False
        self.stage_timers = self.stage_timers_asked
        # lineage record for fault recovery (runtime.lineage)
        self._track_completed = str(mca_param.get(
            "runtime.lineage", 1)).lower() not in ("0", "off", "false")
        # deterministic failure injection: tick task units on the
        # victim rank (comm.fault_inject_unit = tasks)
        self._fault = getattr(comm, "fault", None)
        # periodic async checkpoints (enable_checkpoints): None = off
        self._ckpt = None

        self.devices = device_mod.Registry(self)
        self.pins = pins_mod.PinsManager(self)

        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        # native DTD engines (dsl/dtd_native.py): live engines are
        # pumped by the worker loop; terminated pools fold their
        # counters into _ndtd_totals so completed-task totals survive
        self._ndtd_live: List = []
        self._ndtd_lock = threading.Lock()
        self._ndtd_totals: Dict[str, int] = {}
        # the Python DTD front end's counters, summed over the pools
        # that ended with the stage timers on (fold_dtd_counters)
        self.dtd_counters: Dict[str, float] = {}
        # per-tenant native completions (the tenant PINS module and the
        # metrics collector fold these in at scrape — native pools never
        # fire the per-task EXEC hooks, by design)
        self._ndtd_tenant_totals: Dict[str, int] = {}
        self._active_taskpools: List[Taskpool] = []
        # name → taskpool, past termination too: late control traffic
        # (DTD flush writebacks/acks) must still find its taskpool. Held
        # weakly: a finished pool is addressable as long as somebody
        # can still address it (the rank inside its collective flush
        # holds it), and the Context keeps no pool, and no matrix a pool
        # refers to, for its own life
        self._taskpools_by_name: "weakref.WeakValueDictionary[str, Taskpool]" \
            = weakref.WeakValueDictionary()
        # what add_taskpool exposed to the peers' one-sided fetches
        self._exposed: Dict[int, object] = {}
        # pools added and pools that ended (statusz "taskpools")
        self._taskpools_added = 0
        self._taskpools_terminated = 0
        self._aborted: List[Taskpool] = []
        self._started = False
        self._shutdown = False
        self._work_evt = threading.Event()
        self.grapher = None          # profiling.grapher hook
        self.trace = None            # profiling trace hook
        self.serving = None          # serving.runtime.ServingRuntime
        #                              (attached by serving.enable /
        #                              first Context.submit)
        self.dfsan = None            # analysis.dfsan race sanitizer (PINS
        #                              module sets it; None = zero overhead)
        self.kv_state = None         # serving KV state layer (paged
        #                              prefix cache — serving/kv.py
        #                              KVStateLayer attaches itself)
        # PINS modules selected by the `pins` MCA param; must come after
        # trace/grapher init (task_profiler installs a Trace on self.trace)
        from ..profiling import pins_modules as pins_modules_mod
        self.pins_modules = pins_modules_mod.install_selected(self)
        # bounded device residency for task-written collection tiles
        # (device.hbm_budget_mb; reference: GPU LRU eviction lists,
        # device_gpu.h:115-136) — cold device tiles spill back to host
        # numpy through their collection
        from ..device.hbm import manager_from_mca
        # host values it stages land on the first chip module's chip
        # (a CPU mesh keeps JAX's uncommitted default placement, as the
        # comm stage target does: device/tpu.py)
        chips = self.devices.chips
        self.hbm = manager_from_mca(
            chips[0].jax_device if chips and chips[0].platform != "cpu"
            else None)

        # always-on metrics plane (profiling/metrics.py): the process-
        # global registry plus this context's scrape-time collectors
        # (queue depth, steal rates, wfq pool_stats, tenants, HBM,
        # compile cache). The only HOT-path cost is one sharded counter
        # inc per completed task; profiling.metrics=0 removes even that
        # (the observability bench's A/B baseline).
        from ..profiling import metrics as metrics_mod
        self.metrics = metrics_mod.registry()
        self._metrics_unhook = None
        self._metrics_server = None
        if metrics_mod.enabled():
            self._metrics_unhook = \
                metrics_mod.install_context_collectors(self)
            port = int(mca_param.get("serving.metrics_port", 0))
            if port:
                self._metrics_server = metrics_mod.serve_http(
                    port, statusz_fn=self.statusz)

        self._dot_path = str(mca_param.get("profiling.dot", "") or "")
        if self._dot_path:
            from ..profiling.grapher import Grapher
            Grapher().install(self)     # written out at fini

        if comm is not None and hasattr(comm, "install_activate_handler"):
            comm.install_activate_handler(self)

        for es in self.streams:
            t = threading.Thread(target=self._worker_main, args=(es,),
                                 name=f"parsec-es-{es.th_id}", daemon=True)
            es.thread = t
            t.start()
        debug_verbose(3, "context",
                      "context up: %d streams, sched=%s",
                      nb_cores, self.scheduler.name)

    @property
    def nb_ranks(self) -> int:
        """The CURRENT world size — read through to the comm engine
        (elastic meshes grow/shrink it live; a snapshot taken at
        context construction would route new cross-rank taskpools and
        collections against a stale world)."""
        return self.comm.nb_ranks if self.comm is not None else 1

    # ------------------------------------------------------------------ API
    def set_stage_timers(self, on: bool) -> bool:
        """Ask for the stage timers (or stop asking); returns what was
        asked before. A live profiler session keeps them on regardless."""
        prev, self.stage_timers_asked = self.stage_timers_asked, bool(on)
        self.stage_timers = self.stage_timers_asked or self._profiler_live
        return prev

    def add_taskpool(self, tp: Taskpool) -> None:
        """parsec_context_add_taskpool analog (scheduling.c:678-727)."""
        # a profiler session (jax.profiler.start_trace, TensorBoard's
        # capture) started or stopped since the last pool: its traces get
        # the runtime's stage spans with nothing to configure. Only the
        # profiler's part of the flag moves here.
        live = TraceAnnotation.is_enabled()
        if live != self._profiler_live:
            self._profiler_live = live
            self.stage_timers = self.stage_timers_asked or live
        # registration-time static lint (analysis.lint = off|warn|error):
        # with `error`, a taskpool whose flow declarations carry hazards
        # (undeclared producers, WAW, cycles, ...) is refused BEFORE any
        # runtime state is touched (analysis/lint.py HazardError)
        lint_mode = str(mca_param.get("analysis.lint", "off")).lower()
        if lint_mode in ("warn", "error") and tp.task_classes:
            tp.validate(mode=lint_mode)
        if tp.monitor is None:
            tp.monitor = termdet_mod.new_monitor(comm=self.comm)
        # weakly: the pool holds its monitor, and a monitor that held
        # the pool's bound method would make every pool a cycle
        tp.monitor.monitor(_weakly(tp._on_terminated))
        if self.comm is not None and hasattr(self.comm, "register_termdet"):
            self.comm.register_termdet(tp.name, tp.monitor)
        tp.context = self
        if self.comm is not None and self.nb_ranks > 1:
            # expose the taskpool's collections for one-sided tile
            # fetches (CommEngine.fetch_tile): bodies using the
            # direct-memory gathered-operand pattern resolve remote
            # tiles through the owner's comm thread
            g = getattr(tp, "g", None)
            for obj in vars(g).values() if g is not None else ():
                if hasattr(obj, "data_of") and hasattr(obj, "rank_of") \
                        and hasattr(obj, "name"):
                    self.comm.expose_collection(obj, scope=tp.name)
                    # a peer fetches from it after this rank's part of
                    # the pool has ended and its user has gone on, and
                    # no rank is told when the last peer is through: the
                    # collection (not the pool) stays to the Context's
                    # end, across ranks alone
                    self._exposed[id(obj)] = obj
        with self._lock:
            self._active_taskpools.append(tp)
            self._taskpools_by_name[tp.name] = tp
            self._taskpools_added += 1
        if self.comm is not None and hasattr(self.comm, "taskpool_registered"):
            # drain parked activations; False = registration refused
            # (broken mesh) — the engine already aborted the pool, so
            # don't launch startup work into a dead mesh
            if self.comm.taskpool_registered(tp) is False:
                return
        if tp.on_enqueue is not None:
            tp.on_enqueue(tp)
        self.pins.taskpool_init(tp)
        if self.stage_timers and tp.startup_span is not None:
            with StageSpan(tp.startup_span):
                self._startup(tp)
        else:
            self._startup(tp)
        tp.monitor.ready()
        if self._started:
            self._work_evt.set()

    def _startup(self, tp: Taskpool) -> None:
        startup = tp.startup_hook(tp) or []
        if startup:
            self.schedule(None, list(startup))

    def start(self) -> None:
        """parsec_context_start analog: release the workers."""
        with self._lock:
            self._started = True
        if self.comm is not None:
            self.comm.enable()
        self._work_evt.set()

    @property
    def stage_reads(self) -> bool:
        """True when collection reads should stage-through to the
        accelerator (``runtime.stage_reads``: auto = a real non-CPU
        device is registered). The reference keeps per-device data
        copies with coherency (device_gpu stage-in attaches the GPU
        copy to the data object); here the collection's stored tile is
        REPLACED by its staged device array on first read, so every
        later reader reuses the single H2D transfer instead of paying
        one per task. Set ``0`` for host-pure
        workloads (e.g. wire-latency harnesses: staging would route
        every payload through the accelerator)."""
        # per-read hot path: cache the resolved answer against the MCA
        # registry generation (one int compare) instead of taking the
        # registry lock per collection read
        gen = mca_param.generation()
        cached = self.__dict__.get("_stage_reads_gen")
        if cached is not None and cached[0] == gen:
            return cached[1]
        mode = str(mca_param.get("runtime.stage_reads", "auto"))
        if mode in ("0", "off", "false"):
            result = False
        elif mode in ("1", "on", "true"):
            result = True
        else:
            result = any(
                getattr(d, "platform", "cpu") not in ("cpu",)
                for d in getattr(self.devices, "devices", []))
        self.__dict__["_stage_reads_gen"] = (gen, result)
        return result

    def stage_read(self, dc, key, value):
        """Stage-through one collection read (see :attr:`stage_reads`):
        host arrays are device_put (async) and written back so the
        collection holds the device copy; everything else passes
        through. Among several chip modules a tile of an advised
        collection goes to the chip it is advised to, committed there,
        where its writer runs; any other stays uncommitted and follows
        the module that takes it."""
        import numpy as np
        if not self.stage_reads or not isinstance(value, np.ndarray):
            return value
        import jax
        chips = self.devices.chips
        advice = getattr(dc, "device_advice", None)
        if len(chips) > 1 and advice is not None:
            staged = jax.device_put(
                value, chips[advice(key) % len(chips)].jax_device)
        else:
            staged = jax.device_put(value)
        dc.write_tile(key, staged)
        return staged

    def submit(self, tp: Taskpool, tenant=None,
               deadline_s: Optional[float] = None,
               weight: Optional[float] = None,
               rank_scope=None, hbm_bytes: int = 0):
        """Serving-mode taskpool submission: route ``tp`` through the
        multi-tenant serving runtime (admission control, weighted-fair
        scheduling, per-submission deadline with cancellation, tenant
        quarantine, overload shedding) and return a
        :class:`~parsec_tpu.serving.runtime.Submission` handle. A
        runtime with default knobs is attached on first use; call
        :func:`parsec_tpu.serving.enable` first to configure tenants
        and watermarks explicitly. Raises
        :class:`~parsec_tpu.serving.runtime.AdmissionRejected` (window/
        HBM/overload shed) or :class:`~parsec_tpu.serving.runtime.
        TenantQuarantined` instead of parking unboundedly."""
        if self.serving is None:
            from ..serving.runtime import ServingRuntime
            with self._lock:
                # compare-and-set under the context lock: two client
                # threads racing the first submit must share ONE
                # runtime, or tenant windows/quarantines split across
                # two disconnected tenant tables
                if self.serving is None:
                    ServingRuntime(self)     # attaches as self.serving
        return self.serving.submit(tp, tenant=tenant,
                                   deadline_s=deadline_s, weight=weight,
                                   rank_scope=rank_scope,
                                   hbm_bytes=hbm_bytes)

    def test(self) -> bool:
        """parsec_context_test analog: True iff all taskpools completed."""
        with self._lock:
            return len(self._active_taskpools) == 0

    def wait(self, timeout: Optional[float] = None) -> bool:
        """parsec_context_wait analog: block until every enqueued taskpool
        terminated. Returns False on timeout."""
        if not self._started:
            self.start()
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while self._active_taskpools:
                remaining = None if deadline is None \
                    else max(0.0, deadline - time.monotonic())
                if remaining == 0.0:
                    return False
                self._cv.wait(remaining if remaining is not None else 0.25)
            if self._aborted:
                tp = self._aborted[0]
                self._aborted.clear()
                raise RuntimeError(
                    f"taskpool {tp.name} aborted: {tp.error}") from tp.error
        return True

    # -------------------------------------------------- native DTD engines
    def fold_dtd_counters(self, counters: Dict[str, float]) -> None:
        """What a DTD pool's front end counted while the stage timers
        were on (``dtd.Taskpool.counters``), added to the Context's sums
        when the pool ends; a ``*_peak`` is the largest seen."""
        with self._lock:
            mine = self.dtd_counters
            for name, n in counters.items():
                mine[name] = max(mine.get(name, 0), n) \
                    if name.endswith("_peak") else mine.get(name, 0) + n

    def _ndtd_register(self, eng) -> None:
        with self._ndtd_lock:
            if eng not in self._ndtd_live:
                self._ndtd_live.append(eng)

    def _ndtd_retire(self, eng) -> None:
        """A pool terminated: fold its engine now if drained, else mark
        it retiring — the workers keep pumping it (cancelled pools drop
        their queued tasks at select time there) and the pump folds it
        once the last in-flight task leaves."""
        if eng.inflight() == 0:
            self._ndtd_unregister(eng)
        else:
            # the pump folds this engine AFTER the pool's termination
            # barrier has advanced the sanitizer base — snapshot the
            # pre-barrier base now so the dfsan replay seeds from it
            san = getattr(eng, "_dfsan", None)
            if san is not None:
                eng._dfsan_base = san.base_snapshot()
            eng.retiring = True

    def _ndtd_unregister(self, eng) -> None:
        """Fold a retired engine's monotonic counters into the context
        totals (idempotent — refired termination is absorbed)."""
        with self._ndtd_lock:
            if eng not in self._ndtd_live:
                return
            self._ndtd_live.remove(eng)
            stats = eng.stats()
            for k, v in stats.items():
                if k in ("inflight", "ready", "obs_ring_depth"):
                    continue                    # gauges, not counters
                if k == "ring_highwater":
                    self._ndtd_totals[k] = max(
                        self._ndtd_totals.get(k, 0), v)
                elif k == "lock_pairs":
                    # acquisition-pair BITMASK (ISSUE 14): OR, not sum
                    self._ndtd_totals[k] = \
                        self._ndtd_totals.get(k, 0) | v
                else:
                    self._ndtd_totals[k] = \
                        self._ndtd_totals.get(k, 0) + v
            ten = getattr(eng.tp, "tenant_name", None) or "(untenanted)"
            self._ndtd_tenant_totals[ten] = \
                self._ndtd_tenant_totals.get(ten, 0) + \
                stats.get("completed_native", 0) + \
                stats.get("completed_python", 0)
        # freeze the trace adapter's ring snapshot + free the C rings
        # BEFORE dropping the per-task refs (the adapter keeps only the
        # raw record arrays — expansion stays deferred to dump time)
        obs_retire = getattr(eng, "obs_retire", None)
        if obs_retire is not None:
            obs_retire()
        eng.release_refs()

    def native_dtd_stats(self) -> Dict[str, int]:
        """Aggregate native-DTD engine counters: retired pools' folded
        totals plus every live engine (scrape-time; the hot loop only
        touches C++ atomics)."""
        with self._ndtd_lock:
            out = dict(self._ndtd_totals)
            live = list(self._ndtd_live)
        for eng in live:
            for k, v in eng.stats().items():
                if k == "ring_highwater":
                    out[k] = max(out.get(k, 0), v)
                elif k == "lock_pairs":
                    out[k] = out.get(k, 0) | v
                else:
                    out[k] = out.get(k, 0) + v
        return out

    def native_tenant_stats(self) -> Dict[str, int]:
        """Per-tenant native-engine completions (retired pools' folded
        totals plus live engines): the scrape-time source the tenant
        PINS module and the metrics collector merge, since native pools
        never fire the per-task EXEC hooks."""
        with self._ndtd_lock:
            out = dict(self._ndtd_tenant_totals)
            live = list(self._ndtd_live)
        for eng in live:
            st = eng.stats()
            ten = getattr(eng.tp, "tenant_name", None) or "(untenanted)"
            out[ten] = out.get(ten, 0) + \
                st.get("completed_native", 0) + \
                st.get("completed_python", 0)
        return out

    def _ndtd_pump(self, es: "ExecutionStream") -> bool:
        """Progress the live native DTD engines on this worker; True
        when any task completed (native-bodied ones inside the C call
        with the GIL released, Python-bodied ones here). Exception-
        guarded like _task_progress: a raising user hook (on_retire /
        on_complete) aborts ITS pool instead of killing the worker."""
        with self._ndtd_lock:
            engines = list(self._ndtd_live)
        ran = False
        for eng in engines:
            try:
                if eng.pump(es):
                    ran = True
            except Exception as exc:  # noqa: BLE001 — worker must survive
                warning("scheduling", "native DTD pump of %s raised: %s",
                        eng.tp.name, exc)
                import traceback
                traceback.print_exc()
                eng.tp.abort(exc)
                ran = True
        return ran

    # ------------------------------------------------------ observability
    def statusz(self) -> Dict:
        """Live runtime status as one JSON-able dict: the metrics
        registry, stream counters, active pools, and (when serving) the
        tenant/pool report — the /statusz payload of the metrics
        listener (``serving.metrics_port``)."""
        with self._lock:
            active = [tp.name for tp in self._active_taskpools]
            # pools that ended against pools the Context can still
            # address: the active ones and, weakly, whichever finished
            # ones their users still hold
            pools = {"added": self._taskpools_added,
                     "terminated": self._taskpools_terminated,
                     "referenced": len({id(tp) for tp in (
                         *self._active_taskpools,
                         *self._taskpools_by_name.values())})}
        out = {
            "rank": self.my_rank,
            "nb_ranks": self.nb_ranks,
            "scheduler": self.scheduler.name,
            "active_taskpools": active,
            "taskpools": pools,
            "streams": {es.th_id: dict(es.stats) for es in self.streams},
            "metrics": self.metrics.to_dict(),
        }
        if self.serving is not None:
            out["serving"] = self.serving.report()
        if self.kv_state is not None:
            # KV state plane (pages in use / hit rate / spec counters)
            # — scrape-time snapshot, the autoscaler's KV-pressure row
            out["kv"] = self.kv_state.snapshot()
        out["capacity"] = self._capacity_block()
        # a module's counters, its longest wait for the chip and longest
        # jitted call among them: what a stalled step stood in
        out["devices"] = self.devices.dump_statistics()
        if self.trace is not None:
            out["trace_dropped"] = self.trace.dropped()
            # the native-ring share separately: a truncated NATIVE
            # capture (in-engine ring wrap / evicted snapshot) must be
            # loud on its own row, not hidden in the Python-ring total
            out["trace_native_dropped"] = self.trace.native_dropped()
        nstats = self.native_dtd_stats()
        if nstats:
            out["native_dtd"] = nstats
        return out

    def _capacity_block(self) -> Dict:
        """The statusz ``capacity`` block: configured vs live world
        size, a per-rank role map (self/joined/draining/departed/dead),
        and — when an elastic controller is attached — the autoscaler's
        desired count, last decision, and remaining cooldown. The
        operator's view of elasticity state without running the bench."""
        comm = self.comm
        if comm is not None and hasattr(comm, "world_status"):
            ws = comm.world_status()
        else:
            ws = {"configured": self.nb_ranks, "world": self.nb_ranks,
                  "live": list(range(self.nb_ranks)), "departed": [],
                  "dead": []}
        departed = set(ws.get("departed") or ())
        dead = set(ws.get("dead") or ())
        el = getattr(self.serving, "elastic", None) \
            if self.serving is not None else None
        draining = set(el.draining_ranks()) if el is not None else set()
        roles = {}
        for r in range(int(ws.get("world", self.nb_ranks))):
            if r == self.my_rank:
                roles[r] = "self"
            elif r in dead:
                roles[r] = "dead"
            elif r in departed:
                roles[r] = "departed"
            elif r in draining:
                roles[r] = "draining"
            else:
                roles[r] = "joined"
        out = {"configured_world": ws.get("configured"),
               "world": ws.get("world"),
               "live_world": len(ws.get("live") or ()),
               "roles": roles}
        if el is not None:
            out["autoscaler"] = el.status()
        return out

    def metrics_text(self) -> str:
        """Prometheus text exposition of the metrics registry (the
        /metrics payload)."""
        return self.metrics.to_prometheus_text()

    def fini(self) -> None:
        """parsec_fini analog: drain and stop the workers."""
        if self._metrics_server is not None:
            self._metrics_server.shutdown()
            self._metrics_server = None
        if self._metrics_unhook is not None:
            self._metrics_unhook()
            self._metrics_unhook = None
        if self.serving is not None:
            self.serving.shutdown()
        if self._ckpt is not None:
            # let an in-flight async save land — a torn final step would
            # be discarded by the atomic protocol, but the work is paid
            self._ckpt.wait(timeout=30.0)
        with self._lock:
            self._shutdown = True
        self._work_evt.set()
        for es in self.streams:
            if es.thread is not None:
                es.thread.join(timeout=5.0)
        for dev in self.devices.devices:
            dev.shutdown()
        if self.comm is not None:
            self.comm.disable()
        self.scheduler.remove(self)
        if self._dot_path and self.grapher is not None:
            try:
                self.grapher.write(self._dot_path)
            except OSError as exc:
                warning("profiling", "could not write %s: %s",
                        self._dot_path, exc)
        # MCA-selected PINS modules report at component close then detach
        # (reference modules print their data in their _fini)
        from ..utils.debug import get_verbosity
        for mod in self.pins_modules:
            if get_verbosity() >= 2:    # report() can scan the full trace
                debug_verbose(2, "pins", "%s: %s", mod.name, mod.report())
            mod.uninstall()
        debug_verbose(3, "context", "context down; stats=%s",
                      {es.th_id: es.stats for es in self.streams})

    # --------------------------------------------------------- scheduling
    def schedule(self, es: Optional[ExecutionStream], tasks: Sequence[Task],
                 distance: int = 0) -> None:
        """__parsec_schedule analog: push a ring of ready tasks."""
        if not tasks:
            return
        for t in tasks:
            t.status = TaskStatus.NONE
        self.pins.select_begin(es, tasks)
        if len(tasks) > 1:
            tasks = sorted(tasks, key=lambda t: -t.priority)
        self.scheduler.schedule(es, tasks, distance)
        # is_set() is a plain bool read; while workers are busy the event
        # stays set, so the common completion path skips the heavier
        # set() (lock + notify). A worker that cleared it re-selects
        # BEFORE waiting (see _worker_main), so this can't lose a wakeup.
        evt = self._work_evt
        if not evt.is_set():
            evt.set()

    def find_taskpool(self, name: str, active_only: bool = True):
        """Lookup by name; ``active_only=False`` includes terminated pools
        that somebody still holds (control traffic like DTD flush
        outlives termination; the rank it reaches is inside the same
        collective flush, with the pool in hand)."""
        with self._lock:
            if active_only:
                return next((t for t in self._active_taskpools
                             if t.name == name), None)
            return self._taskpools_by_name.get(name)

    def drop_copies(self, tp: Taskpool) -> None:
        """A finished pool holds no tile: the copies of other chips'
        tiles that ``tp``'s tasks read on a chip go with it (called by
        the pool as it ends, before its waiters wake)."""
        for dev in self.devices.chips:
            dev.drop_copies(tp)

    def _taskpool_terminated(self, tp: Taskpool) -> None:
        if self.dfsan is not None:
            # termdet is a full synchronization point: everything the
            # pool did happens-before whatever runs next (keeps the
            # sanitizer race-free across sequentially-run taskpools)
            self.dfsan.barrier()
        with self._cv:
            try:
                self._active_taskpools.remove(tp)
                self._taskpools_terminated += 1
            except ValueError:
                pass
            if tp.error is not None and tp not in self._aborted and \
                    not getattr(tp, "error_owned", False):
                # error_owned: the serving runtime reports this pool's
                # failure to ITS submitter (quarantine + Submission.wait)
                # — a failed tenant must not poison an unrelated
                # caller's Context.wait
                self._aborted.append(tp)
            quiesced = not self._active_taskpools
            self._cv.notify_all()
        if self.hbm is not None:
            # entries whose collection died with its taskpool: free the
            # accounting, skip the pointless spill
            self.hbm.sweep(_hbm_entry_dead)
        if quiesced and tp.error is None and self._ckpt is not None:
            self._ckpt.quiesce_point()

    # ------------------------------------------------- async checkpoints
    def enable_checkpoints(self, collections: Dict[str, object],
                           directory: Optional[str] = None,
                           interval: Optional[int] = None,
                           interval_s: Optional[float] = None):
        """Register ``collections`` (``{name: DataCollection}``) for
        periodic asynchronous checkpoints: at each QUIESCE point (the
        last active taskpool terminating cleanly — all state lives in
        the collections, the model data/checkpoint.py documents), if
        ``interval`` completed taskpools or ``interval_s`` seconds have
        passed since the last save, this rank's local tile references
        are captured synchronously (write_tile replaces references, so
        the captured cut is consistent) and serialized to disk on a
        background saver thread with the Orbax-style atomic-rename
        protocol. Defaults come from ``runtime.ckpt_interval``/
        ``runtime.ckpt_interval_s``/``runtime.ckpt_dir``. Returns the
        underlying :class:`~parsec_tpu.data.checkpoint.CheckpointManager`.
        """
        from ..data.checkpoint import CheckpointManager
        directory = directory or str(mca_param.get("runtime.ckpt_dir", ""))
        if not directory:
            raise ValueError("enable_checkpoints: no directory (argument "
                             "or runtime.ckpt_dir)")
        if interval is None:
            interval = int(mca_param.get("runtime.ckpt_interval", 0))
        if interval_s is None:
            interval_s = float(mca_param.get("runtime.ckpt_interval_s",
                                             0.0))
        mgr = CheckpointManager(directory, my_rank=self.my_rank,
                                nb_ranks=self.nb_ranks)
        self._ckpt = _CkptState(mgr, dict(collections), interval,
                                interval_s)
        return mgr

    def checkpoint_wait(self, timeout: Optional[float] = None) -> bool:
        """Join the in-flight background checkpoint save, if any (tests
        and pre-shutdown flushes). True when no save is pending."""
        return self._ckpt.wait(timeout) if self._ckpt is not None else True

    def checkpoint_now(self) -> Optional[str]:
        """Force a synchronous checkpoint of the registered collections
        (caller guarantees quiesce). Returns the step directory."""
        return self._ckpt.save_now() if self._ckpt is not None else None

    # --------------------------------------------------------- worker loop
    def _worker_main(self, es: ExecutionStream) -> None:
        from ..utils import binding
        binding.bind_worker(es.th_id)     # best-effort (-b analog)
        backoff_min = int(mca_param.get("runtime.backoff_min_us", 50)) / 1e6
        backoff_max = int(mca_param.get("runtime.backoff_max_us", 2000)) / 1e6
        backoff = backoff_min
        while True:
            if self._shutdown:
                return
            # retiring native engines (aborted pool already removed
            # from _active_taskpools, tasks still draining) count as
            # work: without them in this condition the cancelled tasks
            # would never be dropped and the engine never folded
            if not self._started or not (self._active_taskpools or
                                         self._ndtd_live):
                self._work_evt.clear()
                # re-check after clear to avoid a lost wakeup from
                # add_taskpool()/start() racing with the clear
                if self._shutdown or (self._started and
                                      (self._active_taskpools or
                                       self._ndtd_live)):
                    continue
                self._park(0.1)
                continue
            task = es.next_task
            es.next_task = None
            if task is None:
                task = self._select(es)
            if task is None and self._ndtd_live:
                # native DTD pump (the insert→release loop behind the C
                # ABI): native-bodied tasks drain entirely inside the
                # ctypes call with the GIL released; Python-bodied ones
                # run here. Tried when the Python queues are dry so
                # queued Python pools are never starved by a native loop.
                if self._ndtd_pump(es):
                    backoff = backoff_min
                    continue
            if task is None:
                es.stats["starved"] += 1
                # event-driven wakeup: schedule() sets _work_evt, so a
                # starved worker parks until new work instead of sleeping
                # through the latency path (the reference wakes workers
                # from remote_dep delivery the same way). Clear-then-
                # reselect avoids the lost-wakeup race; the timeout only
                # bounds termdet/shutdown polling.
                self._work_evt.clear()
                task = self._select(es)
                if task is None and self._ndtd_live and \
                        self._ndtd_pump(es):
                    # a native batch armed between the pump above and
                    # the clear: same lost-wakeup guard as the reselect
                    backoff = backoff_min
                    continue
                if task is None:
                    self._park(backoff)
                    backoff = min(backoff * 2, backoff_max)
                    continue
            backoff = backoff_min
            if task.taskpool.cancelled:
                # cancelled pool (deadline expiry / Submission.cancel):
                # drop instead of executing — covers the bypass slot and
                # every scheduler; the decrement keeps the idempotent
                # termdet counters consistent (a cancelled pool already
                # force-terminated, refires are absorbed)
                task.taskpool.addto_nb_tasks(-1)
                continue
            es.stats["selected"] += 1
            try:
                found = self._group_chore(task)
                if found is None:
                    self._task_progress(es, task)
                else:
                    self._group_progress(es, task, found)
            except Exception as exc:  # noqa: BLE001 - worker must survive
                warning("scheduling", "task %r raised: %s", task, exc)
                import traceback
                traceback.print_exc()
                from ..utils import debug_history
                debug_history.dump_on_fatal(f"task {task!r} raised")
                # successors can never fire: abort the pool so waiters are
                # released with the error instead of hanging (parsec_abort)
                task.taskpool.abort(exc)
            # a parked worker holds no task: its pool, and the tiles in
            # the task's data, would live until this worker's next one
            task = found = None

    def _select(self, es: ExecutionStream) -> Optional[Task]:
        if not self.stage_timers:
            return self.scheduler.select(es)
        with StageSpan(SPAN_SELECT) as span:
            task = self.scheduler.select(es)
        es.stats["select_s"] += span.seconds
        es.stats["select_calls"] += 1
        return task

    def _park(self, timeout: float) -> None:
        """A worker with nothing to run waits for schedule(),
        add_taskpool() or start() to set the event."""
        if not self.stage_timers:
            self._work_evt.wait(timeout)
            return
        with StageSpan(SPAN_PARK):
            self._work_evt.wait(timeout)

    def _task_progress(self, es: ExecutionStream, task: Task) -> None:
        """__parsec_task_progress analog (scheduling.c:472-535)."""
        self._executed(
            es, task, self._timed_dispatch(es, self._dispatch, es, task))

    def _executed(self, es: ExecutionStream, task: Task,
                  rc: HookReturn) -> None:
        if rc == HookReturn.ASYNC:
            return                      # device layer completes it later
        if rc == HookReturn.AGAIN:
            task.priority -= 1          # priority demotion + reschedule
            self.schedule(es, [task], distance=1)
            return
        if rc == HookReturn.ERROR:
            raise RuntimeError(f"all incarnations of {task!r} failed")
        self.complete_task(es, task)

    def _timed_dispatch(self, es: ExecutionStream, dispatch, *args):
        """One task's pass through ``dispatch``, under its span where
        the stage timers are on."""
        if not self.stage_timers:
            return dispatch(*args)
        # dispatch = prepare_input + incarnation walk + hook call
        # (for a null body this IS the per-task dispatch overhead)
        with StageSpan(SPAN_DISPATCH) as span:
            rc = dispatch(*args)
        es.stats["dispatch_s"] += span.seconds
        return rc

    # ------------------------------------------------------ group launch
    # A worker that holds a ready accelerator task whose chore has a
    # pure body takes the ready tasks of its taskpool it can select,
    # whatever their class, sorts them by body and has the device module
    # issue each body's tasks as one launch: one trip through jit
    # dispatch (and one hand-off of the GIL) for the group instead of one
    # per task. Formed on the thread that already holds the tasks; G = 1
    # is _task_progress.

    @staticmethod
    def _group_chore(task: Task) -> Optional[Tuple[Chore, Tuple]]:
        """The first incarnation the task's mask leaves, if it is an
        accelerator's, has a pure body the module may launch with others
        (``Chore.pure_body``, asked once a task) and does not veto the
        task, and the key of the bin its tasks share (one class, one
        chore, one key of the pure body: they may share a launch); else
        None, and ``_execute`` walks the incarnations."""
        for i, chore in enumerate(task.task_class.incarnations):
            if task.chore_mask & (1 << i):
                break
        else:
            return None
        if not chore.device_type & DeviceType.TPU:
            return None     # a CPU body is one call as it is
        pure = chore.pure_body(task)
        if pure is None:
            return None
        if chore.evaluate is not None and not chore.evaluate(task):
            return None
        return chore, (task.task_class, id(chore), pure[0])

    def _take_group(self, es: ExecutionStream, task: Task,
                    found: Tuple[Chore, Tuple], dev,
                    limit: int) -> List[Tuple[Chore, List[Task]]]:
        """``task`` (``found`` is its ``_group_chore``) and the tasks the
        scheduler hands this worker next, one bin per key, the bins in
        the order their first task was selected. The take ends when a
        bin holds what ``dev`` says one launch of its first task may
        carry (``limit`` for ``task``'s), at ``GROUP_TAKE`` tasks in all,
        on an empty queue, or on a task that cannot be grouped (another
        taskpool, no group chore, none ``dev`` may launch, or one whose
        written tile is advised to another chip module than ``dev``):
        that one waits in the bypass slot, and this worker takes it up
        next, on its own module. Nothing is pushed back, so the
        scheduler's order is what it was."""
        tp = task.taskpool
        bins = {found[1]: (found[0], [task], limit)}
        taken, end = 1, "limit"
        many = len(self.devices.chips) > 1
        while taken < GROUP_TAKE:
            nxt = self._select(es)
            if nxt is None:
                end = "empty"
                break
            if nxt.taskpool.cancelled:
                nxt.taskpool.addto_nb_tasks(-1)      # as _worker_main
                continue
            entry, why = None, "class"
            its = self._group_chore(nxt) if nxt.taskpool is tp else None
            if its is not None and many and \
                    self.devices.preferred(nxt) not in (None, dev):
                # the tile it writes lies on another chip: never
                # launched here, it waits for that module's turn
                its, why = None, "module"
            if its is not None:
                entry = bins.get(its[1])
                if entry is None:
                    room = dev.group_limit(nxt, its[0])
                    if room:
                        entry = bins[its[1]] = (its[0], [], room)
            if entry is None:
                es.next_task = nxt
                end = why
                break
            es.stats["selected"] += 1
            entry[1].append(nxt)
            taken += 1
            if len(entry[1]) >= entry[2]:
                break
        if self.stage_timers:
            es.stats["group_end_" + end] += 1
            es.stats["group_bins"] += len(bins)
        return [(c, tasks) for c, tasks, _ in bins.values()]

    def _group_progress(self, es: ExecutionStream, task: Task,
                        found: Tuple[Chore, Tuple]) -> None:
        """``_task_progress`` of ``task`` (``found`` is its
        ``_group_chore``) and the ready tasks of its taskpool this worker
        can select: every one is prepared, announced and completed
        exactly once, as alone, and all of them in this pass. The device
        module says how many one launch may carry and what a launch
        holds: the workers take turns, each holding one task until its
        turn, and the turn covers the take and every launch of its bins
        (prepare, staging, the module's wait for the chip, the jitted
        call). The bins that fill a size are launched in that turn, in
        the order their first task was selected. What a launch made
        anew waits on the device for its members' release, so a group
        that holds new outputs releases its members inside the turn, one
        group in flight; the members of a group that holds nothing new
        (it wrote where its tiles lie) are released once the turn is
        given up, while the next worker stages and calls. The tasks of
        the bins too small for a group go alone after those, as a task
        that was never taken would. A launch or a release that raises
        leaves the worker's handler to abort the pool, every load
        released and the turn free."""
        dev = self.devices.device_for(found[0].device_type, task)
        limit = dev.group_limit(task, found[0]) if dev is not None else 0
        alone, held, launched = [task], 1, []
        try:
            if limit:
                self._take_turn(es, dev)
                try:
                    bins = self._take_group(es, task, found, dev, limit)
                    held = sum(len(tasks) for _, tasks in bins)
                    dev.add_load(held - 1)
                    alone = []
                    for c, tasks in bins:
                        if len(tasks) >= GROUP_SIZES[-1]:
                            self._group_launch(es, tasks, c, dev, launched)
                        else:
                            alone += tasks
                finally:
                    dev.group_turn.release()
        finally:
            if dev is not None:
                dev.release_load(held)
        launched.reverse()      # in place: released with the turn free,
        while launched:         # and a member goes with its release
            self.complete_task(es, launched.pop())
        for task in alone:      # too few, or a module without groups
            self._task_progress(es, task)

    def _take_turn(self, es: ExecutionStream, dev) -> None:
        """Take ``dev``'s turn; under its span where the stage timers
        are on: the acquisition alone, a worker that holds a ready task
        and waits while another takes, launches or, where its group
        holds new outputs, releases."""
        if not self.stage_timers:
            dev.group_turn.acquire()
            return
        with StageSpan(SPAN_TURN) as span:
            dev.group_turn.acquire()
        es.stats["turn_s"] += span.seconds

    def _group_launch(self, es: ExecutionStream, tasks: List[Task],
                      chore: Chore, dev, later: List[Task]) -> None:
        """A bin of one chore through ``dev``, inside its turn: the
        members of a launch that holds new outputs are released before
        the next launch, those of one that holds none go to ``later``,
        for the caller to release once the turn is given up."""
        for task in tasks:      # a dispatch span per task, as alone
            self._timed_dispatch(es, self._prepare_input, es, task)
        shared = chore.batch_hook_shared
        if shared:
            # a stacked form takes ONE object for an operand its group
            # shares: the members that hold the same ones side by side,
            # in the order their first was selected (two rows of TSMQRs
            # become ready together, each with its own V2 and T)
            seen: Dict[Tuple[int, ...], int] = {}
            tasks.sort(key=lambda t: seen.setdefault(
                tuple(id(t.data.get(name)) for name in shared), len(seen)))
        done = 0
        while done < len(tasks):
            # the largest group the module can make of them, never
            # padded
            n, new_bytes = dev.execute_group(es, tasks[done:], chore)
            if n:
                for task in tasks[done:done + n]:
                    self._mark_exe(es, task)
                    self._count_advised(es, task, dev)
                    if new_bytes:
                        self.complete_task(es, task)
                    else:
                        later.append(task)
            else:               # the module sends this one alone
                n = 1
                self._executed(es, tasks[done],
                               self._execute(es, tasks[done]))
            # a member goes with its release, and its inputs with it
            tasks[done:done + n] = [None] * n
            done += n

    def _dispatch(self, es: ExecutionStream, task: Task) -> HookReturn:
        self._prepare_input(es, task)
        # execute: walk incarnations honoring the chore mask
        return self._execute(es, task)

    def _prepare_input(self, es: ExecutionStream, task: Task) -> None:
        # prepare_input (generated data_lookup analog): resolve inputs not
        # attached by the release path (collection reads of startup tasks)
        task.status = TaskStatus.PREPARE_INPUT
        lookup = getattr(task.task_class, "data_lookup", None)
        if lookup is not None:
            self.pins.prepare_input_begin(es, task)
            lookup(task)
            self.pins.prepare_input_end(es, task)
        task.status = TaskStatus.HOOK
        self.pins.exec_begin(es, task)

    @staticmethod
    def _mark_exe(es, task: Task) -> None:
        if debug_history.enabled():     # DEBUG_MARK_EXE analog
            debug_history.mark("EXE %s%r es=%s", task.task_class.name,
                               tuple(task.locals),
                               getattr(es, "th_id", -1))

    def _execute(self, es: ExecutionStream, task: Task) -> HookReturn:
        """__parsec_execute analog (scheduling.c:124-203): try incarnations
        in declaration order, skipping masked/vetoed ones."""
        tc = task.task_class
        self._mark_exe(es, task)
        for i, chore in enumerate(tc.incarnations):
            if not (task.chore_mask & (1 << i)):
                continue
            if chore.evaluate is not None and not chore.evaluate(task):
                continue
            dev = self.devices.device_for(chore.device_type, task)
            if dev is None:
                continue
            rc = None
            try:
                rc = dev.execute(es, task, chore)
            finally:
                if rc != HookReturn.ASYNC:
                    # async devices keep their in-flight unit until the
                    # manager completes the task (release_load); every
                    # other outcome — including a raising hook — must
                    # release here or the device leaks load forever
                    dev.release_load()
            if rc == HookReturn.NEXT:
                task.chore_mask &= ~(1 << i)
                continue
            self._count_advised(es, task, dev)
            return rc
        return HookReturn.ERROR

    def _count_advised(self, es: ExecutionStream, task: Task, dev) -> None:
        """With several chip modules: ``task`` was launched on
        ``dev``; did the tile it writes have a module advised, and is
        that ``dev``? (``es.stats["tasks_advised"]``,
        ``["tasks_on_advised"]``; one test with one chip.)"""
        if len(self.devices.chips) > 1:
            home = self.devices.preferred(task)
            if home is not None:
                es.stats["tasks_advised"] += 1
                es.stats["tasks_on_advised"] += home is dev

    def _hbm_track(self, dc, key, value):
        """Register a device-resident tile a task is writing to its
        collection; over budget, the manager spills the coldest tracked
        tile back into its collection as host numpy. Called BEFORE the
        collection write with the entry PINNED (caller unpins after the
        write): the manager always holds the newest version AND cannot
        evict it inside the track→write window, where the spill's host
        write would race the device write (budget under-enforcement).
        Returns the key to unpin, or None when untracked."""
        from ..device.hbm import track_collection_write
        return track_collection_write(self.hbm, dc, key, value)

    def _merge_region(self, ref: DataRef) -> None:
        """The write-back of one region of a tile (``Out(region=...)``):
        merged into the tile the collection holds, in that tile's
        buffer, and counted on the chip module it lies on
        (``region_merges``)."""
        merged = ref.collection.merge_tile(ref.key, ref.value, ref.region)
        if self.hbm is not None:
            mkey = self._hbm_track(ref.collection, ref.key, merged)
            if mkey is not None:
                self.hbm.unpin(mkey)
        where = getattr(merged, "device", None)
        for dev in self.devices.chips:
            if dev.jax_device == where:
                with dev._lock:
                    dev.stats["region_merges"] += 1

    def complete_task(self, es: Optional[ExecutionStream], task: Task) -> None:
        """__parsec_complete_execution + release_deps analog
        (scheduling.c:441-470, parsec.c:1694-1921)."""
        task.status = TaskStatus.COMPLETE
        tp = task.taskpool
        if es is not None:
            es.stats["executed"] += 1
        else:
            # device-manager completion (ASYNC contract): attribute
            # here so TASKS_EXECUTED still covers every task
            with self._lock:
                self.stats["device_completed"] = \
                    self.stats.get("device_completed", 0) + 1
        self.pins.exec_end(es, task)
        self.pins.complete_exec_begin(es, task)
        if self.trace is not None:
            self.trace.task_complete(task)
        if self.grapher is not None:
            self.grapher.task_executed(task)

        self.pins.release_deps_begin(es, task)
        if self.stage_timers:
            # a completion by a device manager (es is None) has its span
            # too; only the es.stats sum needs a stream
            with StageSpan(SPAN_RELEASE) as span:
                self._release_deps(es, task)
            if es is not None:
                es.stats["release_s"] += span.seconds
        else:
            self._release_deps(es, task)
        self.pins.release_deps_end(es, task)
        self.pins.complete_exec_end(es, task)
        # the always-on metrics plane adds NO hot-path work here: the
        # per-stream es.stats["executed"] counters above already exist,
        # and the registry exports their sum as
        # parsec_tasks_completed_total at SCRAPE time (collector)
        tp.addto_nb_tasks(-1)
        # no task mempool here BY MEASUREMENT (round 5, PARITY
        # "Mempools" row): completed tasks die young via refcounting
        # (~0.7 µs/task); a prototyped per-thread freelist measured
        # BREAK-EVEN warm (0.94 µs pop+reset) and cannot reduce the
        # live-object count that drives GC pressure in startup bursts.
        # The reference's mempool.c amortizes C malloc, which CPython's
        # refcounting already covers. Native-path tasks use pmempool_*.

    def _release_deps(self, es: Optional[ExecutionStream],
                      task: Task) -> None:
        """parsec_release_dep_fct analog (parsec.c:1783-1921): walk the
        successors, count their dependencies down, schedule the ready
        ones."""
        tp = task.taskpool
        tc = task.task_class
        ready: List[Task] = []
        # local refs accumulate and release in ONE striped-lock batch
        # (parsec_release_dep_fct walks its ready-ring the same way)
        # instead of a lock pair per dep
        local_refs: List[SuccessorRef] = []
        # remote deps sharing one produced value ship the payload ONCE
        # per rank (the reference's one-data-per-(dep, rank) aggregation,
        # remote_dep.c) — grouped per VALUE here so the engine can also
        # tree-route a value with consumers on >=2 ranks down a
        # broadcast topology (remote_dep_broadcast) instead of paying
        # one root egress per rank
        remote_groups: Optional[Dict[int, Dict[int, List]]] = \
            {} if self.nb_ranks > 1 else None
        san = self.dfsan
        grapher = self.grapher
        successors = tc.iterate_successors(task)
        if self.stage_timers and tc.unfold_span is not None:
            # the front end's share of release: guards, target lambdas,
            # priorities of the whole successor list, before any of it is
            # counted down or scheduled
            with StageSpan(tc.unfold_span) as span:
                successors = list(successors)
            if es is not None:
                es.stats["unfold_s"] += span.seconds
        if tc.ranged and es is not None:
            # a ranged flow's list leaves element by element, one
            # activation each; its consumer is scheduled once, by the
            # activation that completes its count (activate_deps)
            successors = list(successors)
            es.stats["ranged_scatters"] += sum(
                getattr(ref, "element", None) is not None
                for ref in successors)
        for ref in successors:
            if isinstance(ref, DataRef):
                if ref.region is not None:
                    self._merge_region(ref)
                    continue
                # track (pinned) first, write second, unpin last — see
                # _hbm_track
                mkey = None
                if self.hbm is not None:
                    mkey = self._hbm_track(ref.collection, ref.key,
                                           ref.value)
                if san is not None:
                    # stamp the committed version BEFORE it lands so a
                    # racing reader's check sees the writer's clock
                    san.observe_write(task, ref.collection, ref.key)
                ref.collection.write_tile(ref.key, ref.value)
                if mkey is not None:
                    self.hbm.unpin(mkey)
                continue
            if san is not None:
                # happens-before edge task -> successor, observed BEFORE
                # the dep is counted (the successor may run immediately)
                san.observe_edge(task, ref)
            if grapher is not None:
                grapher.dep_edge(task, ref.task_class, ref.locals,
                                 ref.flow_name)
            if ref.reshape_spec is not None or \
                    isinstance(ref.value, DataCopyFuture):
                # reshape promise: one shared conversion per layout
                # (parsec_local_reshape analog, runs on this compute
                # thread; remote consumers get the converted value)
                ref.value = resolve_reshape(ref.value, ref.reshape_spec)
                ref.reshape_spec = None
            if remote_groups is not None:
                target_rank = ref.task_class.affinity_rank(ref.locals) \
                    if hasattr(ref.task_class, "affinity_rank") else self.my_rank
                if target_rank != self.my_rank:
                    remote_groups.setdefault(
                        id(ref.value), {}).setdefault(
                            target_rank, []).append(ref)
                    continue
            local_refs.append(ref)
        if local_refs:
            ready.extend(tp.activate_deps(local_refs))
        if remote_groups:
            for _vid, rank_refs in remote_groups.items():
                first = next(iter(rank_refs.values()))[0]
                if self._comm_bcast and len(rank_refs) >= 2 and \
                        first.value is not None:
                    # one value, consumers on >=2 ranks: tree-routed
                    # broadcast (payload leaves this rank once per tree
                    # edge, not once per consumer rank)
                    self.comm.remote_dep_broadcast(task, rank_refs)
                else:
                    for target_rank, refs in rank_refs.items():
                        self.comm.remote_dep_activate_multi(
                            task, target_rank, refs)
        if self._track_completed:
            # lineage record: survivors report these after a peer death
            # so replay recomputes only the unfinished sub-DAG
            tp.completed_tasks.add((tc.name, tuple(task.locals)))
        if self._fault is not None:
            self._fault.on_task_complete()   # injected failure point
        if tc.on_complete is not None:
            tc.on_complete(task)
        if task.on_complete is not None:
            task.on_complete(task)
        if ready and self.trace is not None:
            # causal parent of everything this completion released: the
            # local dependency edges of the request span tree (wire
            # edges are parented by the comm engine's _span_recv). The
            # ready→select queue-wait stamp (q_us on the released
            # task's begin event) shares this loop — one perf_counter,
            # no separate pass in schedule().
            b = task.prof.get("b")      # (span id, t0, stream) — the
            if b is not None:           # trace hook's fused begin stamp
                sid = b[0]
                rid = task.prof.get("rid")
                now = time.perf_counter()
                for t in ready:
                    p = t.prof
                    p["parent_span"] = sid
                    p["q_t0"] = now
                    if rid is not None:
                        p["rid"] = rid
        if ready:
            if self._bypass_chain and es is not None and \
                    es.next_task is None:
                # bypass-slot chaining: the completing task's best
                # successor never touches the queues — the worker loop
                # runs it next (scheduling.c:346-398). max() takes the
                # FIRST maximal task, matching the old stable
                # sort+pop(0) tie-break exactly.
                best = max(ready, key=lambda t: t.priority)
                ready.remove(best)
                es.next_task = best
            if ready:
                self.schedule(es, ready)


class _SnapshotCollection:
    """A frozen (key → value-reference) cut of one collection, captured
    synchronously at a quiesce point; quacks enough like a
    DataCollection for CheckpointManager.save to serialize it from the
    background saver thread."""

    def __init__(self, items: Dict):
        self._items = items

    def keys(self):
        return list(self._items)

    def is_local(self, _key) -> bool:
        return True         # pre-filtered at capture

    def data_of(self, key):
        return self._items[key]


class _CkptState:
    """Per-context periodic-checkpoint driver (Context.enable_checkpoints).

    Reference capture is synchronous (cheap: ``write_tile`` REPLACES
    tile references rather than mutating arrays, so holding the old
    references is a consistent cut even while the next taskpool runs);
    serialization runs on a daemon saver thread using the atomic-rename
    protocol, so a crash mid-save never corrupts the latest durable
    step. If the saver is still busy at the next due point the save is
    skipped with a warning (the async saver falling behind must not
    stall the runtime)."""

    def __init__(self, mgr, collections: Dict, interval: int,
                 interval_s: float, keep: int = 2):
        self.mgr = mgr
        self.collections = collections
        self.interval = int(interval)
        self.interval_s = float(interval_s)
        self.keep = keep
        self.pools_done = 0
        self._last_pools = 0
        self._last_t = time.monotonic()
        self.saves = 0
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()

    def _capture(self) -> Dict[str, _SnapshotCollection]:
        snap = {}
        for name, dc in self.collections.items():
            items = {}
            for key in dc.keys():
                if hasattr(dc, "is_local") and not dc.is_local(key):
                    continue
                val = dc.data_of(key)
                if val is not None:
                    items[key] = val
            snap[name] = _SnapshotCollection(items)
        return snap

    def _save(self, step: int, snap: Dict) -> Optional[str]:
        try:
            path = self.mgr.save(step, snap,
                                 meta={"pools_done": step})
            self.saves += 1
            if self.keep:
                self.mgr.prune(keep=self.keep)
            return path
        except Exception as exc:  # noqa: BLE001 — saver must not kill
            warning("checkpoint", "async save of step %d failed: %s",
                    step, exc)
            return None

    def quiesce_point(self) -> None:
        with self._lock:
            self.pools_done += 1
            due = (self.interval > 0 and
                   self.pools_done - self._last_pools >= self.interval)
            if not due and self.interval_s > 0:
                due = time.monotonic() - self._last_t >= self.interval_s
            if not due:
                return
            if self._thread is not None and self._thread.is_alive():
                warning("checkpoint", "saver still writing step at "
                        "quiesce %d — skipping this interval",
                        self.pools_done)
                return
            step = self.pools_done
            snap = self._capture()       # synchronous: consistent cut
            self._last_pools = self.pools_done
            self._last_t = time.monotonic()
            t = threading.Thread(target=self._save, args=(step, snap),
                                 name="parsec-ckpt", daemon=True)
            self._thread = t
            t.start()

    def save_now(self) -> Optional[str]:
        with self._lock:
            t = self._thread
        if t is not None:
            t.join()
        with self._lock:
            step = max(self.pools_done, 1)
            snap = self._capture()
            self._last_pools = self.pools_done
            self._last_t = time.monotonic()
        return self._save(step, snap)

    def wait(self, timeout: Optional[float] = None) -> bool:
        with self._lock:
            t = self._thread
        if t is None:
            return True
        t.join(timeout)
        return not t.is_alive()


def _weakly(method):
    """``method`` (a bound method of no arguments) as a callable that
    does not keep its object: a call after the object is gone does
    nothing."""
    ref = weakref.WeakMethod(method)

    def call():
        live = ref()
        if live is not None:
            live()

    return call


def _hbm_entry_dead(_key, entry) -> bool:
    """True when a context-tracked HBM entry's collection weakref (the
    first weakref default of its spill closure) is dead."""
    spill = entry.get("spill")
    for d in getattr(spill, "__defaults__", None) or ():
        if isinstance(d, weakref.ref):
            return d() is None
    return False


def init(nb_cores: Optional[int] = None, scheduler: Optional[str] = None,
         comm=None, argv: Optional[Sequence[str]] = None) -> Context:
    """parsec_init analog. ``argv`` (if given) is parsed for runtime
    options (--mca/--cores/--vpmap/--sched/...; parsec.c:411-463) before
    the context is built; leftover arguments are stored on
    ``context.argv_rest``."""
    rest = None
    if argv is not None:
        from ..utils import cmd_line
        rest = cmd_line.parse(list(argv))
    ctx = Context(nb_cores=nb_cores, scheduler=scheduler, comm=comm)
    ctx.argv_rest = rest
    return ctx


def fini(context: Context) -> None:
    """parsec_fini analog."""
    context.fini()
