"""PINS instrumentation modules.

Reference: parsec/mca/pins/ — modules hook the runtime's callback chains
(pins.h:26-53) per execution stream. The reference ships task_profiler
(writes task begin/end to the trace), print_steals (per-stream steal
counters), alperf (per-class activity/performance), iterators_checker
(runtime sanity of successor iterators) and papi (hardware counters —
analog here: the ``counters`` module below, rusage-backed since this
environment has no PAPI and no portable TPU hardware counters; the
SDE-style software counters live in profiling.sde). Modules are
selected MCA-style via the ``pins`` param (comma-separated names) and
installed at context init.
"""

from __future__ import annotations

import threading
import time
from collections import defaultdict
from typing import Any, Dict, List, Optional

from .pins import PinsEvent
from ..utils import mca_param
from ..utils.debug import debug_verbose

mca_param.register("pins", "",
                   help="comma-separated PINS modules to install at init "
                        "(task_profiler, print_steals, alperf, "
                        "iterators_checker, counters, overhead, tenant, "
                        "straggler, dfsan)")
mca_param.register("profiling.straggler_factor", 3.0,
                   help="straggler watchdog: flag a task instance whose "
                        "body time exceeds its class's rolling p99 "
                        "times this factor")
mca_param.register("profiling.straggler_window", 256,
                   help="straggler watchdog: rolling per-class sample "
                        "window the p99 is estimated over")
mca_param.register("profiling.straggler_min_samples", 20,
                   help="straggler watchdog: observations of a class "
                        "before flagging starts (a cold p99 estimate "
                        "flags compile warmup, not stragglers)")


class PinsModule:
    """Base module: ``install(context)`` subscribes to the PINS chains,
    ``uninstall()`` removes the subscriptions, ``report()`` returns the
    collected data (reference modules print at component close).

    ``native_ok`` (per subscription) is the ISSUE 13 classification:
    ``True`` = the observer has a native-engine equivalent or only
    reads state at scrape time, so it does not force DTD pools onto the
    instrumented Python path; ``"trace"`` = native-ok only while a live
    Trace snapshots the engine rings for it (``observe_native_rings``
    is then fed at pool retirement); ``False`` (default) = a per-task
    Python observer — pools stay on the Python engine."""

    name = "module"

    def __init__(self) -> None:
        self.context = None
        self._subs: List = []    # (event, cb) pairs for uninstall

    def _sub(self, event: PinsEvent, cb, native_ok: object = False) -> None:
        self.context.pins.register(event, cb, native_ok=native_ok)
        self._subs.append((event, cb))

    def install(self, context) -> "PinsModule":
        self.context = context
        return self

    def uninstall(self) -> None:
        for event, cb in self._subs:
            self.context.pins.unregister(event, cb)
        self._subs.clear()

    def report(self) -> Dict[str, Any]:
        return {}


class TaskProfiler(PinsModule):
    """mca/pins/task_profiler analog: records task begin/end into the
    context trace (creating one if absent)."""

    name = "task_profiler"

    def install(self, context) -> "TaskProfiler":
        super().install(context)
        from .trace import Trace
        self._installed_trace = context.trace is None
        if context.trace is None:
            Trace().install(context)
        self.trace = context.trace
        if self._installed_trace:
            # Trace.install registered this outside our bookkeeping — adopt
            # it so uninstall() stops the event flow; a user-installed
            # trace keeps its own subscription
            self._subs.append((PinsEvent.EXEC_BEGIN, self.trace.task_begin))
        return self

    def uninstall(self) -> None:
        super().uninstall()
        if self._installed_trace and self.context.trace is self.trace:
            self.context.trace = None   # stop task_complete recording too
            # Trace.install also hooked the comm engine's msg-size
            # instrumentation — detach it, or the engine keeps recording
            # into the dead trace after fini
            if (self.context.comm is not None and
                    getattr(self.context.comm, "_trace", None) is self.trace):
                self.context.comm.install_trace(None)

    def report(self) -> Dict[str, Any]:
        return self.trace.counts()


class PrintSteals(PinsModule):
    """mca/pins/print_steals analog: per-stream counts of tasks obtained
    by stealing (from a VP peer or the system overflow queue). The
    counters themselves are maintained by the local-queue schedulers in
    ``es.stats["stolen"]``; this module snapshots and reports them."""

    name = "print_steals"

    def report(self) -> Dict[int, Dict[str, int]]:
        return {es.th_id: {"selected": es.stats.get("selected", 0),
                           "stolen": es.stats.get("stolen", 0)}
                for es in self.context.streams}

    def print(self) -> None:
        for th_id, row in sorted(self.report().items()):
            debug_verbose(0, "pins", "stream %d: %d selected, %d stolen",
                          th_id, row["selected"], row["stolen"])


class Alperf(PinsModule):
    """mca/pins/alperf analog: per-task-class activity counters —
    executions and cumulative body time."""

    name = "alperf"

    def install(self, context) -> "Alperf":
        super().install(context)
        self._lock = threading.Lock()
        self._stats: Dict[str, Dict[str, float]] = defaultdict(
            lambda: {"count": 0, "time_s": 0.0})
        self._sub(PinsEvent.EXEC_BEGIN, self._begin)
        self._sub(PinsEvent.EXEC_END, self._end)
        return self

    def _begin(self, es, task) -> None:
        task.prof["alperf_t0"] = time.perf_counter()

    def _end(self, es, task) -> None:
        t0 = task.prof.pop("alperf_t0", None)
        dt = 0.0 if t0 is None else time.perf_counter() - t0
        with self._lock:
            row = self._stats[task.task_class.name]
            row["count"] += 1
            row["time_s"] += dt

    def report(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {k: dict(v) for k, v in self._stats.items()}


class IteratorsChecker(PinsModule):
    """mca/pins/iterators_checker analog: at release time, re-runs the
    completed task's ``iterate_successors`` and validates every ref —
    target class belongs to the same taskpool, the named flow exists,
    the dep bit matches the flow, and (for PTG classes, where the task
    space is closed-form) the target instance exists. Violations raise,
    failing the run loudly the way the reference module aborts."""

    name = "iterators_checker"

    def install(self, context) -> "IteratorsChecker":
        super().install(context)
        self.checked = 0
        self._lock = threading.Lock()
        self._space_cache: Dict[Any, set] = {}
        self._sub(PinsEvent.RELEASE_DEPS_BEGIN, self._check)
        return self

    def _space_of(self, tc) -> Optional[set]:
        if not hasattr(tc, "enumerate_space"):
            return None
        with self._lock:
            space = self._space_cache.get(tc)
            if space is None:
                space = self._space_cache[tc] = set(tc.enumerate_space())
        return space

    def _check(self, es, task) -> None:
        from ..core.taskpool import DataRef, SuccessorRef
        tc = task.task_class
        # DTD successor lists are consumed-once runtime state, not a pure
        # closed-form iterator — only PTG-style classes can be re-iterated
        if not hasattr(tc, "enumerate_space"):
            return
        tp = task.taskpool
        for ref in tc.iterate_successors(task):
            if isinstance(ref, DataRef):
                if ref.collection is None:
                    raise AssertionError(
                        f"{task!r}: DataRef with no collection")
                continue
            assert isinstance(ref, SuccessorRef)
            dst = ref.task_class
            if dst not in tp.task_classes:
                raise AssertionError(
                    f"{task!r} -> {dst.name}: class not in taskpool")
            flow = dst.flow_by_name.get(ref.flow_name)
            if flow is None:
                raise AssertionError(
                    f"{task!r} -> {dst.name}.{ref.flow_name}: no such flow")
            if ref.dep_index != flow.index:
                raise AssertionError(
                    f"{task!r} -> {dst.name}.{ref.flow_name}: dep bit "
                    f"{ref.dep_index} != flow index {flow.index}")
            if len(ref.locals) != len(dst.params):
                raise AssertionError(
                    f"{task!r} -> {dst.name}{ref.locals}: arity "
                    f"{len(ref.locals)} != {len(dst.params)} params")
            space = self._space_of(dst)
            if space is not None and tuple(ref.locals) not in space:
                raise AssertionError(
                    f"{task!r} -> {dst.name}{tuple(ref.locals)}: target "
                    f"instance outside the task space")
        with self._lock:
            self.checked += 1

    def report(self) -> Dict[str, int]:
        return {"tasks_checked": self.checked}


class Counters(PinsModule):
    """mca/pins/papi analog (pins_papi.c:1-592): read a counter set at
    EXEC begin/end per execution stream and accumulate the deltas per
    task class. This environment exposes no PAPI and no portable TPU
    hardware counters (PARITY.md N/A table), so the counter source is
    ``resource.getrusage(RUSAGE_THREAD)`` — per-thread CPU time, page
    faults and context switches — plus the monotonic clock. The
    frame structure matches the reference module: sample at begin,
    delta at end, aggregate per (class, counter)."""

    name = "counters"

    #: counter name -> rusage attribute
    _FIELDS = {
        "utime_s": "ru_utime",
        "stime_s": "ru_stime",
        "minflt": "ru_minflt",
        "majflt": "ru_majflt",
        "nvcsw": "ru_nvcsw",
        "nivcsw": "ru_nivcsw",
    }

    def __init__(self) -> None:
        super().__init__()
        self._begin: Dict[int, tuple] = {}      # task id -> sample
        self.totals: Dict[str, Dict[str, float]] = defaultdict(
            lambda: defaultdict(float))
        self._lock = threading.Lock()

    @staticmethod
    def _sample():
        import resource
        who = getattr(resource, "RUSAGE_THREAD", resource.RUSAGE_SELF)
        return (resource.getrusage(who), time.perf_counter(),
                threading.get_ident())

    def install(self, context) -> "Counters":
        super().install(context)
        self._sub(PinsEvent.EXEC_BEGIN, self._exec_begin)
        self._sub(PinsEvent.EXEC_END, self._exec_end)
        return self

    def _exec_begin(self, es, task) -> None:
        self._begin[id(task)] = self._sample()

    def _exec_end(self, es, task) -> None:
        b = self._begin.pop(id(task), None)
        if b is None:
            return
        (ru0, t0, tid0), (ru1, t1, tid1) = b, self._sample()
        key = task.task_class.name
        with self._lock:
            tot = self.totals[key]
            tot["tasks"] += 1
            tot["wall_s"] += t1 - t0
            if tid0 != tid1:
                # ASYNC completion (a device that completes later): END
                # fires on a different thread, so a RUSAGE_THREAD delta
                # would subtract one thread's counters from another's.
                # Only wall time is cross-thread meaningful.
                tot["async_tasks"] += 1
                return
            for cname, attr in self._FIELDS.items():
                # ru_utime/ru_stime are float seconds in Python's
                # resource module; the rest are ints
                tot[cname] += float(getattr(ru1, attr) -
                                    getattr(ru0, attr))

    def report(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {k: dict(v) for k, v in self.totals.items()}


class OverheadProfiler(PinsModule):
    """Per-stage runtime-overhead timers: insert (DTD insertion, on the
    inserting thread), select (scheduler select), dispatch
    (prepare_input + incarnation walk + hook call) and release
    (release-deps: successor iteration, dependency countdown,
    scheduling). The timers themselves live in the runtime hot loops
    behind ``context.stage_timers`` (one attribute test when off —
    ``runtime.stage_timers`` MCA param); this module asks for the flag
    on install and aggregates the collected stream/taskpool counters
    into the per-task overhead budget the taskrate bench reports. The
    same sites open the ``parsec:`` spans of a profiler trace
    (``core.context.StageSpan``).

    Reported seconds are THREAD seconds (summed across workers): with W
    busy workers, per-task wall overhead is roughly the per-task thread
    time / W."""

    name = "overhead"

    def install(self, context) -> "OverheadProfiler":
        super().install(context)
        self._prev_flag = context.set_stage_timers(True)
        return self

    def uninstall(self) -> None:
        super().uninstall()
        self.context.set_stage_timers(self._prev_flag)

    def report(self) -> Dict[str, Any]:
        # unfold_s is the part of release_s a closed-form front end (PTG)
        # spent evaluating successor lists; 0.0 for a DTD pool
        agg = {"select_s": 0.0, "select_calls": 0, "dispatch_s": 0.0,
               "release_s": 0.0, "unfold_s": 0.0, "executed": 0}
        for es in self.context.streams:
            for k in agg:
                agg[k] += es.stats.get(k, 0)
        # insertion: what the pools that ended folded into the
        # Context (a finished pool is not kept for its counters), and
        # the pools still running
        ended = self.context.dtd_counters
        agg["insert_s"] = ended.get("dtd_insert_s", 0.0)
        agg["insert_calls"] = ended.get("dtd_insert_calls", 0)
        with self.context._lock:
            pools = list(self.context._active_taskpools)
        for tp in pools:
            agg["insert_s"] += getattr(tp, "insert_s", 0.0)
            agg["insert_calls"] += getattr(tp, "insert_calls", 0)
        n = max(agg["executed"], 1)
        agg["per_task_us"] = {
            "insert": round(agg["insert_s"] / max(agg["insert_calls"], 1)
                            * 1e6, 3),
            "select": round(agg["select_s"] / n * 1e6, 3),
            "dispatch": round(agg["dispatch_s"] / n * 1e6, 3),
            "release": round(agg["release_s"] / n * 1e6, 3),
        }
        return agg


class StragglerWatchdog(PinsModule):
    """Online straggler detection (the PINS-shaped watchdog the serving
    plane runs LIVE instead of post-mortem): per task class, body times
    feed a rolling window whose p99 is re-estimated every window/4
    observations; an instance exceeding ``p99 × profiling.
    straggler_factor`` (after ``profiling.straggler_min_samples``
    observations) is flagged — into the report, the always-on metrics
    registry (``parsec_stragglers_total{class}``), and a warning log.
    A uniform slowdown moves the p99 WITH the tasks, so the watchdog
    flags outliers (one wedged worker, one pathological input), not
    load."""

    name = "straggler"

    def install(self, context) -> "StragglerWatchdog":
        super().install(context)
        from collections import deque
        from . import metrics as metrics_mod
        self._deque = deque
        self._factor = float(mca_param.get(
            "profiling.straggler_factor", 3.0))
        self._window = max(int(mca_param.get(
            "profiling.straggler_window", 256)), 8)
        self._min = max(int(mca_param.get(
            "profiling.straggler_min_samples", 20)), 2)
        self._lock = threading.Lock()
        # class -> [window deque, seen count, cached p99 (None = stale)]
        self._rows: Dict[str, list] = {}
        self.flagged: List[Dict[str, Any]] = []
        self._m_flagged = metrics_mod.registry().counter(
            "parsec_stragglers_total",
            "task instances flagged by the straggler watchdog "
            "(body time > rolling p99 x profiling.straggler_factor)",
            ("class",)) if metrics_mod.enabled() else None
        # native_ok="trace": with a live Trace the watchdog is fed the
        # native engine's ring records at pool retirement
        # (observe_native_rings) — near-live for the one-pool-per-
        # request serving shape; without a trace there is no native
        # data source, so the pool stays on the Python path
        self._sub(PinsEvent.EXEC_BEGIN, self._begin, native_ok="trace")
        self._sub(PinsEvent.EXEC_END, self._end, native_ok="trace")
        return self

    def _begin(self, es, task) -> None:
        task.prof["straggler_t0"] = time.perf_counter()

    @staticmethod
    def _p99(samples) -> float:
        s = sorted(samples)
        return s[min(int(len(s) * 0.99), len(s) - 1)]

    def _end(self, es, task) -> None:
        t0 = task.prof.pop("straggler_t0", None)
        if t0 is None:
            return
        self._observe(task.task_class.name, time.perf_counter() - t0,
                      list(task.locals))

    def _observe(self, cls: str, dt: float, locals_: List) -> None:
        """ONE detection rule for both paths (live EXEC hooks and the
        native ring feed): min-samples gate, window//4 p99
        re-estimation, flag shape, counter, log — a one-sided tuning
        edit cannot diverge the engines' straggler behavior."""
        flag = None
        with self._lock:
            row = self._rows.get(cls)
            if row is None:
                row = self._rows[cls] = [
                    self._deque(maxlen=self._window), 0, None]
            win, seen, p99 = row
            if seen >= self._min:
                if p99 is None or seen % max(self._window // 4, 1) == 0:
                    p99 = row[2] = self._p99(win)
                if dt > p99 * self._factor:
                    flag = {"class": cls,
                            "locals": locals_,
                            "body_s": round(dt, 6),
                            "p99_s": round(p99, 6),
                            "factor": round(dt / max(p99, 1e-12), 2)}
                    self.flagged.append(flag)
            win.append(dt)
            row[1] = seen + 1
        if flag is not None:
            if self._m_flagged is not None:
                self._m_flagged.labels(**{"class": cls}).inc()
            debug_verbose(1, "pins",
                          "straggler: %s%r body %.3f ms > p99 %.3f ms "
                          "x %.1f", cls, tuple(locals_),
                          flag["body_s"] * 1e3, flag["p99_s"] * 1e3,
                          self._factor)

    def observe_native_rings(self, arrays, class_names) -> None:
        """Ring-fed native path (ISSUE 13): a natively-executed pool's
        body durations (select→completion from the in-engine event
        rings) arrive in bulk when the rings are snapshotted at pool
        retirement — near-live for the one-pool-per-request serving
        shape. Each record goes through the SAME per-observation rule
        as the live path (_observe), so an outlier inside the first
        fold is still flagged. The per-record Python cost is paid only
        at FOLD time and only with this module installed."""
        import numpy as np
        for a in arrays:
            durs = (a["t1_ns"].astype(np.int64) -
                    a["t0_ns"].astype(np.int64)) / 1e9
            cls_ids = a["cls"]
            seqs = a["seq"]
            for cid in np.unique(cls_ids):
                name = class_names[cid] if cid < len(class_names) \
                    else "dtd_task"
                mask = cls_ids == cid
                for d, s in zip(durs[mask].tolist(),
                                seqs[mask].tolist()):
                    self._observe(name, d, [s])

    def report(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "flagged": list(self.flagged),
                "classes": {cls: {"seen": row[1],
                                  "p99_s": (round(self._p99(row[0]), 6)
                                            if row[0] else None)}
                            for cls, row in self._rows.items()}}


class TenantAccounting(PinsModule):
    """Per-tenant service accounting for the multi-tenant serving
    runtime (ROADMAP item 4): executed tasks and cumulative body
    seconds attributed to each taskpool's ``tenant_name`` (pools
    outside the serving runtime land under ``(untenanted)``), merged
    with the wfq scheduler's per-pool selection counters when that
    scheduler is installed — the evidence that makes starvation
    measurable rather than anecdotal."""

    name = "tenant"

    def install(self, context) -> "TenantAccounting":
        super().install(context)
        from . import metrics as metrics_mod
        self._lock = threading.Lock()
        self._rows: Dict[str, Dict[str, float]] = defaultdict(
            lambda: {"tasks": 0, "body_s": 0.0})
        # unified counter surface: the rows ALSO land in the shared
        # metrics registry (live /metrics export); the per-instance
        # dict remains the isolated per-context view report() serves
        self._m_tasks = self._m_body = None
        if metrics_mod.enabled():
            self._m_tasks = metrics_mod.registry().counter(
                "parsec_tenant_tasks_total",
                "tasks executed per tenant", ("rank", "tenant"))
            self._m_body = metrics_mod.registry().counter(
                "parsec_tenant_body_seconds_total",
                "cumulative task-body seconds per tenant",
                ("rank", "tenant"))
        self._rank = str(context.my_rank)
        # native_ok: pools on the native DTD engine never fire these
        # hooks — their completions are folded per tenant at scrape
        # time from the engine's C++ atomics (report() / the context
        # metrics collector read Context.native_tenant_stats), so the
        # accounting module must not force the 12k/s Python path
        self._sub(PinsEvent.EXEC_BEGIN, self._begin, native_ok=True)
        self._sub(PinsEvent.EXEC_END, self._end, native_ok=True)
        return self

    @staticmethod
    def _tenant_of(task) -> str:
        return getattr(task.taskpool, "tenant_name", None) or \
            "(untenanted)"

    def _begin(self, es, task) -> None:
        task.prof["tenant_t0"] = time.perf_counter()

    def _end(self, es, task) -> None:
        t0 = task.prof.pop("tenant_t0", None)
        dt = 0.0 if t0 is None else time.perf_counter() - t0
        ten = self._tenant_of(task)
        with self._lock:
            row = self._rows[ten]
            row["tasks"] += 1
            row["body_s"] += dt
        if self._m_tasks is not None:
            self._m_tasks.labels(rank=self._rank, tenant=ten).inc()
            self._m_body.labels(rank=self._rank, tenant=ten).inc(dt)

    def report(self) -> Dict[str, Any]:
        with self._lock:
            out = {"tenants": {k: dict(v) for k, v in self._rows.items()}}
        # fold native-engine completions per tenant (ISSUE 13: native
        # pools bypass the EXEC hooks; the engine's atomics are the
        # truth — body_s stays Python-measured, native bodies may
        # never enter Python at all)
        for ten, n in self.context.native_tenant_stats().items():
            t = out["tenants"].setdefault(ten, {"tasks": 0,
                                                "body_s": 0.0})
            t["native_tasks"] = t.get("native_tasks", 0) + n
        sched = self.context.scheduler
        if hasattr(sched, "pool_stats"):
            # fold wfq's selection/backlog view in per tenant
            for row in sched.pool_stats().values():
                ten = row.get("tenant") or "(untenanted)"
                t = out["tenants"].setdefault(ten, {"tasks": 0,
                                                    "body_s": 0.0})
                t["selected"] = t.get("selected", 0) + row["selected"]
                t["pending"] = t.get("pending", 0) + row["pending"]
        return out


_MODULES = {
    "task_profiler": TaskProfiler,
    "print_steals": PrintSteals,
    "alperf": Alperf,
    "iterators_checker": IteratorsChecker,
    "counters": Counters,
    "overhead": OverheadProfiler,
    "tenant": TenantAccounting,
    "straggler": StragglerWatchdog,
}


def available() -> List[str]:
    return sorted(_MODULES) + ["dfsan"]


def new_module(name: str) -> PinsModule:
    if name == "dfsan":
        # the runtime race sanitizer lives in analysis/ (it is half of
        # the hazard-checker package, not a profiling concern); lazy
        # import also keeps pins_modules free of an import cycle
        from ..analysis.dfsan import DataflowSanitizer
        return DataflowSanitizer()
    try:
        return _MODULES[name]()
    except KeyError:
        raise ValueError(f"unknown PINS module {name!r}; have {available()}")


def install_selected(context) -> List[PinsModule]:
    """Install the modules named by the ``pins`` MCA param
    (mca/pins/pins_init.c analog)."""
    spec = str(mca_param.get("pins", "") or "")
    mods = []
    for name in filter(None, (s.strip() for s in spec.split(","))):
        mods.append(new_module(name).install(context))
    return mods
