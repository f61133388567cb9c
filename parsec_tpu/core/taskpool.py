"""Taskpool and task-class structures with dependency tracking.

Mirrors:
- ``parsec_taskpool_t`` (parsec_internal.h:119-161): a DAG instance with a
  task counter, termination-detection monitor, task-class array and
  per-class data repos; registered/looked up by id (parsec.c:2069-2171).
- ``parsec_task_class_t`` (parsec_internal.h:381-425): static description of
  a task type — params, flows, incarnations, and the vtable
  (iterate_successors, release_deps, make_key, ...).
- Dependency tracking (parsec.c:1503-1649): two strategies — a *counter*
  per waiting task, or a *mask* of input-dependency bits; both keyed by the
  task key in a hash table (``parsec_hash_find_deps``).

The release-deps path (parsec.c:1694-1921) is generalized here: a completed
task's class enumerates :class:`SuccessorRef`s; the taskpool counts down /
ORs in each satisfied dependency and constructs the successor task when its
goal is reached, attaching the flowing data values.
"""

from __future__ import annotations

import itertools
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

from .task import Chore, DeviceType, Flow, FlowAccess, Task
from ..utils.debug import debug_verbose

# Dependency-tracking strategies (reference jdf.h:88-91 dep-management modes)
DEPS_COUNTER = "counter"    # parsec_update_deps_with_counter (parsec.c:1554)
DEPS_MASK = "mask"          # parsec_update_deps_with_mask (parsec.c:1601)

from ..utils import mca_param as _mca_param
_mca_param.register(
    "runtime.native_deps", True,
    help="use the C++ dependency table when the native core is available")


@dataclass
class SuccessorRef:
    """One satisfied dependency flowing from a completed task to a successor.

    Produced by ``TaskClass.iterate_successors`` (the generated
    iterate_successors of jdf2c.c); consumed by ``Taskpool.activate_dep``.
    """
    task_class: "TaskClass"          # successor's class
    locals: Tuple[int, ...]          # successor's parameter assignment
    flow_name: str                   # successor's input flow receiving data
    value: Any = None                # payload (None for CTL deps)
    dep_index: int = 0               # input-dep bit for mask mode
    priority: int = 0
    src_flow: Optional[str] = None   # producer's flow (planners/native exec)
    reshape_spec: Any = None         # composed reshape (core/reshape.py);
                                     # resolved before the value fans out
    element: Optional[int] = None    # which element of the producer's
                                     # ranged flow it carries (a scatter)


class CancelledError(RuntimeError):
    """A taskpool was cancelled (deadline expiry or explicit
    Submission.cancel) — distinct from a body failure so serving-side
    waiters can tell 'your deadline passed' from 'your code crashed'."""


@dataclass
class DataRef:
    """A terminal output dependency: write a value back to a collection
    (the ``-> A(k, k)`` form of a JDF dep)."""
    collection: Any                  # data.collection.DataCollection
    key: Tuple[int, ...]
    value: Any = None
    # core.reshape.Region: ``value`` replaces that part of the tile the
    # collection holds, in the tile's own buffer; None: the whole tile
    region: Any = None


class TaskClass:
    """Static description of a task type (parsec_task_class_t analog).

    DSLs (PTG/DTD) construct instances and fill the vtable callables:

    - ``iterate_successors(task) -> Iterable[SuccessorRef | DataRef]``
    - ``deps_goal(locals) -> int`` — number of input deps (counter mode) or
      bitmask of input-dep indices (mask mode) that must be satisfied
    - ``make_key(locals)``, ``priority(locals)``
    """

    # stage span around the evaluation of a completed task's successor
    # list, inside parsec:release (Context._release_deps, stage timers
    # on); None where the list costs nothing worth a span of its own
    unfold_span: Optional[str] = None
    # does a flow of the class hold a list of tiles, gathered or
    # scattered element by element (dsl/ptg.py: ranged data flows)?
    ranged: bool = False

    def __init__(self, name: str, tc_id: int, params: Sequence[str],
                 flows: Sequence[Flow], deps_mode: str = DEPS_COUNTER):
        self.name = name
        self.tc_id = tc_id
        self.params = tuple(params)
        self.flows: List[Flow] = []
        for i, f in enumerate(flows):
            f.index = i
            self.flows.append(f)
        self.flow_by_name: Dict[str, Flow] = {f.name: f for f in self.flows}
        self.deps_mode = deps_mode
        self.incarnations: List[Chore] = []
        self.properties: Dict[str, Any] = {}
        # vtable — filled by the DSL layer
        self.iterate_successors: Callable[[Task], Iterable] = lambda task: ()
        self.deps_goal: Callable[[Tuple[int, ...]], int] = lambda locals: 0
        self.priority_fn: Callable[[Tuple[int, ...]], int] = lambda locals: 0
        self.time_estimate: Optional[Callable[[Task], float]] = None
        self.on_complete: Optional[Callable[[Task], None]] = None

    # -- vtable defaults ---------------------------------------------------
    def make_key(self, locals: Tuple[int, ...]) -> Tuple[int, Tuple[int, ...]]:
        return (self.tc_id, tuple(locals))

    def written_tile(self, task: Task):
        """``(collection, key)`` of the tile ``task`` writes, which a
        context with several chip modules places it by
        (``device.base.Registry.device_for``); None where the front end
        names none (the DSLs fill it in)."""
        return None

    def add_chore(self, chore: Chore) -> "TaskClass":
        self.incarnations.append(chore)
        return self

    def chore_for(self, device_type: DeviceType) -> Optional[Chore]:
        for c in self.incarnations:
            if c.device_type & device_type:
                return c
        return None

    @property
    def output_flows(self) -> List[Flow]:
        return [f for f in self.flows
                if (f.access & FlowAccess.WRITE) and not f.is_ctl]

    @property
    def input_flows(self) -> List[Flow]:
        return [f for f in self.flows
                if (f.access & FlowAccess.READ) and not f.is_ctl]

    def __repr__(self) -> str:
        return f"<TaskClass {self.name} id={self.tc_id}>"


class _PendingDeps:
    """Hash-table dependency tracking for not-yet-ready tasks.

    Entry per task key: satisfied counter/mask + accumulated input values.
    Reference: parsec_hash_find_deps (parsec.c:1525) + update functions.
    Striped locks stand in for the reference's bucket-locked hash table
    (class/parsec_hash_table.c).

    When the native core is available (parsec_tpu/_native), the
    counter/mask accounting runs in the C++ dependency table (pdep_*) on
    64-bit task keys — the same key model the reference uses
    (parsec_key_t) — while input values stay Python-side under the stripe
    locks. Each provider writes its value *before* counting, so whichever
    provider completes the goal observes every value (mutex ordering).
    """

    _NSTRIPES = 64

    def __init__(self) -> None:
        self._entries: Dict[Any, Dict[str, Any]] = {}
        self._locks = [threading.Lock() for _ in range(self._NSTRIPES)]
        # dfsan race sanitizer (analysis/dfsan.py): when installed, the
        # stripe locks report acquisition order so lock-order inversions
        # are flagged; None keeps the hot path a bare Lock
        self.sanitizer = None
        self._native = None
        self._native_lib = None
        from ..utils import mca_param
        if mca_param.get("runtime.native_deps", True):
            from .. import _native
            lib = _native.load()
            if lib is not None:
                self._native_lib = lib
                self._native = lib.pdep_new()

    def __del__(self):
        if getattr(self, "_native", None):
            self._native_lib.pdep_free(self._native)
            self._native = None

    def _lock_for(self, key) -> threading.Lock:
        return self._stripe_lock(hash(key) % self._NSTRIPES)

    def _stripe_lock(self, stripe: int):
        lock = self._locks[stripe]
        san = self.sanitizer
        if san is not None:
            return san.wrap_lock(lock, "pdep", stripe)
        return lock

    @staticmethod
    def _key64(key) -> int:
        return hash(key) & 0xFFFFFFFFFFFFFFFF

    def _pop_data(self, key, priority: int) -> Dict[str, Any]:
        with self._lock_for(key):
            ent = self._entries.pop(key, None)
        if ent is None:
            ent = {"data": {}, "priority": priority}
        ent["priority"] = max(ent["priority"], priority)
        return ent

    @staticmethod
    def _count_locked(ent: Dict[str, Any], key, flow_name: str, value: Any,
                      dep_index: int, goal: int, mode: str,
                      priority: int) -> bool:
        """Apply ONE satisfied dep to an entry; True when the goal is
        reached. Caller holds the entry's stripe lock. The single copy of
        the count/mask accounting shared by :meth:`update` and
        :meth:`update_batch` — the two must never diverge."""
        if value is not None:
            ent["data"][flow_name] = value
        ent["priority"] = max(ent["priority"], priority)
        if mode == DEPS_MASK:
            bit = 1 << dep_index
            if ent["mask"] & bit:
                raise RuntimeError(
                    f"dependency bit {dep_index} satisfied twice for {key}")
            ent["mask"] |= bit
            return ent["mask"] == goal
        ent["count"] += 1
        return ent["count"] == goal

    def update(self, key, flow_name: str, value: Any, dep_index: int,
               goal: int, mode: str, priority: int) -> Optional[Dict[str, Any]]:
        """Record one satisfied dep; return the entry if the goal is reached
        (caller then constructs and schedules the task)."""
        if self._native is not None:
            import ctypes
            if value is not None:
                with self._lock_for(key):
                    ent = self._entries.get(key)
                    if ent is None:
                        ent = {"data": {}, "priority": priority}
                        self._entries[key] = ent
                    ent["data"][flow_name] = value
            prio_out = ctypes.c_int32(priority)
            rc = self._native_lib.pdep_update(
                self._native, self._key64(key), goal, dep_index,
                1 if mode == DEPS_MASK else 0, priority,
                ctypes.byref(prio_out))
            if rc == -1:
                raise RuntimeError(
                    f"dependency bit {dep_index} satisfied twice for {key}")
            if rc == 1:
                return self._pop_data(key, prio_out.value)
            return None
        with self._lock_for(key):
            ent = self._entries.get(key)
            if ent is None:
                ent = {"count": 0, "mask": 0, "data": {}, "priority": priority}
                self._entries[key] = ent
            if self._count_locked(ent, key, flow_name, value, dep_index,
                                  goal, mode, priority):
                del self._entries[key]
                return ent
            return None

    def update_batch(self, items) -> List[Tuple[int, Dict[str, Any]]]:
        """Batched :meth:`update`: ``items`` is a sequence of
        ``(key, flow_name, value, dep_index, goal, mode, priority)``
        tuples. Entries are grouped by lock stripe so each stripe lock is
        taken ONCE per batch instead of once per dependency — the
        release-deps hot loop's dominant lock traffic when a completed
        task fans out to many successors. Returns ``(item_index, entry)``
        for every dependency that completed its target's goal."""
        if self._native is not None:
            # the native table does its own per-key synchronization, so
            # there is no stripe-lock traffic to coalesce — delegate per
            # item to the scalar path
            out = []
            for i, (key, flow_name, value, dep_index, goal, mode,
                    priority) in enumerate(items):
                ent = self.update(key, flow_name, value, dep_index, goal,
                                  mode, priority)
                if ent is not None:
                    out.append((i, ent))
            return out
        by_stripe: Dict[int, List[int]] = {}
        for i, item in enumerate(items):
            by_stripe.setdefault(hash(item[0]) % self._NSTRIPES,
                                 []).append(i)
        out = []
        for stripe, idxs in by_stripe.items():
            with self._stripe_lock(stripe):
                for i in idxs:
                    (key, flow_name, value, dep_index, goal, mode,
                     priority) = items[i]
                    ent = self._entries.get(key)
                    if ent is None:
                        ent = {"count": 0, "mask": 0, "data": {},
                               "priority": priority}
                        self._entries[key] = ent
                    if self._count_locked(ent, key, flow_name, value,
                                          dep_index, goal, mode, priority):
                        del self._entries[key]
                        out.append((i, ent))
        return out

    def finalize(self, key, goal: int, mode: str) -> Optional[Dict[str, Any]]:
        """For DSLs whose goal is only known after linking (DTD): check
        whether the already-accumulated count/mask meets the final goal;
        if so pop and return the entry."""
        if self._native is not None:
            import ctypes
            prio_out = ctypes.c_int32(0)
            rc = self._native_lib.pdep_finalize(
                self._native, self._key64(key), goal,
                1 if mode == DEPS_MASK else 0, ctypes.byref(prio_out))
            if rc == 1:
                return self._pop_data(key, prio_out.value)
            return None
        with self._lock_for(key):
            ent = self._entries.get(key)
            if ent is None:
                return None
            done = (ent["mask"] == goal) if mode == DEPS_MASK \
                else (ent["count"] == goal)
            if done:
                del self._entries[key]
                return ent
            return None

    def drop_values(self) -> None:
        """The pool has ended and no entry left here will complete: let
        go of the values that arrived for them."""
        self._entries.clear()

    def __len__(self) -> int:
        if self._native is not None:
            return int(self._native_lib.pdep_size(self._native))
        return len(self._entries)


_tp_counter = itertools.count(1)


class Taskpool:
    """A DAG instance (parsec_taskpool_t analog).

    Lifecycle: construct → ``context.add_taskpool`` (installs termdet,
    runs ``startup_hook`` to seed no-predecessor tasks) → tasks flow through
    the scheduler → termdet fires ``_on_terminated`` when
    ``nb_tasks == nb_pending_actions == 0``.
    """

    # stage span around ``startup_hook`` and the scheduling of what it
    # returns (Context.add_taskpool, stage timers on); None for none
    startup_span: Optional[str] = None

    def __init__(self, name: str = "taskpool"):
        self.name = name
        self.taskpool_id = next(_tp_counter)
        self.task_classes: List[TaskClass] = []
        self._tc_by_name: Dict[str, TaskClass] = {}
        self.context = None                      # set by add_taskpool
        self.pending = _PendingDeps()
        self.monitor = None                      # termdet monitor
        self.on_enqueue: Optional[Callable] = None
        self.on_complete: Optional[Callable] = None
        self.error: Optional[BaseException] = None
        self._complete_evt = threading.Event()
        self.priority = 0
        # cancellation (serving deadlines, Context.submit): when set,
        # queued-but-not-running tasks are DROPPED at select time
        # (scheduler/worker loop) instead of executed; in-flight tasks
        # drain through the normal completion path. Set via cancel().
        self.cancelled = False
        # multi-tenant serving metadata. fair_weight drives the wfq
        # scheduler's stride (sched/fair.py); tenant_name attributes
        # per-tenant PINS accounting; rank_scope restricts which peer
        # deaths can fail this pool (comm engines abort only pools
        # whose scope contains the dead rank — None = every rank, the
        # pre-serving fail-stop behavior).
        self.fair_weight: float = 1.0
        self.tenant_name: Optional[str] = None
        self.rank_scope: Optional[frozenset] = None
        # True when a supervisor (the serving runtime) owns this pool's
        # error reporting: a failure then never lands in the context's
        # aborted list, so other callers' Context.wait stays clean
        self.error_owned = False
        # request-scoped tracing (profiling/spans.py): serving
        # submissions set trace_rid (deterministic from the pool name,
        # identical on every rank) and root_span (the submission root
        # every startup task / admission park parents to). None keeps
        # the span path COMPLETELY off — plain attribute reads are the
        # only hot-path cost.
        self.trace_rid: Optional[str] = None
        self.root_span: Optional[str] = None
        # lineage record: (class name, locals) of every locally-completed
        # task (runtime.lineage) — after a peer death the survivors'
        # union of these is the completed-set input of
        # data.recovery.plan_recovery. GIL-atomic set.add on the release
        # path; measured noise vs the 14.2k tasks/s baseline.
        self.completed_tasks: set = set()
        # DSL hook: enumerate startup (no-predecessor) tasks
        self.startup_hook: Callable[["Taskpool"], List[Task]] = lambda tp: []

    # -- task classes -----------------------------------------------------
    def add_task_class(self, tc: TaskClass) -> TaskClass:
        self.task_classes.append(tc)
        self._tc_by_name[tc.name] = tc
        return tc

    def get_task_class(self, name: str) -> TaskClass:
        """Lookup by name (PTG taskpools shadow ``task_class`` with the
        class-builder, so the lookup has its own name)."""
        return self._tc_by_name[name]

    # -- static hazard lint (analysis/lint.py) ----------------------------
    def validate(self, mode: str = "error", max_tasks: int = 0):
        """Run the static dataflow lint over this taskpool and return
        the :class:`~parsec_tpu.analysis.lint.LintReport`.

        ``mode="error"`` raises :class:`~parsec_tpu.analysis.lint.
        HazardError` when any error-severity finding (undeclared
        producer, WAW/WAR hazard, access-mode violation, dependency
        cycle, phantom target) is present; ``mode="warn"`` logs the
        findings instead.  Task classes without closed-form PTG specs
        (DTD) are skipped — the dfsan runtime sanitizer covers those.
        Also invoked at registration when the ``analysis.lint`` MCA
        param is ``warn``/``error`` (Context.add_taskpool).
        """
        from ..analysis.lint import validate as _validate
        return _validate(self, mode=mode, max_tasks=max_tasks)

    def new_task_class(self, name: str, params: Sequence[str],
                       flows: Sequence[Flow],
                       deps_mode: str = DEPS_COUNTER) -> TaskClass:
        tc = TaskClass(name, len(self.task_classes), params, flows, deps_mode)
        return self.add_task_class(tc)

    # -- termdet glue (reference parsec_internal.h:123-145) ---------------
    def set_nb_tasks(self, n: int) -> None:
        self.monitor.set_nb_tasks(n)

    def addto_nb_tasks(self, d: int) -> None:
        self.monitor.addto_nb_tasks(d)

    def addto_runtime_actions(self, d: int) -> None:
        self.monitor.addto_runtime_actions(d)

    @property
    def nb_tasks(self) -> int:
        return self.monitor.nb_tasks if self.monitor else 0

    def _on_terminated(self) -> None:
        if self._complete_evt.is_set():
            # terminated is final: an abort()ed pool's still-queued
            # tasks keep draining, and the monitor re-fires when their
            # counters hit zero — a refire must not re-report the pool
            # to the context (it would poison a LATER wait, e.g. the
            # recovery replay's, with the stale abort)
            return
        debug_verbose(4, "taskpool", "%s terminated", self.name)
        if self.context is not None:
            self.context.drop_copies(self)      # before a waiter wakes
        self._complete_evt.set()
        if self.on_complete is not None:
            self.on_complete(self)
        if self.context is not None:
            self.context._taskpool_terminated(self)

    def abort(self, exc: BaseException) -> None:
        """parsec_abort analog: a task body failed — record the error and
        force-terminate so waiters are released instead of hanging."""
        if self.error is None:
            self.error = exc
        self._on_terminated()

    def cancel(self, exc: Optional[BaseException] = None) -> None:
        """Cancel this taskpool (serving deadlines / Context.submit):
        not-yet-running tasks are dropped at select time (the
        ``cancelled`` flag — schedulers and the worker loop decrement
        ``nb_tasks`` instead of executing), in-flight tasks drain
        through the normal completion path, and waiters are released
        now via the abort machinery. Termination is idempotent (PR 6),
        so draining tasks re-firing termdet cannot poison a later wait
        on a DIFFERENT pool — cancellation is a per-taskpool failure
        unit."""
        self.cancelled = True
        self.abort(exc if exc is not None
                   else CancelledError(f"taskpool {self.name} cancelled"))

    @property
    def completed(self) -> bool:
        return self._complete_evt.is_set()

    def wait_completed(self, timeout: Optional[float] = None) -> bool:
        ok = self._complete_evt.wait(timeout)
        if self.error is not None:
            raise RuntimeError(
                f"taskpool {self.name} aborted: {self.error}") from self.error
        return ok

    # -- dependency activation (parsec.c:1694-1780 analog) ----------------
    def _ready_task(self, ref: SuccessorRef, ent: Dict[str, Any]) -> Task:
        """Construct the ready Task for a goal-completing entry — the one
        copy shared by the scalar and batched activation paths."""
        tc = ref.task_class
        task = Task(self, tc, ref.locals,
                    priority=max(ent["priority"], tc.priority_fn(ref.locals)))
        task.data.update(ent["data"])
        return task

    def activate_dep(self, ref: SuccessorRef) -> Optional[Task]:
        """Count one satisfied input dep of ``ref``'s target task; if that
        completes the target's goal, construct the ready Task and return it
        (caller schedules it)."""
        tc = ref.task_class
        ent = self.pending.update(tc.make_key(ref.locals), ref.flow_name,
                                  ref.value, ref.dep_index,
                                  tc.deps_goal(ref.locals), tc.deps_mode,
                                  ref.priority)
        if ent is None:
            return None
        return self._ready_task(ref, ent)

    def activate_deps(self, refs: Sequence[SuccessorRef]) -> List[Task]:
        """Batched :meth:`activate_dep`: count all of a completed task's
        satisfied deps in one striped-lock pass and return every
        successor whose goal was reached. Semantics are identical to
        calling ``activate_dep`` per ref; only the lock traffic changes."""
        if len(refs) == 1:
            task = self.activate_dep(refs[0])
            return [task] if task is not None else []
        items = []
        for ref in refs:
            tc = ref.task_class
            items.append((tc.make_key(ref.locals), ref.flow_name, ref.value,
                          ref.dep_index, tc.deps_goal(ref.locals),
                          tc.deps_mode, ref.priority))
        return [self._ready_task(refs[i], ent)
                for i, ent in self.pending.update_batch(items)]

    def __repr__(self) -> str:
        return f"<Taskpool {self.name} id={self.taskpool_id}>"
