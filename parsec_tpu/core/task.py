"""Task, flow and chore structures.

Mirrors the reference's core runtime objects:
- ``parsec_task_t`` (parsec_internal.h:503-516): runtime task instance with
  locals (parameter assignments), per-flow data, priority, chore mask and
  status (statuses at parsec_internal.h:464-469).
- ``parsec_flow_t`` (parsec_description_structures.h:92-106): named data
  access of a task class with access mode READ/WRITE/RW/CTL.
- ``__parsec_chore_t`` (parsec_internal.h:368-374): an *incarnation* of a
  task class on a device type, with an optional ``evaluate`` predicate and
  the executable ``hook``.

TPU-first divergence: bodies are **functional** — a chore takes the input
tile values and returns the output tile values for its WRITE/RW flows,
instead of mutating buffers in place. Functional bodies are what XLA can
trace, vmap-batch and fuse; the runtime owns the store-back.
"""

from __future__ import annotations

import enum
import functools
import itertools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple


class FlowAccess(enum.IntFlag):
    """Access mode of a flow (reference PARSEC_FLOW_ACCESS_* / SYM_INOUT)."""
    NONE = 0
    READ = 1
    WRITE = 2
    RW = 3
    CTL = 4      # control-only dependency, no data payload


class DeviceType(enum.IntFlag):
    """Device type bits (reference device.h:62-72)."""
    NONE = 0
    CPU = 1
    RECURSIVE = 2
    TPU = 4
    ALL = CPU | RECURSIVE | TPU


class HookReturn(enum.IntEnum):
    """Chore hook return codes (reference PARSEC_HOOK_RETURN_*)."""
    DONE = 0        # body executed, proceed to completion
    AGAIN = 1       # reschedule (priority demoted), e.g. resource busy
    ASYNC = 2       # body will complete asynchronously (device pipeline)
    NEXT = 3        # try the next incarnation
    ERROR = -1


class TaskStatus(enum.IntEnum):
    """Task lifecycle (reference parsec_internal.h:464-469)."""
    NONE = 0
    PREPARE_INPUT = 1
    EVAL = 2
    HOOK = 3
    PREPARE_OUTPUT = 4
    COMPLETE = 5


@dataclass
class Flow:
    """A named dataflow of a task class (parsec_flow_t analog)."""
    name: str
    access: FlowAccess
    index: int = -1          # assigned when attached to a task class

    @property
    def is_ctl(self) -> bool:
        return bool(self.access & FlowAccess.CTL)


@dataclass
class Chore:
    """One incarnation of a task class on a device type.

    ``hook(task, *inputs) -> outputs`` where ``inputs`` are the values of
    the task's flows in declaration order and ``outputs`` the new values of
    its WRITE/RW flows in declaration order (a single value may be returned
    for a single output flow). ``evaluate`` may veto this incarnation for a
    particular task (reference __parsec_chore_t.evaluate).
    """
    device_type: DeviceType
    hook: Callable[..., Any]
    evaluate: Optional[Callable[["Task"], bool]] = None
    # device-layer hints (reference gpu properties, jdf2c.c:6561-6590)
    weight: Optional[Callable[["Task"], float]] = None
    batchable: bool = True   # TPU: may be vmap-batched with same-class tasks
    # Optional hand-written batched form used by the compiled executor in
    # place of vmap(hook): ``batch_hook(*stacked_tiles) -> stacked outs``.
    # For ops whose batched lowering is poor on TPU (triangular solves),
    # a class-specific reformulation (e.g. one wide-RHS solve) is far
    # faster than the mechanical vmap. ``batch_hook_shared`` names input
    # flows the hook assumes hold ONE tile across the whole batch; the
    # executor verifies this per group and falls back to vmap otherwise.
    batch_hook: Optional[Callable[..., Any]] = None
    batch_hook_shared: Optional[Sequence[str]] = None
    # RW flows whose incoming version this task is the LAST reader of:
    # the dependency graph hands it to no other task, and the task writes
    # the tile it came from. A chip module may then give the input's
    # buffer to its program for the flow's output (jit donation), as
    # upstream's kernels update a tile where it lies: a launch queued
    # behind a busy chip allocates nothing for it, and the input is gone
    # once the launch is made. The body returns its outputs in the order
    # of its output flows, and a donated flow is the first of its shape
    # among them (JAX pairs a donated buffer with the first output of its
    # shape). Read by the chip module's program table alone: an executor
    # that lowers the whole pool places its buffers itself. A declaration,
    # not the module's rule: what a launch holds anew the module reads
    # off the program it built (``TPUDevice._build``). Two users: a PTG
    # body whose graph says so (``build_potrf``, ``build_geqrf``), and
    # the DTD front end, which decides it a task at insertion: an INOUT
    # tile no reader was inserted on since its last writer
    # (``dtd.Taskpool._insert_one``; the given flows key the task's
    # class, so tasks that give different flows have different chores).
    donates: Optional[Sequence[str]] = None
    # What the body's kernels need of the chip's compiler beyond its
    # defaults, as XLA compiler options: a chip module compiles this
    # chore's programs with them (on a TPU alone; a rehearsal on the CPU
    # platform knows none of them). One user: ``build_getrf_1d``'s panel
    # task, whose pivoted factorization of a stack of up to 32768 x 128
    # is XLA's ``LuDecompositionBlock``, which works in scoped VMEM and
    # is refused over 16 MiB of it (a v5e core has 128).
    compiler_options: Optional[Dict[str, Any]] = None
    # Hooks that are NOT batchable as-is (they read per-task metadata,
    # e.g. DTD's woven argspec) can still hand a device module their pure
    # body by providing BOTH of: ``batch_sig(task) -> hashable`` — a key
    # such that tasks with equal keys share one pure body — and
    # ``batch_body(task) -> fn(*flow_values)`` — that pure body
    # (UNJITTED; the device jits programs that call it once per member).
    # Used by dtd.insert_task(pure=True). On the dynamic path these two
    # and ``batchable`` are read by ``pure_body`` alone.
    batch_sig: Optional[Callable[["Task"], Any]] = None
    batch_body: Optional[Callable[["Task"], Callable[..., Any]]] = None
    _plain: Optional[functools.partial] = field(
        default=None, init=False, repr=False, compare=False)

    def pure_body(self, task: "Task"
                  ) -> Optional[Tuple[Any, Callable[..., Any]]]:
        """``(key, fn)`` where this incarnation is a pure function of the
        task's flow values a device module may jit, ``fn(*flow_values)
        -> outputs``: tasks of this chore with equal keys share ``fn``,
        so one XLA program runs any of them, alone or several to a
        launch. ``(batch_sig, batch_body)`` of the task for a body that
        declares them, ``(None, hook without its task)`` for a
        ``batchable`` one (the task is host-side metadata such a body
        does not read); ``None`` for a body that dispatches itself."""
        if self.batch_body is not None:
            if self.batch_sig is None:
                return None
            return self.batch_sig(task), self.batch_body(task)
        if not self.batchable:
            return None
        plain = self._plain     # made once a chore, not once a task
        if plain is None or plain.func is not self.hook:
            plain = self._plain = functools.partial(self.hook, None)
        return None, plain


# Tasks a device module issues as one launch, largest first: a worker
# launches the largest size that ready tasks of one body fill, the next
# size from what is left, and single tasks below the smallest. Every
# accelerator body with a pure form has such programs (``Chore.pure_body``
# unrolled, or its ``batch_hook`` over the stacked members). A fixed set,
# so every size is compiled the first time a signature is seen (a
# ``batch_hook``'s when its first group forms) and none later.
# Settled on the v5e (PERF.md section 6, PR 25): what a launch makes
# anew waits in HBM for its members' release, and eight 1024-tiles are
# what the benchmark's 1% on peak_hbm_gib leaves room for (a launch that
# writes where its tiles lie, ``Chore.donates``, holds nothing new, and
# the module queues a second group behind it: PR 36). Which of these
# sizes a task's bytes admit is the module's rule
# (``device.tpu.GROUP_BYTES``).
GROUP_SIZES = (8, 4)
# The most tasks one take of a worker holds, all classes together
# (``Context._take_group``): every one is launched before the worker
# selects again, so this bounds how long a ready task of high priority
# waits in a worker's hands behind what was selected before it.
GROUP_TAKE = 2 * GROUP_SIZES[0]

_task_counter = itertools.count()


class Task:
    """A runtime task instance (parsec_task_t analog)."""

    __slots__ = ("taskpool", "task_class", "locals", "data", "output",
                 "priority", "chore_mask", "status", "uid", "repo_entry",
                 "on_complete", "prof", "dsl", "vc")

    def __init__(self, taskpool, task_class, locals: Tuple[int, ...],
                 priority: int = 0):
        self.taskpool = taskpool
        self.task_class = task_class
        self.locals = tuple(locals)
        # per-flow input values, keyed by flow name
        self.data: Dict[str, Any] = {}
        # per-flow output values (filled by completion path)
        self.output: Dict[str, Any] = {}
        self.priority = priority
        self.chore_mask = (1 << 30) - 1
        self.status = TaskStatus.NONE
        self.uid = next(_task_counter)
        self.repo_entry = None
        self.on_complete: Optional[Callable[["Task"], None]] = None
        self.prof: Dict[str, float] = {}
        self.dsl: Dict[str, Any] = {}   # DSL-private state (DTD links, ...)
        # vector clock stamped by the dfsan race sanitizer
        # (analysis/dfsan.py); None whenever the sanitizer is off
        self.vc: Optional[Dict[int, int]] = None

    @property
    def key(self) -> Tuple[int, Tuple[int, ...]]:
        """Unique key inside the taskpool (task_class.make_key analog)."""
        return self.task_class.make_key(self.locals)

    def input_values(self) -> List[Any]:
        return [self.data.get(f.name) for f in self.task_class.flows
                if not f.is_ctl]

    def __repr__(self) -> str:
        args = ", ".join(map(str, self.locals))
        return f"{self.task_class.name}({args})"


def normalize_outputs(result: Any, out_flow_names: Sequence[str],
                      label: Any) -> Dict[str, Any]:
    """Functional-body result → output-flow dict: None = no outputs,
    dict = as-is, tuple/list zipped against the output flows (arity
    checked), a bare value requires exactly one output flow. THE single
    copy of this contract — the device layer and the native DTD engine
    both normalize through here, so engine/device choice can never
    change what a body's return value means. ``label`` is only used in
    error messages (a Task repr, a seq id, ...)."""
    if result is None:
        return {}
    if isinstance(result, dict):
        return result
    if isinstance(result, (tuple, list)):
        if len(result) != len(out_flow_names):
            raise ValueError(
                f"{label}: body returned {len(result)} values for "
                f"{len(out_flow_names)} output flows")
        return dict(zip(out_flow_names, result))
    if len(out_flow_names) != 1:
        raise ValueError(
            f"{label}: single return value but {len(out_flow_names)} "
            "output flows")
    return {out_flow_names[0]: result}
