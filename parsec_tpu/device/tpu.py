"""TPU device module.

Replaces the reference's CUDA device pipeline
(mca/device/cuda/device_cuda_module.c, 2,765 LoC) with an XLA-native
design. The reference pipelines each GPU task through stage-in → kernel →
stage-out streams with event-driven progress; on TPU the equivalent roles
are played by XLA/PJRT itself:

- *stage-in/out*: one rule for every leaf the module hands to XLA
  (``_here``): a ``jax.Array`` not committed to another chip passes as it
  is — tiles made by previous TPU tasks stay resident in HBM and flow to
  successors without host bounce — and a host value is ``device_put`` on
  this module's chip. A tile committed to ANOTHER chip is copied here
  once per version, chip to chip (``_copy_here``): the module keeps the
  copy for the next reader of that version, within ``REMOTE_BYTES``,
  until the version is superseded or the pool that read it ends, and
  never hands it to a program to write into.
- *streams + events*: JAX dispatch is asynchronous — a jitted call
  returns at once with future-backed arrays, so consecutive tasks
  pipeline on device; blocking only happens at final writebacks.
- *kernel lookup* (reference cuda_find_incarnation, dyld by name): a
  chore says whether it has a pure form the module may jit
  (``Chore.pure_body``), and the module keeps ONE table of programs.

*One launch route.* Where the reference's manager owns the device's
streams (progress_stream, device_cuda_module.c:1961-2097), the leverage
here is the LAUNCH: a program takes the input leaves of N tasks of one
body and calls the pure body once per member, flat and unrolled — the
device does the same work per task in the same buffers, the host pays one
trip through jit dispatch (and one hand-off of the GIL) for the N
(``_programs``). A lone task (``execute``) is a launch of one; the worker
that selected several ready tasks of a taskpool (``Context._take_group``:
one bin per body, whatever classes were ready together) has each bin
issued as launches of ``GROUP_SIZES`` (``execute_group``), on its own
thread. A body without a pure form (it reads its task, or jits inside)
and inputs that share no program run the chore's own hook, pinned to
this chip (``_pinned``).

A launch carries the largest size whose inputs together stay within
``GROUP_BYTES`` (an operand the group shares is there once, and counted
once): small tasks go many to a launch, large ones alone, by the bytes
this module sees and nothing else. What a launch makes is allocated when
it is enqueued and waits in HBM for its members' release, so ONE thing
the module reads off each program decides how far the host may run ahead
of the chip: the bytes of NEW outputs a launch of it holds (``_build``:
the outputs of its first run that lie in no buffer it was given). A flow
whose incoming version its task is the last to read (``Chore.donates``)
is updated where it lies: the program is given the input's buffer for
the flow's output, and such a program returns a mark of its own to be
waited for, since any of its outputs may be given on before anyone has
waited. A group that holds new outputs is one group in flight: it keeps
the module's turn (``group_turn``) through its last member's release and
waits for the last group before its call. A group that holds nothing new
is pipelined: its turn ends at the call (the members are released with
the turn free, while the next worker stages and calls) and it waits for
the group BEFORE the last, so the chip has the next group queued while
it works on this one and the runtime still bounds the groups it queues,
at two. A launch of one neither takes the turn nor waits for a group;
the lone ones still queued hold ``GROUP_BYTES`` of new outputs at most
(``_queued``), and one that holds nothing new is not counted. Whole taskpools
lowered to one program are ``parsec_tpu.compiled``'s business, not this
module's.
"""

from __future__ import annotations

import collections
import numbers
import re
import threading
import time
import weakref
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

from .base import Device
from ..core.spans import (SPAN_EXEC, SPAN_EXEC_CALL, SPAN_EXEC_WAIT,
                          SPAN_STAGE_IN, StageSpan)
from ..core.task import (GROUP_SIZES, Chore, DeviceType, FlowAccess,
                         HookReturn, Task, normalize_outputs)
from ..utils.debug import debug_verbose


# The new outputs of a launch are allocated when it is enqueued and wait
# there for their members' release, one by one (a launch whose outputs
# lie in the buffers it was given holds none); and XLA's program for
# several large tiles is slower than the tiles' own programs (64 GEMMs of
# 4096^3 as four programs of sixteen: half again the device time, PERF.md
# section 6, PR 25), while a task that size keeps the chip busy longer
# than its launch costs the host. So a launch carries the largest size of
# ``GROUP_SIZES`` whose inputs, all together, stay within this many
# bytes: f32 GEMMs of 1024-tiles (12 MiB a task) go eight at a time, of
# 2048-tiles (48 MiB) four at a time, of 4096-tiles (192 MiB) alone. The
# bytes are those the launch holds: an operand its members share (a
# stacked form's ``batch_hook_shared``, one object, checked a launch) is
# one buffer and counts once, so a row of 2048-tile TSMQRs (V2 and T
# shared, two tiles of its own a member: 18 + 4 x 32 MiB) goes four at a
# time as four GEMMs do, and a column of TRSMs (L shared) eight, which
# hold what four TSMQRs hold (PERF.md section 6, PR 33).
# Settled on the v5e (PERF.md section 6, PR 28): the program of four
# 2048-tile GEMMs costs the device no more than the four alone (0.43 s a
# factorization against 0.45) and the host a third of their launches;
# two at a time under 128 MiB left the host 40% slower, and a size 2
# beside the four moved no step time. 2 x 192 MiB stays refused.
GROUP_BYTES = 192 << 20

# A task runs where the tile it writes lies, so what it READS may lie on
# another chip: a Cholesky's panel tile L(m, k) is read by the updates of
# a whole row and a whole column of the trailing matrix, on every chip of
# a 2 x 2 grid. This chip's copy of one version of such a tile is made
# once and serves every later reader here (``_copy_here``); the copies a
# module keeps, and those it has let go whose last reader the chip has
# not finished, together hold this many bytes at most, the least
# recently read going first. A copy is allocated when it is enqueued, as
# a launch's outputs are, and the host runs a whole factorization ahead
# of the chips where every launch writes in place: without a bound a
# chip would hold every remote tile of the step (13 GiB of copies beside
# a 4.9 GiB share of a 98304-matrix in 4096-tiles). The reads of a block
# column come round again with every row of updates, so a bound under
# the columns in flight makes the least recently read copy the next one
# wanted, and every copy is made twice over: two columns of 4096-tiles
# (48 tiles of 64 MiB) in a rehearsal whose workers keep step, three and
# more on the chip, where each worker follows the successors it released
# (4 GiB read 1.09 to 1.10 times the least copies there). 5 GiB hold
# eighty such tiles (PERF.md section 6, PR 37).
REMOTE_BYTES = 5 << 30


class _Copy:
    """This chip's copy of one version of a tile that lies on another:
    ``source`` the version (weakly: the array itself, a new version is a
    new array), ``value`` the copy (None while its first reader makes
    it: ``making``, an event the other readers wait for), ``pool`` the
    pool whose task had it made, ``pins`` the launches that hold it
    between staging and their call, ``mark`` what tells that the last
    launch that read it is over (as in ``_queued``)."""

    __slots__ = ("source", "value", "nbytes", "pool", "pins", "mark",
                 "making")

    def __init__(self, source, nbytes: int, pool: int) -> None:
        self.source, self.value, self.nbytes = source, None, nbytes
        self.pool, self.pins, self.mark = pool, 0, None
        self.making = threading.Event()         # None once it is made


class TPUDevice(Device):
    device_type = DeviceType.TPU
    name = "tpu"

    def __init__(self, jax_device: Any) -> None:
        """One module instance per chip (reference: one
        parsec_device_cuda_module_t per GPU, device_cuda_module.c:326),
        pinned to ``jax_device``."""
        super().__init__()
        import jax
        import numpy as np
        self.jax = jax
        self._arrays = (jax.Array, np.ndarray)      # a tile, no pytree
        self._host = (np.ndarray, np.generic, numbers.Number)
        self.jax_device = jax_device
        self.platform = self.jax_device.platform
        # load-balancing weight: accelerators drastically out-throughput the
        # inline-CPU device (reference GFLOPS table device_cuda_module.c:53)
        self.weight = 100.0 if self.platform != "cpu" else 2.0
        self.name = f"tpu{self.jax_device.id}"
        if self.platform != "cpu":
            # comm staging target: the pipelined receive path (per-
            # segment device_put) and the HBM remote stage-in land
            # bytes straight on this module's chip instead of bouncing
            # through jax's default device (first accelerator module
            # wins; CPU meshes keep uncommitted default placement —
            # committing test arrays to one virtual device would make
            # mixed-placement jits raise)
            from ..comm import device_plane
            device_plane.set_stage_target(self.jax_device)
        # THE program table, {id(chore): {(pure body's key, input
        # signature, stacked?): {size: program}}} (a record also holds
        # the chore's pinned hook, ``_pinned``), with the chore each
        # record is of, weakly: a chore dies with its pool, and the
        # records of the dead go when the next record is made
        # (``_record``); and the process-shared programs this module has
        # already run once
        self._table: Dict[int, Dict[Any, Any]] = {}
        self._chores: Dict[int, "weakref.ref[Chore]"] = {}
        self._table_lock = threading.Lock()
        self._warmed: set = set()
        # the module's turn: held from taking the tasks to the call of
        # the take's last group, and through the members' release where
        # a group holds new outputs (Context._group_progress); and what
        # tells that the groups still on the chip's queue are over, two
        # at most, the older first (a program's own mark, or an output
        # of a group that holds new ones: of those only the last)
        self.group_turn = threading.Lock()
        self._group_marks: List[Any] = []
        # the launches of one still on the chip's queue, oldest first:
        # (an output, weakly, or the program's mark; the bytes of its
        # new outputs), and their sum
        self._lone: Deque[Tuple[Any, int]] = collections.deque()
        self._lone_bytes = 0
        self.stats["batches"] = 0
        self.stats["batched_tasks"] = 0
        # how often the in-place rule engages: launches of one and
        # groups that held nothing new, and groups called while the
        # group before them was still on the chip's queue
        self.stats.update(lone_in_place=0, groups_in_place=0,
                          groups_pipelined=0)
        # write-backs that merged one region of a tile into the tile, in
        # its buffer (``Context._merge_region``); and the tiles that are
        # not floating point (a factorization's pivots) handed to this
        # chip's programs, put here or found here, with their bytes: an
        # operand a group shares counted once a launch (``program.ints``)
        self.stats.update(region_merges=0, int_tiles_staged=0,
                          int_bytes_staged=0)
        # the tiles handed to this chip's programs as elements of a
        # ranged flow's list (dsl/ptg.py), and the launches that took
        # such a list (``program.ranged``)
        self.stats.update(ranged_tiles_staged=0, ranged_launches=0)
        # the host's waits for the chip (``_under``) and its jitted
        # calls: seconds and waits while the stage timers are on (the
        # launches are in ``launches_by_class``); the longest of each
        # always, so that a step that stalls says where it stood
        self.stats.update(chip_wait_s=0.0, chip_waits=0, call_s=0.0,
                          chip_wait_max_s=0.0, call_max_s=0.0)
        # this chip's copies of tiles that lie on other chips, by the
        # id of the version they are copies of, least recently read
        # first; (mark, bytes) of those let go whose last reader may
        # still be on the chip's queue, oldest first; the bytes of both;
        # and the ids whose version has died (a weak reference's
        # callback may run on any thread, inside any lock: it appends
        # here and the next staging looks)
        self._copies: "collections.OrderedDict[int, _Copy]" = \
            collections.OrderedDict()
        self._copies_lock = threading.RLock()
        self._retired: Deque[Tuple[Any, int]] = collections.deque()
        self._copy_bytes = 0
        self._dead: Deque[int] = collections.deque()
        # copies made and their bytes, reads a copy already here served,
        # seconds the host spent making copies: always on
        self.stats.update(remote_copies=0, remote_bytes_in=0,
                          remote_hits=0, remote_copy_s=0.0)
        debug_verbose(3, "device", "TPU device on %s (%s)",
                      self.jax_device, self.platform)

    def dump_statistics(self) -> Dict:
        """The module's counters and, beside them, which factorization
        TSTRF's blocks were traced through (``ops.tile_kernels``: the
        VMEM panel or XLA's LU; the process's traces, not this chip's
        launches)."""
        from ..ops.tile_kernels import LU_BLOCKS_TRACED
        return dict(super().dump_statistics(),
                    lu_blocks_vmem_panel=LU_BLOCKS_TRACED["vmem_panel"],
                    lu_blocks_xla_lu=LU_BLOCKS_TRACED["xla_lu"])

    def execute(self, es, task: Task, chore: Chore) -> HookReturn:
        """``task`` alone: a launch of one from the body's table, or the
        chore's own hook where the table has no program for it."""
        values = task.input_values()
        program = self._programs(task, chore, values,
                                 self._sig(values)).get(1)
        if program is None:
            self._spanned(task, self._run_hook, task, self._pinned(chore))
        else:
            self._spanned(task, self._launch_group, [task], program,
                          [values])
        return HookReturn.DONE

    def execute_group(self, es, tasks: List[Task],
                      chore: Chore) -> Tuple[int, int]:
        """Launch the first tasks of ``tasks`` (one chore, one pure
        body; prepared by the caller) as ONE program and attach their
        outputs: ``(how many they were, the bytes of new outputs the
        launch holds in HBM until they are released)``. The size is the
        largest of ``GROUP_SIZES`` that ``tasks`` fills with inputs of
        one signature and that ``GROUP_BYTES`` admits; the bytes are the
        program's (``program.held``, ``_build``), 0 where every output
        lies in a buffer the program was given: the caller may then
        release the members after the module's turn. ``(0, 0)`` where
        the first task has to go alone (the caller takes the single
        path). Members that differ in signature never share a program.
        Raises where the launch does."""
        if len(tasks) < GROUP_SIZES[-1] or \
                not self._hook_ok(chore, tasks[:GROUP_SIZES[-1]]):
            # asked before a program is built: what is left of a bin,
            # and a class whose members never hold one object where its
            # stacked form shares it (a serial chain's own tile), is
            # never compiled for a group, in a later step least of all
            return 0, 0
        values = [tasks[0].input_values()]
        sig = self._sig(values[0])
        programs = self._programs(tasks[0], chore, values[0], sig,
                                  stacked=chore.batch_hook is not None)
        for size in GROUP_SIZES:                    # largest first
            program = programs.get(size)
            if program is None or size > len(tasks):
                continue
            while len(values) < size:
                more = tasks[len(values)].input_values()
                if self._sig(more) != sig:
                    break
                values.append(more)
            if len(values) < size or \
                    not self._hook_ok(chore, tasks[:size]):
                continue
            self._spanned(tasks[0], self._launch_group, tasks[:size],
                          program, values[:size])
            return size, program.held
        return 0, 0

    @staticmethod
    def _spanned(task: Task, launch: Callable, *args) -> None:
        """One launch, under its ``parsec:exec`` span where the stage
        timers are on: the enqueue as the host pays it (staging, the
        wait for the module's last group under ``parsec:exec_wait``,
        ``default_device`` and the jitted call until it returns under
        ``parsec:exec_call``, attaching the outputs), not the device's
        work."""
        if task.taskpool.context.stage_timers:
            with StageSpan(SPAN_EXEC):
                launch(*args)
        else:
            launch(*args)

    def _launch_group(self, tasks, program, values) -> None:
        """``tasks`` (one, or a group) through ``program`` (``held``:
        the bytes of new outputs a launch of it holds), their outputs
        attached."""
        held = program.held
        timed = tasks[0].taskpool.context.stage_timers
        group = len(tasks) > 1
        used: List[_Copy] = []  # the copies of remote tiles it reads
        flat = self._flat(values, tasks[0].taskpool, used,
                          program.donated_at)
        over = None
        try:
            over = self._launch(tasks, program, flat, held, timed, group)
        finally:
            if used:
                # read: what tells that this launch is over tells that
                # the copies' last reader is
                with self._copies_lock:
                    for copy in used:
                        copy.pins -= 1
                        copy.mark = over

    def _launch(self, tasks, program, flat, held, timed, group):
        """``_launch_group`` once the leaves are on this chip; returns
        what tells that the launch is over (``mark()``: an array to wait
        for, or None once it has gone), None where nothing does."""
        waits = []      # seconds of each wait for the chip this launch made
        marks, queued = self._group_marks, []
        if group and held:
            # the runtime bounds the groups it queues, not their bytes:
            # a chip that lags a few ms behind would hold the outputs
            # and the inputs of as many groups. A group that holds new
            # outputs waits for the last group, whatever kind that was:
            # one group in flight. The chip is far ahead wherever groups
            # form (GROUP_BYTES), so this wait is a check. A launch of
            # one neither waits nor is waited for: it overlaps the
            # groups' waits (what the lone ones still queued hold is
            # bounded in _queued)
            for over in marks[-1:]:
                if not over.is_deleted():       # given on: behind it
                    waits.append(self._under(SPAN_EXEC_WAIT, timed,
                                             over.block_until_ready)[1])
        elif group:
            # a group that holds nothing new goes behind ONE group still
            # on the chip's queue, so that the chip has it queued while
            # it works on that: of the last two groups, those the chip
            # has not finished (a look at a mark costs a quarter of a
            # microsecond), all but the last waited for. A wait that
            # would return at once is not made: it hands the interpreter
            # to another thread and stands in line to have it back
            queued = [m for m in marks
                      if not (m.is_deleted() or m.is_ready())]
            for over in queued[:-1]:
                waits.append(self._under(SPAN_EXEC_WAIT, timed,
                                         over.block_until_ready)[1])
        results, called = self._under(SPAN_EXEC_CALL, timed, self._call,
                                      program, flat)
        # what tells that the launch is over: an output of its last
        # member, or the mark a program whose chore donates returns
        # after its members (a later launch may be GIVEN any output)
        done = self.jax.tree_util.tree_leaves(results[len(tasks) - 1])
        own = len(results) > len(tasks)
        mark = results[-1] if own else done[0] if done else None
        # a tile weakly: one its collection has dropped is done with
        ends = None if mark is None else \
            (lambda: mark) if own else weakref.ref(mark)
        if group:
            if mark is not None:
                self._group_marks = [mark] if held else marks[-1:] + [mark]
        elif mark is not None and held:
            waits += self._queued(ends, held, timed)
        names = [f.name for f in tasks[0].task_class.output_flows]
        for t, res in zip(tasks, results):
            t.output.update(normalize_outputs(res, names, t))
        with self._lock:
            self.stats["tasks"] += len(tasks)
            if group:
                self.stats["batches"] += 1
                self.stats["batched_tasks"] += len(tasks)
                self.stats["groups_in_place"] += not held
                self.stats["groups_pipelined"] += bool(queued)
            else:
                self.stats["lone_in_place"] += not held
            if program.ranged:
                self.stats["ranged_tiles_staged"] += \
                    program.ranged * len(tasks)
                self.stats["ranged_launches"] += 1
            if program.ints is not None:
                own, shared = program.ints
                for (n, nbytes), times in ((own, len(tasks)), (shared, 1)):
                    self.stats["int_tiles_staged"] += n * times
                    self.stats["int_bytes_staged"] += nbytes * times
            # the longest call and the longest wait always: what a
            # stalled step stood in
            if called > self.stats["call_max_s"]:
                self.stats["call_max_s"] = called
            if waits and max(waits) > self.stats["chip_wait_max_s"]:
                self.stats["chip_wait_max_s"] = max(waits)
            if timed:
                self.stats["call_s"] += called
                self.stats["chip_wait_s"] += sum(waits)
                self.stats["chip_waits"] += len(waits)
                self._count_launch(tasks[0], len(tasks))
        return ends

    def _call(self, program, flat):
        """The jitted call until it returns."""
        with self.jax.default_device(self.jax_device):
            return program(*flat)

    @staticmethod
    def _under(name: str, timed: bool, fn: Callable,
               *args) -> Tuple[Any, float]:
        """``(fn(*args), the seconds it took)``, under the span ``name``
        where the stage timers are on (``timed``): the host's wait for
        the chip (``parsec:exec_wait``) and the jitted call until it
        returns (``parsec:exec_call``), both inside the launch's
        ``parsec:exec``."""
        t0 = time.perf_counter()
        if timed:
            with StageSpan(name):
                out = fn(*args)
        else:
            out = fn(*args)
        return out, time.perf_counter() - t0

    def _queued(self, mark, nbytes, timed: bool) -> List[float]:
        """A launch of one holds ``nbytes`` of new outputs and is over
        when the array ``mark()`` is. It neither waits for the
        module's turn nor is waited for, so nothing else bounds how far
        its thread runs ahead of a busy chip, and what a launch makes is
        allocated when it is enqueued: a row of two-tile updates queued
        30 deep held 1 GiB beside a 4.5 GiB matrix, 1.6% more or less
        from run to run (PERF.md section 6, PR 33). So the lone launches
        still on the chip's queue hold ``GROUP_BYTES`` of new outputs at
        most, as a group does: the thread that enqueues more waits for
        the oldest. A chip that keeps up has finished it long before,
        and the wait is a look. (A launch whose program holds nothing
        new, its outputs in the buffers it was given, never comes here:
        a Cholesky's lone SYRKs and TRSMs, a QR's lone updates.) Returns
        the seconds of each wait it made."""
        oldest = []
        with self._lock:
            self._lone.append((mark, nbytes))
            self._lone_bytes += nbytes
            while self._lone_bytes > GROUP_BYTES and len(self._lone) > 1:
                ref, n = self._lone.popleft()
                self._lone_bytes -= n
                oldest.append(ref())
        return [self._under(SPAN_EXEC_WAIT, timed, leaf.block_until_ready)[1]
                for leaf in oldest
                if leaf is not None and not leaf.is_deleted()]

    # --------------------------------------------------------- staging

    def _here(self, leaf, pool=None, used=None):
        """THE staging rule, for every leaf this module hands to XLA on
        any route: a ``jax.Array`` passes as it is unless it is committed
        to another chip (jit raises on mixed committed placements; an
        uncommitted one follows ``default_device``), and a host value
        (numpy, a Python number) is put on this module's chip. A tile
        committed to another chip is read through this chip's copy of
        that version, which is made once, chip to chip, and kept for the
        next reader (``_copy_here``; ``pool``: the reader's taskpool,
        ``used``: where the launch collects the copies it reads); a
        reader without a pool (a program's first run, a flow its program
        writes into) gets a copy of its own. A leaf that is here costs a
        type test and two attribute reads."""
        if isinstance(leaf, self.jax.Array):
            if not leaf.committed or getattr(leaf, "device", None) in (
                    None, self.jax_device):
                return leaf
            if pool is not None:
                return self._copy_here(leaf, pool, used)
        elif not isinstance(leaf, self._host):
            return leaf         # no array: not this module's to place
        return self.jax.device_put(leaf, self.jax_device)

    def _flat(self, values, pool=None, used=None,
              donated=frozenset()) -> List[Any]:
        """The members' input leaves in order, None-valued flows left
        out, each on this module's chip. A flow at one of the positions
        ``donated`` is the program's to write into: it is never served
        from a copy the module keeps."""
        leaves, here = self.jax.tree_util.tree_leaves, self._here
        flat: List[Any] = []
        for vals in values:
            for at, v in enumerate(vals):
                mine = None if at in donated else pool
                if isinstance(v, self._arrays):     # the common tile
                    flat.append(here(v, mine, used))
                elif v is not None:
                    flat.extend(here(leaf, mine, used)
                                for leaf in leaves(v))
        return flat

    # ------------------------------------- copies of other chips' tiles

    def _copy_here(self, leaf, pool, used=None):
        """This chip's copy of ``leaf``, a tile committed to another
        chip: the one a reader before had made, if that version's is
        still here (``remote_hits``), else a new one, chip to chip (no
        host hop: ``leaf`` is a device array), under ``parsec:stage_in``
        where the stage timers are on (``remote_copies``,
        ``remote_bytes_in``, ``remote_copy_s``). The first reader of a
        version on this chip makes the copy, outside the module's lock
        (a ``device_put`` takes the host half a millisecond, and the
        readers of other tiles need not stand behind it); a reader of
        the same version meanwhile waits for that one copy and makes no
        second. A copy goes when its version does (superseded in its
        collection and dropped by its last task: the weak reference
        says), when the pool that had it made ends (``drop_copies``), or
        as the least recently read once ``REMOTE_BYTES`` are held
        (``_room_for``); a launch that reads it pins it from staging to
        its call (``used``)."""
        key = id(leaf)
        with self._copies_lock:
            self._let_go_dead()
            copy = self._copies.get(key)
            mine = copy is None or copy.source() is not leaf
            if mine:
                timed = pool.context is not None and \
                    pool.context.stage_timers
                self._room_for(leaf.nbytes, timed)
                copy = self._copies[key] = _Copy(
                    weakref.ref(leaf, lambda _ref, key=key,
                                dead=self._dead: dead.append(key)),
                    leaf.nbytes, id(pool))
                self._copy_bytes += copy.nbytes
            else:
                self._copies.move_to_end(key)
                self.stats["remote_hits"] += 1
            copy.pins += 1
            making = copy.making
        if mine:
            try:
                copy.value, took = self._under(
                    SPAN_STAGE_IN, timed, self.jax.device_put, leaf,
                    self.jax_device)
            finally:
                with self._copies_lock:
                    copy.making = None
                    if copy.value is None:      # nobody finds a failed one
                        if self._copies.get(key) is copy:
                            self._let_go(key)
                    else:
                        self.stats["remote_copies"] += 1
                        self.stats["remote_bytes_in"] += copy.nbytes
                        self.stats["remote_copy_s"] += took
                making.set()
        elif making is not None:
            making.wait()
            if copy.value is None:
                raise RuntimeError(
                    f"{self.name}: the copy of a tile of another chip "
                    f"that another reader was making failed")
        if used is not None:
            used.append(copy)           # unpinned after the launch's call
        else:
            with self._copies_lock:
                copy.pins -= 1
        return copy.value

    @staticmethod
    def _over(mark) -> bool:
        """Is the launch ``mark`` tells of over, as far as a look says?"""
        leaf = mark() if mark is not None else None
        return leaf is None or leaf.is_deleted() or leaf.is_ready()

    def _let_go(self, key: int) -> None:
        """Forget the copy under ``key`` (under the lock): its bytes
        stay counted until its last reader is over (``_retired``)."""
        copy = self._copies.pop(key)
        self._retired.append((copy.mark, copy.nbytes))

    def _let_go_dead(self) -> None:
        """Under the lock: the copies whose version has died go, and
        the bytes of those let go whose last reader is over."""
        while self._dead:
            key = self._dead.popleft()
            copy = self._copies.get(key)
            if copy is not None and copy.source() is None:
                self._let_go(key)
        while self._retired and self._over(self._retired[0][0]):
            self._copy_bytes -= self._retired.popleft()[1]

    def _room_for(self, nbytes: int, timed: bool) -> None:
        """Under the lock: keep what the module's copies hold, those let
        go and still read on the chip among them, within
        ``REMOTE_BYTES`` with ``nbytes`` more. The oldest let go is
        waited for (the host waits for the chip: ``parsec:exec_wait``,
        ``chip_wait_s``); with none, the least recently read copy that no
        launch has pinned is let go. The host runs ahead of the chip by
        the copies it may enqueue and no further."""
        waits = []
        while self._copy_bytes + nbytes > REMOTE_BYTES:
            if self._retired:
                mark, n = self._retired.popleft()
                self._copy_bytes -= n
                if not self._over(mark):
                    waits.append(self._under(
                        SPAN_EXEC_WAIT, timed,
                        mark().block_until_ready)[1])
                continue
            key = next((k for k, c in self._copies.items()
                        if not c.pins), None)
            if key is None:
                break           # every copy here is being read now
            self._let_go(key)
        if waits:
            with self._lock:
                if max(waits) > self.stats["chip_wait_max_s"]:
                    self.stats["chip_wait_max_s"] = max(waits)
                if timed:
                    self.stats["chip_wait_s"] += sum(waits)
                    self.stats["chip_waits"] += len(waits)

    def drop_copies(self, pool) -> None:
        """Let go of the copies ``pool``'s tasks had made: a finished
        pool holds no tile (the Context calls this when a pool ends)."""
        if not self._copies and not self._dead:
            return
        with self._copies_lock:
            for key in [k for k, c in self._copies.items()
                        if c.pool == id(pool)]:
                self._let_go(key)
            self._let_go_dead()

    def copies_held(self) -> Tuple[int, int]:
        """``(copies, bytes)`` of other chips' tiles this module keeps
        now, the dead ones let go first."""
        with self._copies_lock:
            self._let_go_dead()
            return len(self._copies), sum(
                c.nbytes for c in self._copies.values())

    def _pinned(self, chore: Chore) -> Chore:
        """A self-dispatching hook (an impure DTD body; a PTG body that
        jits inside with its locals static) pinned to THIS module's chip:
        without it the body runs wherever its inputs happen to sit, and
        on a multi-chip host every module then computes on chip 0. Made
        once a chore."""
        mine = self._record(chore)
        pinned = mine.get("pinned")
        if pinned is None:
            jax, dev, here = self.jax, self.jax_device, self._here
            own = weakref.ref(chore)    # the record must not keep it

            def hook(t, *vals):
                def array_here(leaf):
                    # a host value is the body's own to read: it may be
                    # host code that writes into its numpy tile
                    # (serving/decode.py)
                    return here(leaf, t.taskpool) \
                        if isinstance(leaf, jax.Array) else leaf

                with jax.default_device(dev):
                    return own().hook(t, *(
                        jax.tree_util.tree_map(array_here, v) for v in vals))

            pinned = mine["pinned"] = Chore(chore.device_type, hook)
        return pinned

    # --------------------------------------------------- program table

    def _sizes(self, named, chore: Optional[Chore]):
        """The sizes of ``GROUP_SIZES`` a launch of members like the one
        whose ``(flow name, value)`` pairs are ``named`` may have,
        largest first: the operands ``chore``'s stacked form shares
        counted once, the others once a member."""
        leaves = self.jax.tree_util.tree_leaves
        shared = chore.batch_hook_shared if chore is not None else None
        nbytes = [0, 0]                         # a member's own, shared
        for name, v in named:
            if isinstance(v, self._arrays):     # the common tile
                n = v.nbytes
            elif v is None:
                continue
            else:
                n = sum(getattr(leaf, "nbytes", 0) for leaf in leaves(v))
            nbytes[bool(shared) and name in shared] += n
        return [size for size in GROUP_SIZES
                if nbytes[1] + size * nbytes[0] <= GROUP_BYTES]

    def group_limit(self, task: Task, chore: Optional[Chore] = None) -> int:
        """The most tasks like ``task`` one launch of ``chore`` may
        carry, by the inputs it holds now (a PTG task's collection reads
        are resolved later: ``execute_group`` decides on the whole
        signature)."""
        sizes = self._sizes(task.data.items(), chore)
        return sizes[0] if sizes else 0

    def _sig(self, values):
        """Signature of one task's input values: tasks share a program
        only when every position agrees on (None-ness, pytree structure,
        leaf shapes/dtypes). None where a leaf is neither an array nor a
        number: the task runs its chore's own hook."""
        tu = self.jax.tree_util
        sig = []
        for v in values:
            if v is None:
                sig.append(None)
            elif isinstance(v, self._arrays):   # the common tile
                sig.append((v.shape, v.dtype))
            else:
                leaves, treedef = tu.tree_flatten(v)
                leaf_sig = []
                for leaf in leaves:
                    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
                        leaf_sig.append((tuple(leaf.shape), leaf.dtype))
                    elif isinstance(leaf, numbers.Number):
                        leaf_sig.append(("scalar", type(leaf).__name__))
                    else:
                        return None
                sig.append((str(treedef), tuple(leaf_sig)))
        return tuple(sig)

    @staticmethod
    def _hook_ok(chore: Chore, tasks: List[Task]) -> bool:
        """May this group use the chore's hand-batched ``batch_hook``?
        Shared flows must hold ONE value object across the group (the
        wavefront executor's _hook_applies check, by value identity —
        a host-runtime TRSM wave shares its factor from one producer)."""
        for name in chore.batch_hook_shared or ():
            first = tasks[0].data.get(name)
            if any(t.data.get(name) is not first for t in tasks[1:]):
                return False
        return True

    def _record(self, chore: Chore) -> Dict[Any, Any]:
        """This module's record of ``chore``."""
        cid = id(chore)
        of = self._chores.get(cid)
        if of is not None and of() is chore:
            return self._table[cid]
        with self._table_lock:
            of = self._chores.get(cid)
            if of is None or of() is not chore:
                # id(chore) is reused once a pool's chore is gone: the
                # records of the chores that have died go now, this id's
                # among them
                for dead in [i for i, ref in self._chores.items()
                             if ref() is None]:
                    del self._chores[dead], self._table[dead]
                self._table[cid] = {}
                self._chores[cid] = weakref.ref(chore)
            return self._table[cid]

    def _programs(self, task: Task, chore: Chore, values, sig,
                  stacked: bool = False) -> Dict[int, Callable]:
        """``{size: program}`` for ``chore`` on inputs of signature
        ``sig``; ``program(*leaves of every member) -> one result per
        member``, and ``program.held`` the bytes of new outputs a launch
        of it holds (``_build``). Unrolled over the chore's pure body:
        every size of
        ``GROUP_SIZES`` that ``GROUP_BYTES`` admits and 1, built and run
        once the first time a signature is seen, so nothing compiles in a
        later step, whatever sizes it forms. A chore with a
        ``batch_hook`` has size 1 alone there and the group sizes
        ``stacked`` over that hook, built when its first group forms: a
        class whose tasks never meet (a Cholesky's POTRF) does not pay
        for them beside a resident matrix. Empty for a body that
        dispatches itself and for inputs that share no program (``_sig``;
        a body woven from its task's own arguments, so keyed, with a flow
        that holds no value)."""
        pure = chore.pure_body(task)
        if pure is None or sig is None or \
                (pure[0] is not None and None in sig):
            return {}
        key, body = pure
        mine = self._record(chore)
        programs = mine.get((key, sig, stacked))    # two dict hits a launch
        if programs is None:
            with self._table_lock:      # serializes compile-on-miss only
                for kind in ((False, True) if stacked else (False,)):
                    if (key, sig, kind) not in mine:
                        mine[key, sig, kind] = self._build(
                            task, chore, body, values, (key, sig), kind)
                programs = mine[key, sig, stacked]
        return programs

    def _build(self, task, chore, body, values, slot, stacked):
        from ..utils import compile_cache
        jax, tu, hook = self.jax, self.jax.tree_util, chore.batch_hook
        # a member's flows as pytrees (a flow without a value is None,
        # no leaf, and the body gets None in its place), and of those
        # the ones a batch_hook takes (READ flows, stacked: the wavefront
        # executor's convention)
        flows = [f for f in task.task_class.flows if not f.is_ctl]
        shape = [tu.tree_structure(v) for v in values]
        reads = tuple(bool(f.access & FlowAccess.READ) and v is not None
                      for f, v in zip(flows, values))

        def members(flat, size):
            it = iter(flat)
            return [[tu.tree_unflatten(td, [next(it) for _ in
                                            range(td.num_leaves)])
                     for td in shape] for _ in range(size)]

        # the leaves of a member the chore hands over (``Chore.donates``:
        # the program writes the flow's output where its input lies, so
        # a launch still queued holds nothing new for it)
        given, at = [], 0
        for f, td in zip(flows, shape):
            if f.name in (chore.donates or ()):
                given += range(at, at + td.num_leaves)
            at += td.num_leaves

        def donated(size):
            return tuple(m * at + i for m in range(size) for i in given)

        # the positions, among a member's flows, of those the program
        # writes into: never staged from a copy the module keeps
        donated_at = frozenset(
            i for i, f in enumerate(flows)
            if f.name in (chore.donates or ()))

        def marked(program):
            # an output of such a program may be given to a later launch
            # before anyone has waited for this one: it returns, after
            # its members, one element of its first output to wait for
            def fn(*flat):
                res = program(*flat)
                return res + (tu.tree_leaves(res)[0].ravel()[:1],)
            return fn

        def unrolled(size):
            # flat and unrolled: no stack, no vmap, no slicing; each
            # output is its own buffer, whatever the size
            return lambda *flat: tuple(
                body(*vals) for vals in members(flat, size))

        def stacked_over(size):
            def program(*flat):
                cols = zip(*members(flat, size))
                args = [tu.tree_map(lambda *x: jax.numpy.stack(x), *col)
                        for col, read in zip(cols, reads) if read]
                res = hook(*args)
                return tuple(tu.tree_map(lambda x, i=i: x[i], res)
                             for i in range(size))
            return program

        # the leaves of a member that are not floating point (pivots):
        # (tiles, bytes) of a member's own and of those the stacked form
        # shares, which a launch holds once; None where there are none
        ints = [[0, 0], [0, 0]]
        for f, v in zip(flows, values):
            for leaf in tu.tree_leaves(v):
                kind = getattr(getattr(leaf, "dtype", None), "kind", "f")
                if kind in "iub":
                    one_launch = stacked and f.name in (
                        chore.batch_hook_shared or ())
                    ints[one_launch][0] += 1
                    ints[one_launch][1] += leaf.nbytes
        ints = tuple(map(tuple, ints)) if ints[0][0] or ints[1][0] else None
        # the tiles of a member that are elements of a ranged flow's
        # list: operands of the launch like any other (a program a list
        # length: the length is in the signature), donated and read off
        # the first run one by one
        ranged = sum(len(v) for v in values if isinstance(v, list))

        # equal bodies across taskpools, contexts and device modules
        # trace once: a stable fingerprint shares the program process-
        # wide; an unstable one stays with this chore
        stable, fp = compile_cache.function_fingerprint(
            hook if stacked else body)
        # what the body's kernels ask of the chip's compiler
        # (``Chore.compiler_options``; the CPU platform knows none)
        options = chore.compiler_options if self.platform == "tpu" else None
        shared = ("tpu_program", fp, stacked, reads, tuple(given),
                  tuple(sorted((options or {}).items())), *slot) \
            if stable else None
        sizes = self._sizes(zip((f.name for f in flows), values), chore)
        if not stacked:
            sizes = [1] if hook is not None else sizes + [1]
        programs, one = {}, None
        with jax.default_device(self.jax_device):
            for size in sizes:
                fn = (stacked_over if stacked else unrolled)(size)
                if given:
                    fn = marked(fn)
                # the program's name is what a device trace keeps of a
                # launch (its "XLA Modules" line: jit_parsec_<class>_x<n>)
                fn.__name__ = fn.__qualname__ = "parsec_%s_x%d" % (
                    re.sub(r"\W", "_", getattr(task.task_class, "name",
                                                "task")), size)
                def jit(f, gives=donated(size)):
                    return jax.jit(f, donate_argnums=gives,
                                   compiler_options=options)
                fn = jit(fn) if shared is None else compile_cache.cached_jit(
                    fn, key=(*shared, size), persist=False, jit_wrapper=jit)
                # a shared program this module has yet to run compiles
                # for its chip now (an unshared one is new), and its
                # first run says what a launch of it holds anew: the
                # outputs that lie in no buffer it was given. Read off
                # the program, not off the declaration: a stacked form's
                # results are slices of one product, and whether they
                # come back in the members' own buffers is XLA's doing
                if shared is None or (shared, size) not in self._warmed \
                        or not hasattr(fn, "held"):
                    self._warmed.add((shared, size))
                    one = one or self._flat([values])
                    args = one * size
                    for i in donated(size):     # the task's own stay
                        args[i] = jax.numpy.copy(args[i])
                    given_to = {args[i].unsafe_buffer_pointer()
                                for i in donated(size)}
                    # compiles; the result is dropped
                    fn.held = sum(
                        x.nbytes for x in tu.tree_leaves(fn(*args)[:size])
                        if x.unsafe_buffer_pointer() not in given_to)
                fn.donated_at = donated_at
                fn.ints = ints
                fn.ranged = ranged
                programs[size] = fn
        return programs
