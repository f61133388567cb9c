"""TPU device module.

Replaces the reference's CUDA device pipeline
(mca/device/cuda/device_cuda_module.c, 2,765 LoC) with an XLA-native
design. The reference pipelines each GPU task through stage-in → kernel →
stage-out streams with event-driven progress; on TPU the equivalent roles
are played by XLA/PJRT itself:

- *stage-in/out*: ``jax.device_put`` / implicit transfer of host values;
  tile data produced by previous TPU tasks stays resident in HBM as
  ``jax.Array`` and flows to successors without host bounce.
- *streams + events*: JAX dispatch is asynchronous — calling a jitted body
  returns immediately with future-backed arrays, so consecutive tasks
  pipeline on device; blocking only happens at final writebacks.
- *kernel lookup* (reference cuda_find_incarnation, dyld by name): bodies
  are Python jnp/pallas functions jitted per task class on first use and
  cached (XLA compile cache handles shape variants).

*Group launch* (``execute_group``): the worker that selected several
ready tasks of one taskpool (``Context._take_group``: one bin per body,
whatever classes were ready together) has each bin issued here as ONE
jitted program. Every batchable body has one: it calls the body once per
member, flat and unrolled — the device does the same work per task in
the same buffers, the host pays one trip through jit dispatch for the
group — or, for a body with a ``batch_hook``, that hook over the stacked
members. A module has one group in flight (``group_turn``; the next
launch waits for the last one's output) and a launch carries the largest
size of ``GROUP_SIZES`` whose members' inputs together stay within
``GROUP_BYTES``, because what a launch makes waits in HBM for its
members' release: small tasks go many to a launch, large ones alone, by
the bytes this module sees and nothing else. Whole taskpools lowered to
one program are ``parsec_tpu.compiled``'s business, not this module's.
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Tuple

from .base import Device
from ..core.context import SPAN_EXEC, StageSpan
from ..core.task import (GROUP_SIZES, Chore, DeviceType, FlowAccess,
                         HookReturn, Task, normalize_outputs)
from ..utils.debug import debug_verbose


# The outputs of a launch are allocated when it is enqueued and wait
# there for their members' release, one by one; and XLA's program for
# several large tiles is slower than the tiles' own programs (64 GEMMs of
# 4096^3 as four programs of sixteen: half again the device time, PERF.md
# section 6, PR 25), while a task that size keeps the chip busy longer
# than its launch costs the host. So a launch carries the largest size of
# ``GROUP_SIZES`` whose members' inputs, all together, stay within this
# many bytes: f32 GEMMs of 1024-tiles (12 MiB a task) go eight at a time,
# of 2048-tiles (48 MiB) four at a time, of 4096-tiles (192 MiB) alone.
# Settled on the v5e (PERF.md section 6, PR 28): the program of four
# 2048-tile GEMMs costs the device no more than the four alone (0.43 s a
# factorization against 0.45) and the host a third of their launches;
# two at a time under 128 MiB left the host 40% slower, and a size 2
# beside the four moved no step time. 2 x 192 MiB stays refused.
GROUP_BYTES = 192 << 20


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
        self._jit_cache: Dict[Any, Callable] = {}
        self._cache_lock = threading.Lock()
        # group programs: {(id(chore), batch_sig, input signature):
        # {size: program}}, an entry dropped when its chore dies; and the
        # process-shared programs this module has already run once
        # {id(chore): {(batch_sig, signature): {size: program}}}
        self._group_cache: Dict[int, Dict[Any, Dict[int, Callable]]] = {}
        self._group_lock = threading.Lock()
        # one group in flight: held from taking the tasks to the last
        # member's release (Context._group_progress); and an output of
        # the last launch, which the next one waits for
        self.group_turn = threading.Lock()
        self._group_out: Any = None
        self._warmed: set = set()
        self.stats["batches"] = 0
        self.stats["batched_tasks"] = 0
        debug_verbose(3, "device", "TPU device on %s (%s)",
                      self.jax_device, self.platform)

    def _jitted(self, task: Task, chore: Chore) -> Callable:
        # per-device first-level lookup stays ONE dict hit (this runs
        # per task on the dispatch hot path — the PR 3 overhead budget);
        # the (tc_id, taskpool_id, id(chore)) key guards id() reuse of
        # a GC'd pool's chore. Jit-cache unification happens at BUILD
        # time only: on a miss, bodies with a stable code fingerprint
        # fetch their wrapper from the process-wide compile_cache store,
        # so a new taskpool, a new Context, or a second TPUDevice for
        # the same body never re-traces. Unstable fingerprints stay
        # per-instance — never shared on an id()-grade identity.
        key = (task.task_class.tc_id, task.taskpool.taskpool_id, id(chore))
        fn = self._jit_cache.get(key)
        if fn is None:
            with self._cache_lock:
                fn = self._jit_cache.get(key)
                if fn is None:
                    from ..utils import compile_cache
                    body = chore.hook
                    stable, fp = compile_cache.function_fingerprint(body)
                    if stable:
                        fn = compile_cache.cached_jit(
                            lambda *tiles, _b=body: _b(None, *tiles),
                            key=("tpu_body", fp), persist=False)
                    else:
                        # bodies take (task, *tiles); the task argument
                        # is host-side metadata — closed over as static
                        fn = self.jax.jit(
                            lambda *tiles, _b=body: _b(None, *tiles))
                    self._jit_cache[key] = fn
        return fn

    def execute(self, es, task: Task, chore: Chore) -> HookReturn:
        if task.taskpool.context.stage_timers:
            # the enqueue as the host pays it (staging device_puts,
            # default_device, the jitted call until it returns), not the
            # device's work
            with StageSpan(SPAN_EXEC):
                return self._launch(task, chore)
        return self._launch(task, chore)

    def _launch(self, task: Task, chore: Chore) -> HookReturn:
        return self._run_hook(task, self._placed(task, chore))

    def _placed(self, task: Task, chore: Chore) -> Chore:
        """``chore`` with its hook pinned to THIS module's chip."""
        # Bodies that need task metadata (locals) opt out of the jit cache
        # by setting chore.batchable = False → called directly (they may
        # jit internally with locals as static args).
        if not chore.batchable:
            return self._pinned(chore)
        return self._staged(task, chore)

    def _move(self, leaf):
        """A leaf committed to another chip, moved here (jit raises on
        mixed committed placements); host values and uncommitted arrays
        follow ``default_device``."""
        if isinstance(leaf, self.jax.Array) and leaf.committed and \
                getattr(leaf, "device", None) not in (None,
                                                      self.jax_device):
            return self.jax.device_put(leaf, self.jax_device)
        return leaf

    def _pinned(self, chore: Chore) -> Chore:
        """A self-dispatching hook (DTD woven bodies jit themselves)
        pinned to THIS module's chip: without it the body runs wherever
        its inputs happen to sit, and on a multi-chip host every module
        then computes on chip 0."""
        jax, dev, move = self.jax, self.jax_device, self._move

        def hook(t, *vals):
            with jax.default_device(dev):
                return chore.hook(
                    t, *(jax.tree_util.tree_map(move, v) for v in vals))

        return Chore(device_type=chore.device_type, hook=hook,
                     evaluate=chore.evaluate)

    def _staged(self, task: Task, chore: Chore) -> Chore:
        jitted = self._jitted(task, chore)

        def hook(t, *tiles):
            # pin this module's chip: default_device alone does NOT
            # decide placement — committed inputs win (and inputs
            # committed to different chips make jit raise), so stage
            # every input onto this module's device explicitly
            # (device_put is a no-op for already-resident buffers)
            staged = [self.jax.device_put(x, self.jax_device)
                      if x is not None else None for x in tiles]
            with self.jax.default_device(self.jax_device):
                return jitted(*staged)

        return Chore(device_type=chore.device_type, hook=hook,
                     evaluate=chore.evaluate)

    # ---------------------------------------------------- group launch
    # The reference pipelines each GPU task through a manager owning the
    # device's streams (progress_stream, device_cuda_module.c:1961-2097).
    # Here the leverage is the LAUNCH: N ready tasks of one body become
    # one jitted call, dividing the trip through jit dispatch (and the
    # hand-offs of the GIL around it) by N. The worker that selected the
    # tasks forms the group (Context._take_group); nothing changes
    # thread.

    @staticmethod
    def _sizes(nbytes: int):
        """The sizes of ``GROUP_SIZES`` that members of ``nbytes`` bytes
        of inputs each may fill, largest first."""
        return [size for size in GROUP_SIZES
                if size * nbytes <= GROUP_BYTES]

    def group_limit(self, task: Task) -> int:
        """The most tasks like ``task`` one launch may carry, by the
        inputs it holds now (a PTG task's collection reads are resolved
        later: ``execute_group`` decides on the whole signature)."""
        leaves = self.jax.tree_util.tree_leaves
        nbytes = 0
        for v in task.data.values():
            if isinstance(v, self._arrays):     # the common tile
                nbytes += v.nbytes
            elif v is not None:
                nbytes += sum(getattr(leaf, "nbytes", 0)
                              for leaf in leaves(v))
        sizes = self._sizes(nbytes)
        return sizes[0] if sizes else 0

    def execute_group(self, es, tasks: List[Task], chore: Chore) -> int:
        """Launch the first tasks of ``tasks`` (one chore, one
        ``batch_sig``; prepared by the caller) as ONE program, attach
        their outputs and return how many they were: the largest size of
        ``GROUP_SIZES`` that ``tasks`` fills with inputs of one signature
        and that ``GROUP_BYTES`` admits. 0 where the first task has to go
        alone (the caller takes the single path). Members that differ in
        signature never share a program. Raises where the launch does."""
        values = [tasks[0].input_values()]
        sig = self._sig(values[0])
        programs = self._group_programs(tasks[0], chore, values[0], sig)
        for size, program in programs.items():      # largest first
            if size > len(tasks):
                continue
            while len(values) < size:
                more = tasks[len(values)].input_values()
                if self._sig(more) != sig:
                    break
                values.append(more)
            if len(values) < size or \
                    not self._hook_ok(chore, tasks[:size]):
                continue
            if tasks[0].taskpool.context.stage_timers:
                with StageSpan(SPAN_EXEC):      # one span per launch
                    self._launch_group(tasks[:size], program,
                                       values[:size])
            else:
                self._launch_group(tasks[:size], program, values[:size])
            return size
        return 0

    def _launch_group(self, tasks, program, values) -> None:
        t0 = time.perf_counter()
        flat = self._flat(values)
        # the runtime bounds the launches it queues, not their bytes: a
        # chip that lags a few ms behind would hold the outputs and the
        # inputs of as many groups. The chip is far ahead wherever groups
        # form (GROUP_BYTES), so this wait is a check
        if self._group_out is not None:
            self._group_out.block_until_ready()
        with self.jax.default_device(self.jax_device):
            results = program(*flat)
        done = self.jax.tree_util.tree_leaves(results[-1])
        self._group_out = done[0] if done else None
        names = [f.name for f in tasks[0].task_class.output_flows]
        for t, res in zip(tasks, results):
            t.output.update(normalize_outputs(res, names, t))
        with self._lock:
            self.stats["tasks"] += len(tasks)
            self.stats["exec_s"] += time.perf_counter() - t0
            self.stats["batches"] += 1
            self.stats["batched_tasks"] += len(tasks)
            if tasks[0].taskpool.context.stage_timers:
                self._count_launch(tasks[0], len(tasks))

    def _flat(self, values) -> List[Any]:
        """The members' input leaves in order, None-valued flows left
        out, each on this module's chip."""
        leaves, move = self.jax.tree_util.tree_leaves, self._move
        flat: List[Any] = []
        for vals in values:
            for v in vals:
                if isinstance(v, self._arrays):     # the common tile
                    flat.append(move(v))
                elif v is not None:
                    flat.extend(move(leaf) for leaf in leaves(v))
        return flat

    def _sig(self, values):
        """Signature of one task's input values: tasks share a group
        program only when every position agrees on (None-ness, pytree
        structure, leaf shapes/dtypes). None where a leaf is neither an
        array nor a number: the task runs alone."""
        import numbers
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

    def _group_programs(self, task: Task, chore: Chore, values,
                        sig: Tuple) -> Dict[int, Callable]:
        """``{size: program}``, largest first, for ``chore`` on inputs of
        signature ``sig``; ``program(*leaves of every member) -> one
        result per member``. Every size of ``GROUP_SIZES`` that
        ``GROUP_BYTES`` admits is built, and run once, the first time a
        signature is seen, and so is the single path's program: whatever
        sizes a later step forms, nothing compiles then. Empty for inputs
        that share no program (``_sig``; a woven body with a flow that
        holds no value)."""
        mine = self._group_cache.get(id(chore))
        if mine is None:
            with self._group_lock:
                mine = self._group_cache.get(id(chore))
                if mine is None:
                    mine = self._group_cache[id(chore)] = {}
                    # id(chore) is reused once the pool's chore is gone
                    weakref.finalize(chore, self._group_cache.pop,
                                     id(chore), None)
        if sig is None or (None in sig and chore.batch_body is not None):
            return {}
        bsig = chore.batch_sig(task) if chore.batch_sig is not None \
            else None
        programs = mine.get((bsig, sig))    # two dict hits per launch
        if programs is None:
            with self._group_lock:      # serializes compile-on-miss only
                programs = mine.get((bsig, sig))
                if programs is None:
                    programs = mine[bsig, sig] = \
                        self._build_group_programs(
                            task, chore, values, bsig, sig)
        return programs

    def group_due(self, chore: Chore) -> bool:
        """Should this module be handed tasks of ``chore`` too few for a
        group, to build its programs on them? Once, for a body whose
        program is the one task's own, repeated: its class then has them
        from the pool's first step on, however rarely its tasks meet. A
        ``batch_hook`` is another program than the lone task's, stacked
        and vmapped, and is built when its first group forms: a class
        whose tasks never meet (a Cholesky's POTRF) does not pay for it
        beside a resident matrix."""
        return chore.batch_hook is None and \
            id(chore) not in self._group_cache

    def _build_group_programs(self, task, chore, values, bsig, sig):
        from ..utils import compile_cache
        jax, tu = self.jax, self.jax.tree_util
        if chore.batch_body is not None:
            kind, body = "woven", chore.batch_body(task)
        elif chore.batch_hook is not None:
            kind, body = "hooked", chore.batch_hook
        else:
            # the plain hook, as the single path jits it: the task is
            # host-side metadata a batchable body does not read, and a
            # flow without a value is None in its place (``sig`` says
            # which, so the hook and ``sig`` identify the program)
            kind, hook = "plain", chore.hook
            held = [v is not None for v in values]

            def body(*vals):
                it = iter(vals)
                return hook(None, *(next(it) if h else None for h in held))
        # (treedef, leaves) of a member's flows that hold a value, and of
        # those the ones a batch_hook takes (READ flows, stacked: the
        # wavefront executor's convention)
        flows = [f for f in task.task_class.flows if not f.is_ctl]
        info = [(td, td.num_leaves) for td in
                (tu.tree_structure(v) for v in values if v is not None)]
        reads = tuple(bool(f.access & FlowAccess.READ)
                      for f, v in zip(flows, values) if v is not None)

        def members(flat, size):
            it = iter(flat)
            return [[tu.tree_unflatten(td, [next(it) for _ in range(nl)])
                     for td, nl in info] for _ in range(size)]

        def unrolled(size):
            # flat and unrolled: no stack, no vmap, no slicing; each
            # output is its own buffer, as when the tasks run alone
            return lambda *flat: tuple(
                body(*vals) for vals in members(flat, size))

        def stacked(size):
            def program(*flat):
                cols = zip(*members(flat, size))
                args = [tu.tree_map(lambda *x: jax.numpy.stack(x), *col)
                        for col, read in zip(cols, reads) if read]
                res = body(*args)
                return tuple(tu.tree_map(lambda x, i=i: x[i], res)
                             for i in range(size))
            return program

        # equal bodies across taskpools, contexts and device modules
        # trace once: a stable fingerprint shares the program process-
        # wide; an unstable one stays with this chore
        stable, fp = compile_cache.function_fingerprint(
            chore.hook if kind == "plain" else body)
        shared = ("tpu_group", fp, kind, reads, bsig, sig) \
            if stable else None

        def first_run(which) -> bool:
            """Has this module yet to run ``which`` of the shared
            programs? (An unshared one is new by construction.)"""
            if shared is None:
                return True
            new = (shared, which) not in self._warmed
            self._warmed.add((shared, which))
            return new

        one = self._flat([values])
        nbytes = sum(getattr(leaf, "nbytes", 0) for leaf in one)
        programs = {}
        with jax.default_device(self.jax_device):
            for size in self._sizes(nbytes):
                fn = (stacked if kind == "hooked" else unrolled)(size)
                if shared is None:
                    fn = jax.jit(fn)
                else:
                    fn = compile_cache.cached_jit(
                        fn, key=(*shared, size), persist=False)
                if first_run(size):
                    fn(*one * size)     # compiles; the result is dropped
                programs[size] = fn
        if first_run("alone"):
            self._placed(task, chore).hook(task, *values)
        return programs
