"""Wavefront compiler: PTG DAG → batched XLA execution.

Why this exists: the reference keeps the MXU-equivalent (CUDA cores) busy
by pipelining *individual* tile tasks through streams
(device_cuda_module.c pipeline). On TPU, per-task dispatch of tile-sized
kernels cannot reach a useful fraction of peak — launch + gap overheads
dominate and XLA can't fuse across dispatches. The TPU-idiomatic execution
of a task DAG is:

1. enumerate the task space (closed-form, from the PTG description);
2. level the DAG into *waves* (all tasks whose predecessors completed in
   earlier waves) — host-side topological leveling;
3. inside a wave, group tasks by task class and execute each group as ONE
   vmapped XLA call: gather the group's input tiles from a stacked
   HBM-resident store (one (ntiles, mb, nb) jax.Array per collection),
   run the batched body (a single large batched matmul for GEMM-like
   classes → MXU-friendly), scatter outputs back;
4. the whole schedule is a pure function ``stores → stores``, so it can be
   jitted end-to-end (one XLA program for the whole DAG) or dispatched
   wave-by-wave with power-of-two batch bucketing to bound compilation.

Store-based execution is valid when every intermediate tile version has
its readers ordered (by wave level) before the next writer of that tile —
true for accumulate-chain dense LA DAGs (POTRF/GEMM/QR). ``plan_taskpool``
verifies this *hazard-freedom* property while planning and rejects DAGs
that need value-passing (those run on the host runtime instead).
"""

from __future__ import annotations

import hashlib
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..core.task import DeviceType, FlowAccess, Task
from ..core.taskpool import DataRef
from ..dsl.ptg import PTGTaskClass, Taskpool as PTGTaskpool
from ..utils import compile_cache
from ..utils.debug import debug_verbose


@dataclass
class WaveGroup:
    """All tasks of one class inside one wave (sub-grouped by reshape
    signature when dep ``[type=...]`` specs differ across instances)."""
    tc: PTGTaskClass
    level: int
    tasks: List[Tuple[int, ...]]
    # per non-CTL flow, (collection name, np.int32[B] tile-slot indices)
    in_slots: List[Tuple[str, np.ndarray]] = field(default_factory=list)
    out_slots: List[Tuple[str, np.ndarray]] = field(default_factory=list)
    # per in-flow composed ReshapeSpec (or None), shared by every task
    # in the group — applied to the gathered stack before the body
    in_specs: List[Optional[Any]] = field(default_factory=list)


@dataclass
class WavefrontPlan:
    taskpool: PTGTaskpool
    waves: List[List[WaveGroup]]
    collections: Dict[str, Any]              # name -> collection
    slot_maps: Dict[str, Dict[Tuple, int]]   # name -> (tile key -> slot)
    n_tasks: int = 0
    # True when some non-CTL flow carries task->task values with no tile
    # placement: only executors that keep values in carry state (the
    # panel-fused path) or the host runtime can run such plans
    has_value_flows: bool = False
    # dep [type=...] support: True when any dep declares a ReshapeSpec
    has_reshapes: bool = False
    # (collection name, slot) -> spec of the LAST terminal data write —
    # applied by write_back (the Out-side conversion of DataRef writes)
    terminal_specs: Dict[Tuple[str, int], Any] = field(default_factory=dict)

    @property
    def n_waves(self) -> int:
        return len(self.waves)


def plan_structure_fingerprint(plan: "WavefrontPlan"
                               ) -> Tuple[bool, str]:
    """``(stable, digest)`` over everything of a plan that shapes a
    traced program: collection geometry/dtypes, the full wave/group
    structure with slot indices, and reshape-spec identities. Equal
    digests ⇒ equal traces (given equal bodies/fusers, fingerprinted
    separately) — the key that lets rebuilt executors share jitted
    callables instead of re-tracing per function object."""
    h = hashlib.sha256()
    stable = True
    for name in sorted(plan.collections):
        dc = plan.collections[name]
        h.update(repr((name, dc.mb, dc.nb, dc.mt, dc.nt,
                       str(np.dtype(dc.dtype)),
                       bool(getattr(dc, "scratch", False)))).encode())
    for w, wave in enumerate(plan.waves):
        for grp in wave:
            h.update(repr((w, grp.tc.name, tuple(grp.tasks))).encode())
            for (nm, idx) in grp.in_slots:
                h.update(nm.encode())
                h.update(np.ascontiguousarray(idx).tobytes())
            for (nm, idx) in grp.out_slots:
                h.update(nm.encode())
                h.update(np.ascontiguousarray(idx).tobytes())
            for s in grp.in_specs:
                if s is None:
                    h.update(b"nospec")
                    continue
                h.update(repr(getattr(s, "key", None)).encode())
                ok, fp = compile_cache.function_fingerprint(s.fn)
                stable = stable and ok
                h.update(fp.encode())
    h.update(repr((plan.n_tasks, plan.has_value_flows,
                   plan.has_reshapes)).encode())
    return stable, h.hexdigest()


def class_body_fingerprint(tc: PTGTaskClass, device_type: DeviceType
                           ) -> Tuple[bool, str]:
    """``(stable, digest)`` of the bodies a compiled executor may trace
    for ``tc``: the chore hook plus its batched reformulations."""
    chore = tc.chore_for(device_type) or tc.chore_for(DeviceType.CPU)
    if chore is None:
        return False, f"nobody:{tc.name}"
    parts, stable = [tc.name], True
    for fn in (chore.hook, chore.batch_hook, chore.batch_body):
        if fn is None:
            parts.append("none")
            continue
        ok, fp = compile_cache.function_fingerprint(fn)
        stable = stable and ok
        parts.append(fp)
    parts.append(repr(tuple(getattr(chore, "batch_hook_shared", None)
                            or ())))
    return stable, hashlib.sha256(
        "\x00".join(parts).encode()).hexdigest()


def _flow_tile(tc: PTGTaskClass, fname: str, locals) -> Tuple[Any, Tuple]:
    spec = tc.specs[fname]
    if spec.tile is None:
        raise ValueError(
            f"compiled mode requires FlowSpec.tile on {tc.name}.{fname}")
    dc, key = spec.tile(tc.tp.g, *locals)
    return dc, tuple(key)


def _is_value_flow(tc: PTGTaskClass, f) -> bool:
    """Non-CTL flow with no tile placement: a task->task value (e.g. a
    whole factored panel) that never lives in a collection. Such flows
    still level the DAG (their edges order waves) but have no slots; the
    per-tile executors cannot feed them — wave fusers carry them in
    state, the host runtime passes them with activations."""
    return (not f.is_ctl) and tc.specs[f.name].tile is None


def plan_taskpool(tp: PTGTaskpool) -> WavefrontPlan:
    """Enumerate, level, group and hazard-check a PTG taskpool.

    Dep ``[type=...]`` reshape specs (parsec_reshape.c analog) are
    static per-edge layout maps, so the planner resolves them up front:
    each consumer's composed (Out ∘ In) spec is recorded per group and
    applied to the gathered stack at execution (XLA fuses the cast/
    transpose into the body); terminal DataRef specs are applied by
    write_back. Groups whose instances disagree on specs are split."""
    from ..dsl.ptg import (taskpool_has_ranged_flows, taskpool_uses_reshape,
                           taskpool_writes_regions)
    if taskpool_writes_regions(tp):
        raise ValueError(
            f"taskpool {tp.name}: a write-back of a region of a tile "
            f"(Out(region=...)) is the host runtime's: an executor "
            f"scatters whole tiles")
    if taskpool_has_ranged_flows(tp):
        raise ValueError(
            f"taskpool {tp.name}: a ranged data flow (In(gather=True) on "
            f"a data flow, Out(scatter=True)) is the host runtime's: an "
            f"executor gathers ONE tile a flow from its stacked stores")
    has_reshapes = taskpool_uses_reshape(tp)
    # ---- enumerate tasks and assign ids
    tasks: List[Tuple[PTGTaskClass, Tuple[int, ...]]] = []
    tid: Dict[Tuple[str, Tuple], int] = {}
    for tc in tp.task_classes:
        for p in tc.enumerate_space():
            tid[(tc.name, p)] = len(tasks)
            tasks.append((tc, p))
    n = len(tasks)

    # ---- build successor edges via the closed-form iterators
    succs: List[List[int]] = [[] for _ in range(n)]
    edges: List[Tuple[int, int, str]] = []   # (producer, consumer, flow)
    # (consumer tid, flow) -> composed producer∘consumer ReshapeSpec
    # (None recorded for spec-less edges so mixed spec/no-spec fan-ins
    # are detectable; consumers treat stored-None as missing)
    edge_specs: Dict[Tuple[int, str], Any] = {}
    _NO_SPEC = object()
    indeg = np.zeros(n, dtype=np.int64)
    for i, (tc, p) in enumerate(tasks):
        dry = Task(tp, tc, p)
        for f in tc.flows:
            dry.data[f.name] = 0
            dry.output[f.name] = 0
        for ref in tc.iterate_successors(dry):
            if isinstance(ref, DataRef):
                continue
            j = tid[(ref.task_class.name, tuple(ref.locals))]
            succs[i].append(j)
            edges.append((i, j, ref.flow_name))
            # conflicting per-(consumer, flow) reshape specs — including
            # a reshaped edge mixed with an unreshaped one — would
            # silently apply one edge's spec to every gathered operand;
            # detect at plan time and direct such DAGs to the host
            # runtime (which applies specs per edge)
            prev = edge_specs.get((j, ref.flow_name), _NO_SPEC)
            # identity = (name, fn): name alone would let two same-named
            # specs with DIFFERENT fns through, silently applying one
            # edge's fn to both gathered operands — the exact
            # misconversion this guard exists to reject
            new_id = ((ref.reshape_spec.name, ref.reshape_spec.fn)
                      if ref.reshape_spec is not None else None)
            if prev is not _NO_SPEC:
                prev_id = ((prev.name, prev.fn)
                           if prev is not None else None)
                if prev_id != new_id:
                    ctc, cp = tasks[j]
                    pn = prev.name if prev is not None else None
                    nn = (ref.reshape_spec.name
                          if ref.reshape_spec is not None else None)
                    what = (f"same name {pn!r} but different fn objects "
                            "(share ONE ReshapeSpec instance across "
                            "edges when the conversion is the same)"
                            if pn == nn else f"{pn!r} vs {nn!r}")
                    raise ValueError(
                        f"task {ctc.name}{cp} flow {ref.flow_name!r} "
                        f"receives conflicting reshape specs ({what}) "
                        "on different incoming edges; the compiled "
                        "executors apply one spec per gathered flow — "
                        "run this taskpool on the host runtime")
            edge_specs[(j, ref.flow_name)] = ref.reshape_spec
            indeg[j] += 1

    # ---- Kahn leveling (batched in the C++ core when available)
    from .. import _native
    native_levels = None
    if n and _native.available():
        try:
            native_levels = _native.kahn_levels(
                n, [(i, j) for (i, j, _f) in edges])
        except RuntimeError as exc:
            raise RuntimeError(f"PTG DAG has a cycle: {exc}") from exc
    if native_levels is not None:
        level = np.asarray(native_levels, dtype=np.int64)
    else:
        level = np.zeros(n, dtype=np.int64)
        frontier = [i for i in range(n) if indeg[i] == 0]
        seen = len(frontier)
        while frontier:
            nxt = []
            for i in frontier:
                for j in succs[i]:
                    level[j] = max(level[j], level[i] + 1)
                    indeg[j] -= 1
                    if indeg[j] == 0:
                        nxt.append(j)
                        seen += 1
            frontier = nxt
        if seen != n:
            raise RuntimeError("PTG DAG has a cycle")

    # ---- per-task input reshape specs (static, from the closed form)
    def _in_flows(tc: PTGTaskClass):
        return [f for f in tc.flows if not f.is_ctl
                and not _is_value_flow(tc, f)
                and (f.access & FlowAccess.READ)]

    def _task_in_specs(i: int, tc: PTGTaskClass, p) -> Tuple:
        if not has_reshapes:
            return ()
        specs = []
        for f in _in_flows(tc):
            spec = edge_specs.get((i, f.name))
            if spec is None:
                dep = tc._active_in(tp.g, tc.specs[f.name], p)
                if dep is not None and dep.src is None and \
                        dep.reshape is not None:
                    spec = dep.reshape
            specs.append(spec)
        return tuple(specs)

    task_specs: List[Tuple] = [
        _task_in_specs(i, tc, p) for i, (tc, p) in enumerate(tasks)]

    # ---- group into waves (split by reshape signature: one group =
    # one batched body call, so every instance must share its specs)
    n_waves = int(level.max()) + 1 if n else 0
    waves: List[List[WaveGroup]] = [[] for _ in range(n_waves)]
    groups: Dict[Tuple, WaveGroup] = {}
    for i, (tc, p) in enumerate(tasks):
        sig = tuple(s.key if s is not None else None
                    for s in task_specs[i])
        gkey = (int(level[i]), tc.name, sig)
        grp = groups.get(gkey)
        if grp is None:
            grp = WaveGroup(tc=tc, level=int(level[i]), tasks=[],
                            in_specs=list(task_specs[i]) or
                            [None] * len(_in_flows(tc)))
            groups[gkey] = grp
            waves[int(level[i])].append(grp)
        grp.tasks.append(p)

    # ---- collect collections + slot maps; hazard check
    collections: Dict[str, Any] = {}
    slot_maps: Dict[str, Dict[Tuple, int]] = {}

    def _register(dc) -> str:
        if dc.name not in collections:
            collections[dc.name] = dc
            slot_maps[dc.name] = dc.tile_index()
        elif collections[dc.name] is not dc:
            raise ValueError(f"two collections share the name {dc.name!r}")
        return dc.name

    has_value_flows = any(
        _is_value_flow(tc, f)
        for tc in tp.task_classes for f in tc.flows)
    for w, wave in enumerate(waves):
        for grp in wave:
            tc = grp.tc
            in_fl = [f for f in tc.flows if not f.is_ctl
                     and not _is_value_flow(tc, f)
                     and (f.access & FlowAccess.READ)]
            out_fl = [f for f in tc.flows if not f.is_ctl
                      and not _is_value_flow(tc, f)
                      and (f.access & FlowAccess.WRITE)]
            ins: Dict[str, List[int]] = {f.name: [] for f in in_fl}
            outs: Dict[str, List[int]] = {f.name: [] for f in out_fl}
            in_names: Dict[str, str] = {}
            out_names: Dict[str, str] = {}
            for p in grp.tasks:
                for f in in_fl:
                    dc, key = _flow_tile(tc, f.name, p)
                    name = _register(dc)
                    in_names[f.name] = name
                    ins[f.name].append(slot_maps[name][key])
                for f in out_fl:
                    dc, key = _flow_tile(tc, f.name, p)
                    name = _register(dc)
                    out_names[f.name] = name
                    outs[f.name].append(slot_maps[name][key])
            grp.in_slots = [(in_names[f.name],
                             np.asarray(ins[f.name], dtype=np.int32))
                            for f in in_fl]
            grp.out_slots = [(out_names[f.name],
                              np.asarray(outs[f.name], dtype=np.int32))
                             for f in out_fl]

    # ---- hazard checks for store-based execution
    # (a) a tile must not be written twice in one wave (lost update);
    # (b) for every dataflow edge P --tile T--> R, no OTHER task may write
    #     T in a wave w with level(P) < w < level(R): the store would hand
    #     R a newer version than the dataflow prescribes. Same-wave writes
    #     (w == level(R)) are safe — the wave gathers before it scatters.
    write_waves: Dict[Tuple[str, Tuple], List[int]] = {}
    for w, wave in enumerate(waves):
        for grp in wave:
            for p in grp.tasks:
                for f in grp.tc.flows:
                    if f.is_ctl or not (f.access & FlowAccess.WRITE) \
                            or _is_value_flow(grp.tc, f):
                        continue
                    dc, key = _flow_tile(grp.tc, f.name, p)
                    tk = (dc.name, key)
                    lst = write_waves.setdefault(tk, [])
                    if w in lst:
                        raise RuntimeError(
                            f"tile {tk} written twice in wave {w}: DAG "
                            f"under-constrained for store-based execution")
                    lst.append(w)
    for (i, j, fname) in edges:
        tc_j, p_j = tasks[j]
        f_j = tc_j.flow_by_name[fname]
        if f_j.is_ctl or _is_value_flow(tc_j, f_j):
            continue
        dc, key = _flow_tile(tc_j, fname, p_j)
        lw, lr = int(level[i]), int(level[j])
        for w in write_waves.get((dc.name, key), ()):
            if lw < w < lr:
                tc_i, p_i = tasks[i]
                raise RuntimeError(
                    f"WAR/versioning hazard on tile {(dc.name, key)}: "
                    f"{tc_i.name}{p_i}@wave{lw} feeds {tc_j.name}{p_j}@"
                    f"wave{lr} but the tile is rewritten in wave {w}; "
                    f"use the host runtime for this DAG")

    # ---- terminal DataRef reshape specs (Out-side [type=...]): applied
    # once by write_back, matching the host runtime's per-write
    # conversion for the FINAL value. A reshaped write that a LATER
    # data-sourced read would observe has no store representation (the
    # store keeps raw values) — refuse loudly.
    terminal_specs: Dict[Tuple[str, int], Any] = {}
    if has_reshapes:
        term_wave: Dict[Tuple[str, int], int] = {}
        reshaped_wmin: Dict[Tuple[str, int], int] = {}
        data_read_wave: Dict[Tuple[str, int], int] = {}
        g = tp.g
        for i, (tc, p) in enumerate(tasks):
            w = int(level[i])
            for spec_ in tc.spec_list:
                for dep in spec_.outs:
                    if dep.data is None or not dep.active(g, p):
                        continue
                    dc, key = dep.data(g, *p)
                    slot_key = (dc.name, slot_maps[dc.name][tuple(key)])
                    if dep.reshape is not None:
                        reshaped_wmin[slot_key] = min(
                            reshaped_wmin.get(slot_key, 1 << 30), w)
                        if term_wave.get(slot_key, -1) <= w:
                            terminal_specs[slot_key] = dep.reshape
                            term_wave[slot_key] = w
                    elif term_wave.get(slot_key, -1) <= w:
                        terminal_specs.pop(slot_key, None)
                        term_wave[slot_key] = w
                dep = tc._active_in(g, spec_, p)
                if dep is not None and dep.data is not None and \
                        spec_.tile is not None:
                    dc, key = dep.data(g, *p)
                    slot_key = (dc.name, slot_maps[dc.name][tuple(key)])
                    data_read_wave[slot_key] = max(
                        data_read_wave.get(slot_key, -1), w)
        for slot_key, w_r in reshaped_wmin.items():
            if data_read_wave.get(slot_key, -1) > w_r:
                raise NotImplementedError(
                    f"tile {slot_key} is written with an Out-side "
                    f"reshape and read back from the collection in a "
                    f"later wave; store-based execution keeps raw "
                    f"values — run this taskpool on the host runtime")

    plan = WavefrontPlan(taskpool=tp, waves=waves, collections=collections,
                         slot_maps=slot_maps, n_tasks=n,
                         has_value_flows=has_value_flows,
                         has_reshapes=has_reshapes,
                         terminal_specs=terminal_specs)
    debug_verbose(3, "wavefront", "planned %s: %d tasks, %d waves",
                  tp.name, n, len(waves))
    return plan


class WavefrontExecutor:
    """Executes a :class:`WavefrontPlan` on the TPU.

    Two executable forms, both pure and jittable end-to-end:
    - :meth:`run_tile_dict` — every tile its own array; preferred
      single-chip form (no per-wave full-store copies; used by bench).
    - :meth:`run_arrays` — stacked ``{name: store}`` form; the input to
      the SPMD mesh path (sharded along the slot axis; used by
      __graft_entry__ and compiled.spmd).
    - :meth:`run` — host-driven wrapper: collections → stacked stores →
      ``run_arrays`` → write back.

    Batch padding: every group's gather/scatter indices are padded to the
    next power of two; scatter padding lands in a dummy slot appended to
    each store, so bucketized compilation reuses a handful of shapes per
    class instead of one per wave.
    """

    def __init__(self, plan: WavefrontPlan, bucket: bool = True,
                 device_type: DeviceType = DeviceType.TPU):
        import jax
        import jax.numpy as jnp
        if getattr(plan.taskpool, "requires_fuser", False):
            raise ValueError(
                f"taskpool {plan.taskpool.name!r} has bodies that read "
                "the collection directly (CTL-gather pattern); per-tile "
                "compiled execution cannot feed them — use the "
                "PanelExecutor (compiled.panels) or the host runtime")
        if plan.has_value_flows:
            raise ValueError(
                f"taskpool {plan.taskpool.name!r} carries task->task "
                "values with no tile placement; per-tile compiled "
                "execution cannot route them — use the PanelExecutor "
                "(wave fusers keep values in carry state) or the host "
                "runtime")
        self.jax, self.jnp = jax, jnp
        self.plan = plan
        self.bucket = bucket
        self.device_type = device_type
        self._vmapped: Dict[str, Callable] = {}
        self._segments: Dict[Tuple, Callable] = {}
        # body fingerprints (per class, memoized): the segment/whole-DAG
        # caches are shared through the module-level keyed store in
        # compile_cache — jit caches by FUNCTION OBJECT, so the old
        # per-instance jax.jit wrappers re-traced the same programs on
        # every executor rebuilt from an equal plan. Classes whose
        # bodies have no stable fingerprint fall back to per-instance
        # caching (never to silent cross-instance sharing).
        self._body_fps: Dict[str, Optional[str]] = {}
        self._plan_fp: Optional[str] = None
        self._jitted = None

    def _body_fp(self, tc: PTGTaskClass) -> Optional[str]:
        fp = self._body_fps.get(tc.name, "")
        if fp == "":
            ok, digest = class_body_fingerprint(tc, self.device_type)
            fp = digest if ok else None
            self._body_fps[tc.name] = fp
        return fp

    @property
    def jitted(self) -> Callable:
        """The whole-DAG jitted ``run_arrays`` — shared across
        executors built from structurally-equal plans (and persisted
        when the executor store is enabled), keyed by the plan
        fingerprint + every class's body fingerprint + store shapes."""
        if self._jitted is not None:
            return self._jitted
        if self._plan_fp is None:
            ok, digest = plan_structure_fingerprint(self.plan)
            self._plan_fp = digest if ok else None
        fps = [self._body_fp(grp.tc) for wave in self.plan.waves
               for grp in wave]
        if self._plan_fp is None or any(f is None for f in fps):
            self._jitted = self.jax.jit(self.run_arrays)
            return self._jitted
        import jax
        shapes = tuple(sorted(
            (name, len(self.plan.slot_maps[name]) + 1, dc.mb, dc.nb,
             str(np.dtype(dc.dtype)))
            for name, dc in self.plan.collections.items()))
        sds = {name: jax.ShapeDtypeStruct(
            (len(self.plan.slot_maps[name]) + 1, dc.mb, dc.nb),
            np.dtype(dc.dtype))
            for name, dc in self.plan.collections.items()}
        key = ("wf_monolith", self._plan_fp, tuple(sorted(set(fps))),
               shapes, self.bucket, self.device_type.name)
        self._jitted = compile_cache.cached_jit(
            self.run_arrays, key=key, example_args=(sds,))
        return self._jitted

    # -- body lookup ------------------------------------------------------
    def _raw_body(self, tc: PTGTaskClass) -> Callable:
        """The host body adapted to the executor's calling convention:
        the executor gathers only READ flows, while host bodies take
        every non-CTL flow in declaration order (WRITE-only flows are
        placeholder arguments) — rebuild the full argument list with
        None in the WRITE-only slots."""
        chore = tc.chore_for(self.device_type) or \
            tc.chore_for(DeviceType.CPU)
        if chore is None:
            raise ValueError(f"no body for {tc.name}")
        body = chore.hook
        nonctl = [f for f in tc.flows if not f.is_ctl]
        if all(f.access & FlowAccess.READ for f in nonctl):
            return body
        reads = [bool(f.access & FlowAccess.READ) for f in nonctl]

        def adapted(task, *read_vals, _b=body, _reads=tuple(reads)):
            it = iter(read_vals)
            args = [next(it) if r else None for r in _reads]
            return _b(task, *args)

        return adapted

    def _chore(self, tc: PTGTaskClass):
        return tc.chore_for(self.device_type) or tc.chore_for(DeviceType.CPU)

    def _hook_applies(self, chore, grp: WaveGroup) -> bool:
        """A batch_hook may assume flows named in ``batch_hook_shared``
        hold ONE tile across the whole group (e.g. the shared triangular
        factor of a TRSM wave). Verify that from the planner's slot
        indices — host-side, once per group — and fall back to vmap when
        the grouping breaks the assumption (future leveling changes must
        not silently mis-apply the hook)."""
        if chore is None or chore.batch_hook is None:
            return False
        shared = getattr(chore, "batch_hook_shared", None) or ()
        if not shared:
            return True
        in_fl = [f for f in grp.tc.flows
                 if not f.is_ctl and (f.access & FlowAccess.READ)]
        by_name = {f.name: slots for f, (_n, slots) in
                   zip(in_fl, grp.in_slots)}
        return all(len(np.unique(by_name[name])) == 1
                   for name in shared if name in by_name)

    def _body(self, tc: PTGTaskClass, batch: int,
              grp: Optional[WaveGroup] = None) -> Callable:
        """Batched body. Preference order: the chore's hand-written
        ``batch_hook`` (class-specific batched reformulation, guarded by
        its shared-flow assumption), then the batch == 1 unvmapped fast
        path (batched cholesky/triangular-solve lower poorly on TPU and
        diagonal-panel groups are singletons on the critical path), then
        mechanical vmap."""
        chore = self._chore(tc)
        if grp is not None and self._hook_applies(chore, grp):
            # raw hook: _exec_group normalizes every body's outputs
            return chore.batch_hook
        if batch == 1:
            fn = self._vmapped.get((tc.name, 1))
            if fn is None:
                body = self._raw_body(tc)

                def one(*tiles, _b=body, _tc=tc):
                    outs = self._normalize_outs(
                        _tc, _b(None, *(t[0] for t in tiles)))
                    return tuple(o[None] for o in outs)

                fn = one
                self._vmapped[(tc.name, 1)] = fn
            return fn
        fn = self._vmapped.get(tc.name)
        if fn is None:
            body = self._raw_body(tc)
            fn = self.jax.vmap(lambda *tiles, _b=body: _b(None, *tiles))
            self._vmapped[tc.name] = fn
        return fn

    @staticmethod
    def _pad(idx: np.ndarray, size: int, fill: int) -> np.ndarray:
        if len(idx) == size:
            return idx
        out = np.full(size, fill, dtype=np.int32)
        out[:len(idx)] = idx
        return out

    @staticmethod
    def _normalize_outs(tc: PTGTaskClass, outs) -> tuple:
        """Body returns → tuple ordered by WRITE-flow declaration order.
        Bodies may return a dict keyed by flow name (the host runtime
        convention), a tuple/list, or a single value."""
        out_fl = [f for f in tc.flows
                  if not f.is_ctl and (f.access & FlowAccess.WRITE)]
        if isinstance(outs, dict):
            missing = [f.name for f in out_fl if f.name not in outs]
            if missing:
                raise ValueError(
                    f"{tc.name}: body dict missing outputs {missing}")
            return tuple(outs[f.name] for f in out_fl)
        if not isinstance(outs, (tuple, list)):
            outs = (outs,)
        if len(outs) != len(out_fl):
            raise ValueError(
                f"{tc.name}: body returned {len(outs)} outputs "
                f"for {len(out_fl)} write flows")
        return tuple(outs)

    def _exec_group(self, grp: WaveGroup, batch: int,
                    inputs: List[Any]) -> List[Any]:
        """Run one wave-group's batched body over gathered inputs and
        return its validated per-write-flow stacked outputs (the shared
        core of both executor forms)."""
        outs = self._body(grp.tc, batch, grp)(*inputs)
        return list(self._normalize_outs(grp.tc, outs))

    # -- pure store-passing execution ------------------------------------
    @staticmethod
    def _apply_in_specs(grp: WaveGroup, inputs: List[Any]) -> List[Any]:
        """Apply the group's composed dep [type=...] specs to the
        gathered stacks (cast/transpose act on the last two axes, so
        batched application is exact; ReshapeSpec.fn must be batch-safe
        for compiled execution)."""
        if not any(s is not None for s in grp.in_specs):
            return inputs
        return [s.apply(x) if s is not None else x
                for s, x in zip(grp.in_specs, inputs)]

    def run_arrays(self, stores: Dict[str, Any]) -> Dict[str, Any]:
        """stores: name → (ntiles+1, mb, nb) array (last slot = dummy)."""
        jnp = self.jnp
        stores = dict(stores)
        for wave in self.plan.waves:
            # gather-before-scatter inside the wave: snapshot reads
            snapshot = stores
            updates: List[Tuple[str, Any, Any]] = []
            for grp in wave:
                B = len(grp.tasks)
                Bp = 1 << (B - 1).bit_length() if self.bucket else B
                inputs = []
                for (name, idx) in grp.in_slots:
                    gidx = self._pad(idx, Bp, 0)
                    inputs.append(snapshot[name][gidx])
                inputs = self._apply_in_specs(grp, inputs)
                outs = self._exec_group(grp, Bp, inputs)
                for (name, idx), val in zip(grp.out_slots, outs):
                    dummy = stores[name].shape[0] - 1
                    sidx = self._pad(idx, Bp, dummy)
                    updates.append((name, sidx, val))
            for name, sidx, val in updates:
                stores[name] = stores[name].at[sidx].set(
                    val.astype(stores[name].dtype))
        return stores

    # -- tile-dict execution ---------------------------------------------
    # The stacked-store form pays a full-store copy per wave for the
    # functional scatter (dominant on bandwidth-limited chips). In the
    # tile-dict form every tile is its own array: a wave stacks only the
    # tiles of its batch, and "scatter" is dict rebinding — zero copies
    # of untouched tiles. Preferred single-chip form; the stacked form
    # remains the input to the SPMD mesh path (sharded along slots).

    def make_tiles(self, host: bool = False
                   ) -> Dict[Tuple[str, int], Any]:
        """Tile dict from the collections. ``host=True`` keeps tiles as
        host numpy (for budgeted segmented execution: the HBM manager
        stages them in lazily instead of everything landing in device
        memory up front)."""
        import numpy as _np
        jnp = self.jnp
        tiles: Dict[Tuple[str, int], Any] = {}
        for name, dc in self.plan.collections.items():
            scratch = dc.scratch
            for key, slot in self.plan.slot_maps[name].items():
                if scratch:   # factor scratch: zeros, no host read
                    z = (_np.zeros if host else jnp.zeros)(
                        (dc.mb, dc.nb), dc.dtype)
                    tiles[(name, slot)] = z
                elif host:
                    tiles[(name, slot)] = _np.asarray(dc.data_of(key))
                else:
                    tiles[(name, slot)] = jnp.asarray(dc.data_of(key))
        return tiles

    def run_tile_dict(self, tiles: Dict[Tuple[str, int], Any]
                      ) -> Dict[Tuple[str, int], Any]:
        """Pure function tile-dict → tile-dict; jit for the fused form."""
        tiles = dict(tiles)
        for wave in self.plan.waves:
            snapshot = tiles           # values are immutable jax arrays
            updates: List[Tuple[Tuple[str, int], Any]] = []
            for grp in wave:
                B = len(grp.tasks)
                inputs = [self.jnp.stack([snapshot[(name, int(s))]
                                          for s in idx])
                          for (name, idx) in grp.in_slots]
                inputs = self._apply_in_specs(grp, inputs)
                outs = self._exec_group(grp, B, inputs)
                for (name, idx), val in zip(grp.out_slots, outs):
                    for b, s in enumerate(idx):
                        updates.append(((name, int(s)), val[b]))
            for k, v in updates:
                tiles[k] = v
        return tiles

    # -- segmented tile-dict execution -----------------------------------
    # Whole-DAG jit compiles every wave-group's ops into one XLA program:
    # compile time grows with task count (42 s at 120 tasks, minutes at
    # thousands). The segmented form dispatches one cached jitted segment
    # per (class, bucket) shape: compile cost scales with the number of
    # DISTINCT shapes (a handful per class — power-of-two bucketed), not
    # with tasks or waves, and segments are reused across waves, runs and
    # problem sizes with the same tile shape. JAX async dispatch keeps
    # the per-call overhead pipelined. Trade-off: the program can't be
    # fused across waves, so prefer run_tile_dict/jit for small DAGs and
    # the panel path for dense one-matrix DAGs.

    def _segment(self, grp: WaveGroup, batch: int) -> Callable:
        chore = self._chore(grp.tc)
        hooked = self._hook_applies(chore, grp)
        shapes = tuple(
            (self.plan.collections[name].mb,
             self.plan.collections[name].nb,
             np.dtype(self.plan.collections[name].dtype).str)
            for (name, _idx) in grp.in_slots) if grp.in_slots else ()
        sig = tuple(s.key if s is not None else None
                    for s in grp.in_specs)
        key = (grp.tc.name, batch, hooked, shapes, sig)
        fn = self._segments.get(key)
        if fn is None:
            body = self._body(grp.tc, batch,
                              grp if hooked else None)
            specs = tuple(grp.in_specs)

            def seg(*ins, _b=body, _tc=grp.tc, _specs=specs):
                if any(s is not None for s in _specs):
                    ins = [s.apply(x) if s is not None else x
                           for s, x in zip(_specs, ins)]
                return tuple(self._normalize_outs(_tc, _b(*ins)))

            # shared across executors (and processes, via the store)
            # when the class's bodies fingerprint stably: rebuilding an
            # executor for the same (class, bucket) never re-traces.
            # Spec fns enter through sig keys only, so require stable
            # fingerprints for them too; else stay per-instance.
            body_fp = self._body_fp(grp.tc)
            spec_ok = all(
                s is None or
                compile_cache.function_fingerprint(s.apply)[0]
                for s in specs)
            if body_fp is not None and spec_ok:
                import jax
                sds = tuple(jax.ShapeDtypeStruct((batch, mb, nb), dt)
                            for (mb, nb, dt) in shapes)
                fn = compile_cache.cached_jit(
                    seg, key=("wf_segment", body_fp, key),
                    example_args=sds if sds else None)
            else:
                fn = self.jax.jit(seg)
            self._segments[key] = fn
        return fn

    def _split_group(self, grp: WaveGroup,
                     manager: Optional[Any]) -> List[WaveGroup]:
        """Split a wave-group so one sub-batch's tile working set
        (inputs + outputs) fits in ~half the manager's budget."""
        if manager is None:
            return [grp]
        tile_bytes = max(
            dc.mb * dc.nb * np.dtype(dc.dtype).itemsize
            for dc in self.plan.collections.values())
        max_tiles = max(1, (manager.zone.capacity // 2) // tile_bytes)
        per_task = max(1, len(grp.in_slots) + len(grp.out_slots))
        chunk = max(1, max_tiles // per_task)
        if len(grp.tasks) <= chunk:
            return [grp]
        subs = []
        for lo in range(0, len(grp.tasks), chunk):
            hi = lo + chunk
            subs.append(WaveGroup(
                tc=grp.tc, level=grp.level, tasks=grp.tasks[lo:hi],
                in_slots=[(n, idx[lo:hi]) for (n, idx) in grp.in_slots],
                out_slots=[(n, idx[lo:hi])
                           for (n, idx) in grp.out_slots],
                in_specs=list(grp.in_specs)))
        return subs

    def _use_schedule(self) -> Dict[Tuple[str, int], List[int]]:
        """Wave indices at which each tile is read — the static schedule
        that makes Belady eviction possible for the HBM manager."""
        uses: Dict[Tuple[str, int], List[int]] = {}
        for w, wave in enumerate(self.plan.waves):
            for grp in wave:
                for (name, idx) in grp.in_slots:
                    for s in idx:
                        uses.setdefault((name, int(s)), []).append(w)
        return uses

    _NEVER = 1 << 30      # "never read again" — the ideal evictee

    def run_tile_dict_segmented(self, tiles: Dict[Tuple[str, int], Any],
                                manager: Optional[Any] = None
                                ) -> Dict[Tuple[str, int], Any]:
        """Tile-dict execution dispatched wave-by-wave through cached
        per-(class, bucket) jitted segments (bounded compile time).

        With an :class:`~..device.hbm.HBMManager`, tile residency is
        bounded by its budget: inputs are staged in (evicting the tile
        with the farthest next use — the plan gives Belady's policy for
        free), outputs registered, and the next wave's inputs are
        prefetched while the current wave's dispatches are in flight.
        Problems larger than the budget complete by spilling to host.
        """
        from ..utils import mca_param
        jnp = self.jnp
        tiles = dict(tiles)
        if manager is not None:
            uses = self._use_schedule()
            # spills rebind the tiles dict to the host copy, so the
            # executor drops its device reference and XLA can actually
            # free the buffer (logical AND physical residency agree)
            _spill = tiles.__setitem__
            for key, val in tiles.items():
                # register lazily (host-side): tiles stage in at first use
                manager.register(key, val, spill=_spill,
                                 next_use=uses.get(key, [self._NEVER])[0])

        def _next_use(key, w):
            for u in uses.get(key, ()):
                if u > w:
                    return u
            return self._NEVER

        prefetch = manager is not None and bool(
            mca_param.get("device.hbm_prefetch", 1))
        for w, wave in enumerate(self.plan.waves):
            snapshot = dict(tiles)     # gather-before-scatter snapshot
            updates: List[Tuple[Tuple[str, int], Any]] = []
            for grp in wave:
                # under a budget, split oversized groups so one
                # sub-batch's working set fits (the reference stages
                # per task; a k=0 trailing-update group can otherwise
                # reference nearly the whole matrix at once)
                for sub in self._split_group(grp, manager):
                    gkeys = [(name, int(s))
                             for (name, idx) in sub.in_slots
                             for s in idx]
                    if manager is not None:
                        protect = tuple(gkeys)
                        for key in gkeys:
                            snapshot[key] = manager.ensure(
                                key, snapshot.get(key), protect=protect,
                                next_use=_next_use(key, w))
                    B = len(sub.tasks)
                    Bp = 1 << (B - 1).bit_length() if self.bucket else B
                    inputs = []
                    for (name, idx) in sub.in_slots:
                        pidx = self._pad(idx, Bp, int(idx[0]))
                        inputs.append(jnp.stack(
                            [snapshot[(name, int(s))] for s in pidx]))
                    outs = self._segment(sub, Bp)(*inputs)
                    for (name, idx), val in zip(sub.out_slots, outs):
                        for b, s in enumerate(idx):  # padding dropped
                            updates.append(((name, int(s)), val[b]))
            for k, v in updates:
                tiles[k] = v
                if manager is not None:
                    manager.put(k, v, spill=_spill,
                                next_use=_next_use(k, w))
            if prefetch and w + 1 < len(self.plan.waves):
                # stage the next wave's inputs while this wave's async
                # dispatches drain (device_cuda stage-in stream analog).
                # Opportunistic only: best_effort staging fills FREE
                # space and never evicts — pinning or thrashing the
                # resident set would defeat budgets sized for one
                # sub-group
                for grp in self.plan.waves[w + 1]:
                    for (name, idx) in grp.in_slots:
                        for s in idx:
                            key = (name, int(s))
                            tiles[key] = manager.ensure(
                                key, tiles.get(key), best_effort=True,
                                next_use=_next_use(key, w))
        return tiles

    def write_back_tiles(self, tiles: Dict[Tuple[str, int], Any]) -> None:
        tspecs = self.plan.terminal_specs
        for name, dc in self.plan.collections.items():
            if dc.scratch:
                continue      # nobody reads factor scratch after the run
            for key, slot in self.plan.slot_maps[name].items():
                v = tiles[(name, slot)]
                spec = tspecs.get((name, slot))
                dc.write_tile(key, spec.apply(v) if spec is not None else v)

    # -- host-driven run --------------------------------------------------
    def make_stores(self) -> Dict[str, Any]:
        jnp = self.jnp
        stores = {}
        for name, dc in self.plan.collections.items():
            if dc.scratch:
                n = len(self.plan.slot_maps[name])
                stores[name] = jnp.zeros((n + 1, dc.mb, dc.nb), dc.dtype)
                continue
            arr, _ = dc.to_stacked()
            dummy = jnp.zeros((1,) + arr.shape[1:], dtype=arr.dtype)
            stores[name] = jnp.concatenate([arr, dummy], axis=0)
        return stores

    def write_back(self, stores: Dict[str, Any]) -> None:
        tspecs = self.plan.terminal_specs
        for name, dc in self.plan.collections.items():
            if dc.scratch:
                continue
            if any(k[0] == name for k in tspecs):
                # per-tile path: some slots carry terminal [type=...]
                # conversions the stacked write can't express
                for key, slot in self.plan.slot_maps[name].items():
                    v = stores[name][slot]
                    spec = tspecs.get((name, slot))
                    dc.write_tile(key, spec.apply(v)
                                  if spec is not None else v)
                continue
            dc.from_stacked(stores[name][:-1], self.plan.slot_maps[name])

    def run(self, jit: bool = True) -> float:
        t0 = time.perf_counter()
        stores = self.make_stores()
        fn = self.jitted if jit else self.run_arrays
        out = fn(stores)
        for v in out.values():
            v.block_until_ready()
        dt = time.perf_counter() - t0
        self.write_back(out)
        return dt
