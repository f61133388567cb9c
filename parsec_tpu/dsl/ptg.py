"""PTG: parameterized task graphs (the JDF-language equivalent).

Reference: the JDF language + parsec_ptgpp source-to-source compiler
(parsec/interfaces/ptg/ptg-compiler/: parsec.l, parsec.y, jdf2c.c 8,636
LoC). A JDF task class declares parameters with ranges, a partitioning
predicate (``: A(k, k)``), per-flow guarded dependencies
(``RW T <- (k == 0) ? A(k, k) : T SYRK(k-1, k)``; ``-> T TRSM(k+1..NT, k)``)
and per-device bodies. The generated C gives PTG its key property:
**O(1) distributed dependency discovery** — each rank evaluates, from
closed-form expressions, which tasks exist, who their successors are, and
which are remote, with no global graph materialization.

Here the same structure is expressed directly in Python: guards, parameter
ranges and dependency targets are closures over the taskpool globals, so
discovery stays closed-form (no graph is ever materialized). Both sides of
each edge are declared (``ins`` on the consumer, ``outs`` on the producer)
exactly as in JDF; :func:`check_taskpool` cross-validates the two views the
way the reference's iterators_checker PINS module does at runtime.

Dependency counting uses the mask strategy with one bit per consumer flow
(a JDF flow has exactly one active input dependency per task instance, so
flow-granular bits are sufficient and duplicate activations are caught —
reference mask mode, parsec.c:1601). Exception: classes with a gathered
flow (``In(gather=True)``) use counter mode — N producers feed one flow,
so the per-flow bit cannot count them and duplicate detection is traded
away exactly as in the reference's counter mode (parsec.c:1554).

**Ranged data flows.** A flow's value may be an ordered LIST of tiles: a
panel task of an LU with partial pivoting reads and rewrites a whole
block column. One rule, four forms: ``In(src=..., gather=True)`` on a
data flow (the list of its producers' values, in the order ``params_fn``
names them, whatever order they complete in), ``In(data=...,
gather=True)`` (a list of collection tiles), ``Out(dst=...,
scatter=True)`` (element i to the consumers ``params_fn``'s entry i
names) and ``Out(data=..., scatter=True)`` (element i written back to
tile i). An element is one activation; the consumer is scheduled once,
when its count is met. The tiles of a ranged flow are operands of the
task's launch like any other flow's (``device/tpu.py`` flattens the
list), which is why they travel as values and are not reached through
the collection behind the scheduler's back, as upstream's
``zgetrf_1d.jdf`` bodies do under CTL dependencies: donation
(``Chore.donates``), what a launch holds, read staging and the race
sanitizer see every one of them.

Example (tiled Cholesky's POTRF class)::

    tp = ptg.Taskpool("potrf", NT=4, A=A)
    POTRF = tp.task_class(
        "POTRF", params=("k",),
        space=lambda g: ((k,) for k in range(g.NT)),
        affinity=lambda g, k: (g.A, (k, k)),
        flows=[
          ptg.FlowSpec("T", ptg.RW,
            ins=[ptg.In(data=lambda g, k: (g.A, (k, k)),
                        guard=lambda g, k: k == 0),
                 ptg.In(src=("SYRK", lambda g, k: (k - 1, k), "T"),
                        guard=lambda g, k: k > 0)],
            outs=[ptg.Out(dst=("TRSM",
                               lambda g, k: [(m, k) for m in range(k + 1, g.NT)],
                               "A"),),
                  ptg.Out(data=lambda g, k: (g.A, (k, k)))]),
        ])
    @POTRF.body
    def potrf_body(task, T):
        return cholesky_tile(T)
"""

from __future__ import annotations

import itertools
import types
import weakref
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

from ..core.spans import SPAN_PTG_STARTUP, SPAN_PTG_UNFOLD
from ..core.future import DataCopyFuture
from ..core.reshape import compose_specs
from ..core.task import Chore, DeviceType, Flow, FlowAccess, Task
from ..core.taskpool import DEPS_COUNTER, DEPS_MASK, DataRef, \
    SuccessorRef, TaskClass
from ..core.taskpool import Taskpool as CoreTaskpool

READ = FlowAccess.READ
WRITE = FlowAccess.WRITE
RW = FlowAccess.RW
CTL = FlowAccess.CTL


@dataclass
class In:
    """Consumer-side dependency of a flow (JDF ``<-``).

    Exactly one of:
    - ``src=(class_name, params_fn, flow_name)``: value produced by another
      task (``<- T SYRK(k-1, k)``)
    - ``data=lambda g, *p: (collection, key)``: read from a collection
      (``<- A(k, k)``)
    - ``new=lambda g, *p: value``: materialize a fresh value (JDF ``NEW``)
    ``guard`` selects whether this dep is active for a task instance; the
    guards of a flow's ins must be disjoint (one active input per flow).
    ``reshape`` (core.reshape.ReshapeSpec) converts the incoming value to
    this consumer's datatype/layout — the JDF ``[type = ...]`` annotation
    (reshape promises, parsec_reshape.c).

    ``gather=True``: ``src``'s params_fn returns a LIST of producer
    coordinates and the flow waits for ALL of them. On a CTL flow that is
    the reference's CTL-gather fan-in (tests/dsl/ptg/controlgather/
    ctlgat.jdf, PARSEC_HAS_CTL_GATHER; a coordinate named twice counts
    once). On a data flow the task's value for the flow is the ordered
    list of the producers' values: element i is what the i-th coordinate
    of the list sent, whatever order they completed in; a producer that
    sends several elements (a scatter) is named once for each, and they
    fill its places in the order it scattered them. With ``data=`` the
    function returns the list of ``(collection, key)`` and the value is
    the list of those tiles, each read as a single tile is. A flow that
    gathers does so in every one of its ins. A class with a gathered
    flow uses counter-mode dependency tracking.
    """
    src: Optional[Tuple[str, Callable, str]] = None
    data: Optional[Callable] = None
    new: Optional[Callable] = None
    guard: Optional[Callable] = None
    reshape: Optional[Any] = None
    gather: bool = False

    def active(self, g, params) -> bool:
        return self.guard is None or bool(self.guard(g, *params))


@dataclass
class Out:
    """Producer-side dependency of a flow (JDF ``->``).

    Exactly one of:
    - ``dst=(class_name, params_fn, flow_name)``: feed another task;
      ``params_fn`` may return one tuple or a list of tuples (ranged deps,
      ``-> T TRSM(k+1..NT-1, k)``)
    - ``data=lambda g, *p: (collection, key)``: terminal write-back
    ``reshape`` converts the produced value before it reaches this dep's
    target (producer-side ``[type = ...]``); it composes with the
    consumer's ``In.reshape``. ``region`` (core.reshape.Region, on a
    ``data`` write-back only: ``[type = UPPER_TILE]``): the value is that
    part of the tile and is merged into the tile the collection holds,
    where it lies; the pool orders the merge after the readers of the
    tile's other part.

    ``scatter=True``: the flow's value is a list (a ranged flow), and
    this dep hands it out element by element. ``dst``'s params_fn returns
    a list as long as the value: entry i names who gets element i — one
    coordinate tuple, a list of them (element i goes to each), or an
    empty list (nobody). ``data`` returns a list as long as the value of
    ``(collection, key)``, element i written back to tile i (None: not
    written). A length that differs from the value's raises at the task.
    Without it a ranged ``dst`` still broadcasts ONE value.
    """
    dst: Optional[Tuple[str, Callable, str]] = None
    data: Optional[Callable] = None
    guard: Optional[Callable] = None
    reshape: Optional[Any] = None
    region: Optional[Any] = None
    scatter: bool = False

    def active(self, g, params) -> bool:
        return self.guard is None or bool(self.guard(g, *params))


@dataclass
class FlowSpec:
    """One flow of a task class.

    ``tile``: optional ``lambda g, *p: (collection, key)`` naming the
    logical tile this flow reads/writes. Not needed by the host runtime
    (values travel with activations) but required by the compiled
    wavefront/SPMD executors, which gather/scatter tiles from stacked
    HBM stores instead of chasing values (JDF's data-placement info).
    """
    name: str
    access: FlowAccess
    ins: List[In] = field(default_factory=list)
    outs: List[Out] = field(default_factory=list)
    tile: Optional[Callable] = None

    @property
    def ranged(self) -> bool:
        """Is the flow's value a list of tiles (a gathered data flow, or
        one that is scattered)? Its ``tile`` then names the list."""
        return not self.access & FlowAccess.CTL and (
            any(d.gather for d in self.ins) or
            any(d.scatter for d in self.outs))


class _AnyList:
    """:func:`check_taskpool`'s stand-in for a ranged flow's value: as
    long as whatever it is scattered over."""

    def __iter__(self):
        return itertools.repeat(0)


_ANY = _AnyList()


def _coords(targets) -> List[Tuple[int, ...]]:
    """One coordinate tuple or a list of them (a generator too), as the
    list of tuples: the Out-dst convention."""
    if isinstance(targets, tuple):
        return [targets]
    return [tuple(x) if isinstance(x, (tuple, list)) else (x,)
            for x in targets]


class PTGTaskClass(TaskClass):
    """Task class built from closed-form flow specs."""

    unfold_span = SPAN_PTG_UNFOLD       # _iterate_successors, as a list

    def __init__(self, tp: "Taskpool", name: str, tc_id: int,
                 params: Sequence[str], specs: List[FlowSpec],
                 space: Callable, affinity: Optional[Callable],
                 priority: Optional[Callable]):
        flows = [Flow(s.name, s.access) for s in specs]
        for s in specs:
            if any(d.region is not None and d.data is None for d in s.outs):
                raise ValueError(
                    f"{name}.{s.name}: a region belongs to a write-back "
                    f"(Out(data=...)): a value between tasks travels whole")
            ctl = bool(s.access & FlowAccess.CTL)
            for d in s.ins:
                if d.gather and d.src is None and (ctl or d.data is None):
                    raise ValueError(
                        f"{name}.{s.name}: gather requires a src "
                        f"producer list" + ("" if ctl else
                                            " or a data tile list"))
            if not ctl and len({d.gather for d in s.ins}) > 1:
                raise ValueError(
                    f"{name}.{s.name}: a data flow that gathers a list "
                    f"gathers in every one of its ins (its value is a "
                    f"list or a tile, not one or the other by guard)")
            if s.ranged and any(d.reshape is not None
                                for d in (*s.ins, *s.outs)):
                raise ValueError(
                    f"{name}.{s.name}: a ranged flow (gather/scatter) "
                    f"takes no reshape: its value is a list of tiles")
            if ctl and any(d.scatter for d in s.outs):
                raise ValueError(
                    f"{name}.{s.name}: a CTL flow carries nothing to "
                    f"scatter (a ranged dst already reaches many tasks)")
        # gather fan-in needs counting, not one-bit-per-flow masking
        mode = DEPS_COUNTER if any(d.gather for s in specs
                                   for d in s.ins) else DEPS_MASK
        super().__init__(name, tc_id, params, flows, deps_mode=mode)
        # the pool holds its classes; a class reaches its pool weakly
        # (``tp``), holds the globals themselves and takes its vtable
        # from the methods below, so that neither a class nor its pool
        # is a cycle: a finished pool that its user drops is freed there
        # and then, with the collections in ``g``, by reference count
        self._tp = weakref.ref(tp)
        self.g = tp.g
        self.specs = {s.name: s for s in specs}
        self.spec_list = specs
        self.space = space
        self.affinity = affinity
        # ranged data flows: the deps that hand a flow's value on whole
        # (``iterate_successors``) and those that hand out a list's
        # elements (``_scattered``); the values gathered so far for the
        # tasks of this class that wait for a list (``_part``)
        self.ranged = any(s.ranged for s in specs)
        self._whole = {s.name: [d for d in s.outs if not d.scatter]
                       for s in specs}
        self._scatters = [(self.flow_by_name[s.name], d) for s in specs
                          for d in s.outs if d.scatter]
        self._parts: Dict[Tuple[str, Tuple[int, ...]], Tuple] = {}
        # the tile a task of this class writes (``written_tile``): its
        # first written flow's (the first of them where the flow is
        # ranged), else the one its affinity names
        self._written = next(
            ((lambda g, *p, _tiles=s.tile: _tiles(g, *p)[0]) if s.ranged
             else s.tile for s in specs if s.tile is not None and
             s.access & FlowAccess.WRITE and
             not s.access & FlowAccess.CTL), affinity)
        if priority is not None:
            self.priority_fn = lambda locals, _g=tp.g: priority(_g, *locals)
        del self.iterate_successors, self.deps_goal
        # deps_goal runs once per ARRIVING activation (activate_dep), so
        # gather classes would re-enumerate their N-element target list
        # N times without this (the reference computes goals once per
        # task instance); the closed form is pure, so cache per locals
        self._goal_cache: Dict[Tuple[int, ...], int] = {}

    @property
    def tp(self) -> "Taskpool":
        """The class's pool (which holds the class, not the reverse)."""
        tp = self._tp()
        if tp is None:
            raise ReferenceError(
                f"task class {self.name}: its taskpool is gone (hold the "
                f"taskpool, not only a class of it)")
        return tp

    # -- body decorators --------------------------------------------------
    def body(self, fn: Callable = None, device: DeviceType = DeviceType.ALL,
             evaluate: Optional[Callable] = None, batchable: bool = True,
             batch_hook: Optional[Callable] = None,
             batch_hook_shared=None, donates=None,
             compiler_options=None):
        """Attach an incarnation (JDF ``BODY [type=...] ... END``).
        ``batch_hook``/``batch_hook_shared``: optional hand-batched form
        for the compiled executor; ``donates``: the RW flows a chip
        module may update where they lie (every tile of a ranged flow's
        list); ``compiler_options``: what its kernels need of the chip's
        compiler (see core.task.Chore)."""
        def deco(f):
            self.add_chore(Chore(device, f, evaluate=evaluate,
                                 batchable=batchable,
                                 batch_hook=batch_hook,
                                 batch_hook_shared=batch_hook_shared,
                                 donates=donates,
                                 compiler_options=compiler_options))
            return f
        return deco(fn) if fn is not None else deco

    def body_cpu(self, fn=None, **kw):
        return self.body(fn, device=DeviceType.CPU, **kw)

    def body_tpu(self, fn=None, **kw):
        return self.body(fn, device=DeviceType.TPU, **kw)

    # -- closed-form vtable ----------------------------------------------
    def _active_in(self, g, spec: FlowSpec, params) -> Optional[In]:
        active = [d for d in spec.ins if d.active(g, params)]
        if len(active) > 1:
            raise RuntimeError(
                f"{self.name}{tuple(params)}: flow {spec.name} has "
                f"{len(active)} active input deps (guards must be disjoint)")
        return active[0] if active else None

    @staticmethod
    def _coord_set(targets) -> set:
        """Normalize a gather target list to a set of coordinate tuples
        (accepts generators; duplicates collapse — each producer sends
        exactly one activation, so a duplicated coordinate must not
        inflate the goal into an unreachable count). A bare tuple means
        ONE coordinate, matching the Out-dst convention."""
        if isinstance(targets, tuple):
            targets = [targets]
        return {tuple(x) if isinstance(x, (tuple, list)) else (x,)
                for x in targets}

    def deps_goal(self, locals) -> int:
        """Mask of flow bits (mask mode) or count (counter mode, used by
        CTL-gather classes) of *task*-fed deps; collection reads and NEW
        are resolved locally at prepare_input, not counted."""
        g = self.g
        if self.deps_mode == DEPS_COUNTER:
            key = tuple(locals)
            cached = self._goal_cache.get(key)
            if cached is not None:
                return cached
            count = 0
            for f in self.flows:
                dep = self._active_in(g, self.specs[f.name], locals)
                if dep is None or dep.src is None:
                    continue
                if not dep.gather:
                    count += 1
                elif f.is_ctl:
                    count += len(self._coord_set(dep.src[1](g, *locals)))
                else:       # one activation an element, named or not
                    count += len(_coords(dep.src[1](g, *locals)))
            self._goal_cache[key] = count
            return count
        mask = 0
        for f in self.flows:
            dep = self._active_in(g, self.specs[f.name], locals)
            if dep is not None and dep.src is not None:
                mask |= 1 << f.index
        return mask

    def data_lookup(self, task: Task) -> None:
        """Resolve collection-sourced and NEW inputs (generated
        data_lookup / jdf_generate_code_data_lookup analog)."""
        g = self.g
        for f in self.flows:
            if f.name in task.data:
                continue
            dep = self._active_in(g, self.specs[f.name], task.locals)
            if dep is None:
                continue
            if dep.data is not None:
                where = dep.data(g, *task.locals)
                if dep.gather:      # a list of tiles, each read as one
                    value = [self._read_tile(task, dc, key)
                             for dc, key in where]
                else:
                    value = self._read_tile(task, *where)
            elif dep.new is not None:
                value = dep.new(g, *task.locals)
            elif dep.gather and not f.is_ctl:
                # the list its producers filled, every element there
                # before its activation was counted
                value = self._part(f.name, task.locals)[0]
                self._parts.pop((f.name, task.locals), None)
            else:
                continue
            if dep.reshape is not None:
                value = dep.reshape.apply(value)
            task.data[f.name] = value

    @staticmethod
    def _read_tile(task: Task, dc, key):
        """One collection tile as ``task`` reads it."""
        value = dc.data_of(key)
        ctx = task.taskpool.context
        if ctx is not None:
            san = ctx.dfsan
            if san is not None:
                # race-checked: a collection read unordered with
                # a terminal writer of the same tile observes a
                # schedule-dependent version (analysis/dfsan.py)
                san.observe_read(task, dc, key)
            # stage-through: the collection keeps the device
            # copy so one H2D serves every reader (Context.
            # stage_read; no-op without an accelerator)
            value = ctx.stage_read(dc, key, value)
        return value

    _RESHAPES, _GATHERS = 1, 2

    def _in_kind(self, flow_name: str) -> int:
        """What a producer has to do for this class's ``flow_name``
        beyond handing its value over: 0 nothing, ``_RESHAPES`` an In of
        it declares a reshape, ``_GATHERS`` it is a data flow that
        gathers a list (cached — keeps the plain hot path free of guard
        evals and at the one test it had)."""
        cache = self.__dict__.setdefault("_in_kind_cache", {})
        hit = cache.get(flow_name)
        if hit is None:
            spec = self.specs[flow_name]
            hit = self._GATHERS if spec.ranged and \
                any(d.gather for d in spec.ins) else \
                self._RESHAPES if any(d.reshape is not None
                                      for d in spec.ins) else 0
            cache[flow_name] = hit
        return hit

    # -- ranged flows: a list gathered, a list scattered -----------------
    def _part(self, flow_name: str, locals: Tuple[int, ...]):
        """``(values, places)`` of the list task ``locals`` of this class
        gathers in ``flow_name``: the elements that have arrived, each at
        its place, and the places of each producer in the consumer's own
        list (``In.src``'s params_fn, in its order)."""
        key = (flow_name, locals)
        part = self._parts.get(key)
        if part is None:
            dep = self._active_in(self.g, self.specs[flow_name], locals)
            if dep is None or not dep.gather or dep.src is None:
                raise RuntimeError(
                    f"{self.name}{locals}: flow {flow_name} gathers no "
                    f"list of producers there")
            places: Dict[Tuple[int, ...], List[int]] = {}
            members = _coords(dep.src[1](self.g, *locals))
            for i, coord in enumerate(members):
                places.setdefault(coord, []).append(i)
            # two producers may both come first: one list stays
            part = self._parts.setdefault(
                key, ([None] * len(members), places))
        return part

    def _put(self, flow_name: str, locals: Tuple[int, ...],
             producer: Task, nth: int, value: Any) -> None:
        """``producer``'s ``nth`` element for task ``locals``'s gathered
        ``flow_name``, put at its place BEFORE its activation is counted
        (whoever completes the count finds every element)."""
        values, places = self._part(flow_name, locals)
        try:
            values[places[producer.locals][nth]] = value
        except (KeyError, IndexError):
            raise RuntimeError(
                f"{self.name}{locals}.{flow_name}: the gathered list does "
                f"not name {producer!r} {nth + 1} time(s)") from None

    def _scattered(self, task: Task):
        """The activations and write-backs of the deps that hand out a
        ranged flow's list element by element (``Out(scatter=True)``)."""
        g, p = self.g, task.locals
        for f, dep in self._scatters:
            if not dep.active(g, p):
                continue
            values = task.output.get(f.name, task.data.get(f.name))
            over = (dep.data or dep.dst[1])(g, *p)
            listed = isinstance(values, (list, tuple))
            if values is not _ANY and not (listed and
                                           len(values) == len(over)):
                raise ValueError(
                    f"{task!r}: flow {f.name} holds "
                    f"{len(values) if listed else 'no list of'} values "
                    f"and is scattered over {len(over)} "
                    f"{'tiles' if dep.data else dep.dst[0] + ' targets'}")
            if dep.data is not None:
                for v, where in zip(values, over):
                    if where is not None:
                        yield DataRef(collection=where[0], key=where[1],
                                      value=v)
                continue
            dst_tc = task.taskpool.task_class_by_name(dep.dst[0])
            dst_flow = dst_tc.flow_by_name[dep.dst[2]]
            gathers = dst_tc._in_kind(dst_flow.name) == self._GATHERS
            sent: Dict[Tuple[int, ...], int] = {}
            for i, (v, targets) in enumerate(zip(values, over)):
                for tgt in _coords(targets):
                    if gathers:
                        nth = sent[tgt] = sent.get(tgt, -1) + 1
                        dst_tc._put(dst_flow.name, tgt, task, nth, v)
                    yield SuccessorRef(
                        task_class=dst_tc, locals=tgt,
                        flow_name=dst_flow.name,
                        value=None if gathers else v,
                        dep_index=dst_flow.index,
                        priority=dst_tc.priority_fn(tgt),
                        src_flow=f.name, element=i)

    def iterate_successors(self, task: Task):
        """Producer-side expansion (generated iterate_successors analog,
        jdf2c.c; consumed by parsec_release_dep_fct parsec.c:1783)."""
        g = self.g
        for f in self.flows:
            spec = self.specs[f.name]
            value = None
            if not f.is_ctl:
                value = task.output.get(f.name, task.data.get(f.name))
            promise = None   # one shared DataCopyFuture per produced flow
            for dep in self._whole[f.name]:
                if not dep.active(g, task.locals):
                    continue
                if dep.data is not None:
                    dc, key = dep.data(g, *task.locals)
                    v = value if dep.reshape is None \
                        else dep.reshape.apply(value)
                    yield DataRef(collection=dc, key=key, value=v,
                                  region=dep.region)
                    continue
                cls_name, params_fn, dst_flow = dep.dst
                dst_tc = task.taskpool.task_class_by_name(cls_name)
                targets = params_fn(g, *task.locals)
                if isinstance(targets, tuple):
                    targets = [targets]
                dst_bit_flow = dst_tc.flow_by_name[dst_flow]
                consumer_kind = dst_tc._in_kind(dst_flow)
                for tgt in targets:
                    tgt = tuple(tgt) if isinstance(tgt, (tuple, list)) else (tgt,)
                    composed = None
                    v = None if dst_bit_flow.is_ctl else value
                    if dep.reshape is not None or consumer_kind:
                        if consumer_kind == self._GATHERS:
                            # one element of the list the consumer gathers
                            dst_tc._put(dst_flow, tgt, task, 0, v)
                            v = None
                        else:
                            dst_in = dst_tc._active_in(
                                g, dst_tc.specs[dst_flow], tgt)
                            composed = compose_specs(
                                dep.reshape, dst_in.reshape
                                if dst_in is not None else None)
                    if composed is not None and v is not None:
                        if promise is None:
                            promise = DataCopyFuture(value)
                        v = promise
                    yield SuccessorRef(
                        task_class=dst_tc, locals=tgt, flow_name=dst_flow,
                        value=v, reshape_spec=composed,
                        dep_index=dst_bit_flow.index,
                        priority=dst_tc.priority_fn(tgt),
                        src_flow=f.name)
        if self._scatters:
            yield from self._scattered(task)

    # -- distribution -----------------------------------------------------
    def written_tile(self, task: Task):
        """``(collection, key)`` of the tile ``task`` writes (the JDF's
        ``: descA(m, k)``): its first written flow's ``tile`` (the first
        of the list where that flow is ranged), else its affinity's;
        None where the class names neither."""
        fn = self._written
        return None if fn is None else fn(self.g, *task.locals)

    def affinity_rank(self, locals) -> int:
        if self.affinity is None:
            return 0
        dc, key = self.affinity(self.g, *locals)
        return dc.rank_of(key)

    def enumerate_space(self) -> Iterable[Tuple[int, ...]]:
        for p in self.space(self.g):
            yield tuple(p) if isinstance(p, (tuple, list)) else (p,)

    def nb_local_tasks(self, my_rank: int = 0, nb_ranks: int = 1) -> int:
        """Closed-form local-task count (generated nb_local_tasks analog)."""
        n = 0
        for p in self.enumerate_space():
            if nb_ranks == 1 or self.affinity_rank(p) == my_rank:
                n += 1
        return n


class Taskpool(CoreTaskpool):
    """PTG taskpool: globals namespace + task classes
    (the ``__parsec_<name>_internal_taskpool_t`` analog)."""

    startup_span = SPAN_PTG_STARTUP     # _startup walks the whole space

    def __init__(self, name: str = "ptg", **globals_kw):
        super().__init__(name=name)
        self.g = types.SimpleNamespace(**globals_kw)
        # the hook is handed its pool: the function, not a method bound
        # to the pool it would be stored on
        self.startup_hook = type(self)._startup

    def task_class_by_name(self, name: str) -> PTGTaskClass:
        return self._tc_by_name[name]

    def task_class(self, name: str, params: Sequence[str],
                   space: Callable, flows: List[FlowSpec],
                   affinity: Optional[Callable] = None,
                   priority: Optional[Callable] = None) -> PTGTaskClass:
        tc = PTGTaskClass(self, name, len(self.task_classes), params,
                          flows, space, affinity, priority)
        self.add_task_class(tc)
        return tc

    # -- startup (jdf_generate_startup_tasks analog) ----------------------
    def _startup(self) -> List[Task]:
        ctx = self.context
        my_rank = ctx.my_rank if ctx is not None else 0
        nb_ranks = ctx.nb_ranks if ctx is not None else 1
        if nb_ranks > 1 and taskpool_has_ranged_flows(self):
            raise NotImplementedError(
                f"taskpool {self.name}: a ranged data flow (gather/"
                f"scatter) keeps the elements that have arrived with the "
                f"consumer's class on one rank; {nb_ranks} ranks are "
                f"not supported")
        total = 0
        ready: List[Task] = []
        for tc in self.task_classes:
            for p in tc.enumerate_space():
                if nb_ranks > 1 and tc.affinity_rank(p) != my_rank:
                    continue
                total += 1
                if tc.deps_goal(p) == 0:
                    t = Task(self, tc, p, priority=tc.priority_fn(p))
                    ready.append(t)
        self.set_nb_tasks(total)
        return ready


def taskpool_uses_reshape(tp: Taskpool) -> bool:
    """True if any dep of any task class declares a reshape spec (or
    a write-back of a region of a tile, which is one). The
    compiled (wavefront/SPMD) and native executors move raw tile values
    and must refuse such taskpools instead of silently skipping the
    conversions (the host runtime resolves them in complete_task)."""
    for tc in tp.task_classes:
        for spec in tc.spec_list:
            if any(d.reshape is not None for d in spec.ins) or \
                    any(d.reshape is not None or d.region is not None
                        for d in spec.outs):
                return True
    return False


def taskpool_writes_regions(tp: Taskpool) -> bool:
    """True if any write-back of the pool is of a region of a tile
    (``Out(region=...)``). The host runtime merges one into the tile its
    collection holds (``Context._release_deps``); the compiled and the
    native executors scatter whole tiles and refuse such a pool."""
    return any(d.region is not None for tc in tp.task_classes
               for spec in tc.spec_list for d in spec.outs)


def taskpool_has_ranged_flows(tp: Taskpool) -> bool:
    """True if any data flow of the pool is a list of tiles
    (``In(gather=True)`` on a data flow, ``Out(scatter=True)``). The
    host runtime counts an element an activation and launches the list
    as operands of one program; the compiled executors gather and
    scatter ONE tile a flow from their stacked stores and refuse such a
    pool, and so does a context of several ranks (an element's value is
    kept with the consumer's class, on this rank)."""
    return any(tc.ranged for tc in tp.task_classes)


def check_taskpool(tp: Taskpool, nb_ranks: int = 1) -> None:
    """Cross-validate producer (outs) and consumer (ins) dep declarations
    by enumerating the whole space — the iterators_checker PINS module
    equivalent (mca/pins/iterators_checker), used by tests.

    Verifies: every SuccessorRef lands on an existing task instance and a
    flow whose active In names the producer back; every task's goal mask is
    covered by exactly the refs aimed at it.
    """
    g = tp.g
    exists: Dict[str, set] = {tc.name: set(tc.enumerate_space())
                              for tc in tp.task_classes}
    incoming: Dict[Tuple[str, Tuple], int] = {}
    # counter-mode consumers additionally track WHICH producer fed them
    # how many times — a duplicate edge compensated by a missing one
    # passes a bare count but breaks the gather barrier at runtime
    incoming_pairs: Dict[Tuple[str, Tuple], Dict[Tuple, int]] = {}
    for tc in tp.task_classes:
        for p in tc.enumerate_space():
            task = Task(tp, tc, p)
            for f in tc.flows:
                task.data[f.name] = 0
                task.output[f.name] = _ANY if tc.specs[f.name].ranged else 0
            for ref in tc.iterate_successors(task):
                if isinstance(ref, DataRef):
                    continue
                if ref.locals not in exists[ref.task_class.name]:
                    raise AssertionError(
                        f"{tc.name}{p} -> {ref.task_class.name}{ref.locals}: "
                        f"target task does not exist")
                spec = ref.task_class.specs[ref.flow_name]
                dep = ref.task_class._active_in(g, spec, ref.locals)
                if dep is None or dep.src is None:
                    raise AssertionError(
                        f"{tc.name}{p} -> {ref.task_class.name}{ref.locals}."
                        f"{ref.flow_name}: consumer declares no task input")
                src_cls, src_params_fn, src_flow = dep.src
                sp = src_params_fn(g, *ref.locals)
                if dep.gather:
                    members = _coords(sp)
                    if src_cls != tc.name or tuple(p) not in members:
                        raise AssertionError(
                            f"{ref.task_class.name}{ref.locals}."
                            f"{ref.flow_name}: gather over {src_cls} does "
                            f"not name {tc.name}{p}")
                else:
                    sp = tuple(sp) if isinstance(sp, (tuple, list)) else (sp,)
                    if src_cls != tc.name or tuple(sp) != tuple(p):
                        raise AssertionError(
                            f"{ref.task_class.name}{ref.locals}."
                            f"{ref.flow_name} expects {src_cls}{sp}, "
                            f"got {tc.name}{p}")
                k = (ref.task_class.name, ref.locals)
                if ref.task_class.deps_mode == DEPS_COUNTER:
                    incoming[k] = incoming.get(k, 0) + 1
                    pk = (tc.name, tuple(p), ref.flow_name)
                    pairs = incoming_pairs.setdefault(k, {})
                    pairs[pk] = pairs.get(pk, 0) + 1
                else:
                    incoming[k] = incoming.get(k, 0) | (1 << ref.dep_index)
    for tc in tp.task_classes:
        for p in tc.enumerate_space():
            goal = tc.deps_goal(p)
            got = incoming.get((tc.name, p), 0)
            if got != goal:
                kind = "count" if tc.deps_mode == DEPS_COUNTER else "mask"
                raise AssertionError(
                    f"{tc.name}{p}: goal {kind} {goal} but incoming deps "
                    f"{got}")
            if tc.deps_mode != DEPS_COUNTER:
                continue
            # every expected producer must feed EXACTLY once
            expected: Dict[Tuple, int] = {}
            for f in tc.flows:
                dep = tc._active_in(g, tc.specs[f.name], p)
                if dep is None or dep.src is None:
                    continue
                src_cls, src_params_fn, _sf = dep.src
                if dep.gather and f.is_ctl:
                    for coord in PTGTaskClass._coord_set(
                            src_params_fn(g, *p)):
                        expected[(src_cls, coord, f.name)] = 1
                elif dep.gather:    # a producer is named once an element
                    for coord in _coords(src_params_fn(g, *p)):
                        key = (src_cls, coord, f.name)
                        expected[key] = expected.get(key, 0) + 1
                else:
                    sp = src_params_fn(g, *p)
                    sp = tuple(sp) if isinstance(sp, (tuple, list)) else (sp,)
                    key = (src_cls, sp, f.name)
                    expected[key] = expected.get(key, 0) + 1
            got_pairs = incoming_pairs.get((tc.name, p), {})
            if got_pairs != expected:
                raise AssertionError(
                    f"{tc.name}{p}: producer multiplicity mismatch — "
                    f"expected {expected}, got {got_pairs}")
    for tc in tp.task_classes:      # the stand-in values it scattered
        tc._parts.clear()
