"""Symbolic dataflow model of a PTG taskpool.

The lint (analysis/lint.py) needs a *materialized* view of what the
closed-form flow declarations actually generate: every task instance of
every class over its bounded parameter space (``enumerate_space()``),
every producer→consumer edge, and every collection-tile access.  The
reference audits the same information at two places — the JDF compiler's
``jdf_sanity_checks`` (jdf.c) statically and the iterators_checker PINS
module at runtime; this model is the shared substrate for both kinds of
check here.

The model never runs task bodies: producer-side expansion walks the
``FlowSpec.outs`` declarations directly (the same closures
``PTGTaskClass.iterate_successors`` evaluates), so building it is pure
and side-effect free.  Spaces are bounded by construction in PTG;
``max_tasks`` caps the enumeration so a registration-time lint on a huge
taskpool degrades to the structural (per-class) checks instead of
scanning millions of instances.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Set, Tuple

from ..core.task import FlowAccess


def _tile_key(dc, key) -> Tuple[str, Tuple]:
    """Stable identity of a collection tile: (collection label, key).
    Shared by the static lint AND the dfsan runtime sanitizer so both
    name tiles identically in findings and digests."""
    label = getattr(dc, "name", None)
    if not label:
        label = f"dc{getattr(dc, 'dc_id', id(dc))}"
    return (label, tuple(key) if isinstance(key, (tuple, list)) else (key,))


def _norm(coords) -> Tuple:
    """Normalize a dep-target coordinate to a tuple (bare scalar = one
    coordinate, matching the Out-dst convention)."""
    return tuple(coords) if isinstance(coords, (tuple, list)) else (coords,)


@dataclass
class TileAccess:
    """One declared collection access of a task instance."""
    node: int                 # index into Model.nodes
    flow: str
    tile: Tuple[str, Tuple]
    access: FlowAccess
    kind: str                 # "read" (In.data) | "write" (Out.data)


@dataclass
class Edge:
    """One producer→consumer dependency edge between task instances."""
    src: int
    dst: int
    src_flow: str
    dst_flow: str


class Node:
    """A task instance (class name + parameter assignment)."""

    __slots__ = ("idx", "tc", "coords")

    def __init__(self, idx: int, tc, coords: Tuple[int, ...]):
        self.idx = idx
        self.tc = tc
        self.coords = coords

    @property
    def label(self) -> str:
        return f"{self.tc.name}({', '.join(map(str, self.coords))})"

    def __repr__(self) -> str:
        return self.label


def _is_lintable_class(tc) -> bool:
    """PTG-style classes expose closed-form specs + a bounded space; DTD
    wire classes and hand-built TaskClass vtables do not."""
    return hasattr(tc, "spec_list") and hasattr(tc, "enumerate_space")


@dataclass
class Model:
    """Materialized instance DAG of a (PTG) taskpool."""

    taskpool: Any
    nodes: List[Node] = field(default_factory=list)
    index: Dict[Tuple[str, Tuple], int] = field(default_factory=dict)
    succ: List[List[int]] = field(default_factory=list)
    edges: List[Edge] = field(default_factory=list)
    # (src_idx, src_flow, dst_idx, dst_flow) actually emitted by outs —
    # the consumer-side (ins) checks cross-validate against this
    produced: Set[Tuple[int, str, int, str]] = field(default_factory=set)
    reads: Dict[Tuple[str, Tuple], List[TileAccess]] = field(default_factory=dict)
    writes: Dict[Tuple[str, Tuple], List[TileAccess]] = field(default_factory=dict)
    # per-node terminal writes / touched tiles / affinity target
    # (owner-computes check)
    node_writes: Dict[int, List[Tuple[str, Tuple]]] = field(default_factory=dict)
    node_touch: Dict[int, set] = field(default_factory=dict)
    node_affinity: Dict[int, Tuple[str, Tuple]] = field(default_factory=dict)
    # tile label -> live collection object (data/recovery.py resolves
    # lost-tile ownership and cut-read sources through this)
    collections: Dict[str, Any] = field(default_factory=dict)
    # build diagnostics consumed by the lint
    problems: List[Tuple[str, str, str, str]] = field(default_factory=list)
    #         (rule, task_label, flow, message)
    skipped_classes: List[str] = field(default_factory=list)
    truncated: bool = False

    # -- ordering -----------------------------------------------------------
    def topo_order(self) -> Tuple[List[int], List[int]]:
        """Kahn's algorithm: (topological order, nodes left on a cycle)."""
        indeg = [0] * len(self.nodes)
        for outs in self.succ:
            for d in outs:
                indeg[d] += 1
        stack = [i for i, d in enumerate(indeg) if d == 0]
        order: List[int] = []
        while stack:
            u = stack.pop()
            order.append(u)
            for d in self.succ[u]:
                indeg[d] -= 1
                if indeg[d] == 0:
                    stack.append(d)
        on_cycle = [i for i, d in enumerate(indeg) if d > 0]
        return order, on_cycle

    def find_cycle(self) -> Optional[List[int]]:
        """One concrete dependency cycle (node indices, first == last),
        or None when the instance DAG is acyclic."""
        _, on_cycle = self.topo_order()
        if not on_cycle:
            return None
        # Kahn leftovers include nodes merely DOWNSTREAM of a cycle;
        # iteratively trim members without an in-set successor until
        # every survivor provably has one (the cycles themselves), so
        # the walk below can never dead-end
        members = set(on_cycle)
        while True:
            drop = [u for u in members
                    if not any(d in members for d in self.succ[u])]
            if not drop:
                break
            members.difference_update(drop)
        start = min(members)
        path = [start]
        seen_at = {start: 0}
        u = start
        while True:
            u = next(d for d in self.succ[u] if d in members)
            if u in seen_at:
                return path[seen_at[u]:] + [u]
            seen_at[u] = len(path)
            path.append(u)

    def reaches(self, src: int, dst: int) -> bool:
        """Is there a dependency path src ⇝ dst? (iterative DFS with a
        per-source descendant memo — hazard queries cluster by source)."""
        if src == dst:
            return True
        memo = self.__dict__.setdefault("_desc_memo", {})
        desc = memo.get(src)
        if desc is None:
            desc = set()
            stack = list(self.succ[src])
            while stack:
                u = stack.pop()
                if u in desc:
                    continue
                desc.add(u)
                # splice in an already-computed memo instead of re-walking
                sub = memo.get(u)
                if sub is not None:
                    desc |= sub
                    continue
                stack.extend(self.succ[u])
            memo[src] = desc
        return dst in desc

    def ordered(self, a: int, b: int) -> bool:
        return self.reaches(a, b) or self.reaches(b, a)


def build_model(tp, max_tasks: int = 0) -> Model:
    """Materialize the instance DAG of ``tp``.

    ``max_tasks`` (0 = the ``analysis.lint_max_tasks`` MCA default)
    bounds the enumeration; past the cap the model is marked
    ``truncated`` and instance-level checks are skipped by the lint.
    """
    from ..utils import mca_param
    if max_tasks <= 0:
        max_tasks = int(mca_param.get("analysis.lint_max_tasks", 20000))

    m = Model(taskpool=tp)
    g = getattr(tp, "g", None)
    classes = [tc for tc in tp.task_classes if _is_lintable_class(tc)]
    m.skipped_classes = [tc.name for tc in tp.task_classes
                         if not _is_lintable_class(tc)]
    if g is None or not classes:
        m.truncated = bool(tp.task_classes)
        return m

    # pass 1: enumerate every instance
    total = 0
    for tc in classes:
        for p in tc.enumerate_space():
            total += 1
            if total > max_tasks:
                m.truncated = True
                return m
            idx = len(m.nodes)
            node = Node(idx, tc, tuple(p))
            m.nodes.append(node)
            m.succ.append([])
            m.index[(tc.name, tuple(p))] = idx

    def _reg_tile(dc, key):
        tk = _tile_key(dc, key)
        m.collections.setdefault(tk[0], dc)
        return tk

    # pass 2: producer-side expansion (outs) — edges + collection writes
    for node in m.nodes:
        tc, p = node.tc, node.coords
        for spec in tc.spec_list:
            for dep in spec.outs:
                if not dep.active(g, p):
                    continue
                if dep.data is not None:
                    dc, key = dep.data(g, *p)
                    tk = _reg_tile(dc, key)
                    acc = TileAccess(node.idx, spec.name, tk, spec.access,
                                     "write")
                    m.writes.setdefault(tk, []).append(acc)
                    m.node_writes.setdefault(node.idx, []).append(tk)
                    continue
                cls_name, params_fn, dst_flow = dep.dst
                dst_tc = tp._tc_by_name.get(cls_name)
                if dst_tc is None:
                    m.problems.append((
                        "phantom-target", node.label, spec.name,
                        f"{node.label}.{spec.name} -> {cls_name}.{dst_flow}: "
                        f"no task class named {cls_name!r} in the taskpool"))
                    continue
                targets = params_fn(g, *p)
                if isinstance(targets, tuple):
                    targets = [targets]
                for tgt in targets:
                    tgt = _norm(tgt)
                    dst_idx = m.index.get((cls_name, tgt))
                    if dst_idx is None:
                        coords = ", ".join(map(str, tgt))
                        m.problems.append((
                            "phantom-target", node.label, spec.name,
                            f"{node.label}.{spec.name} -> "
                            f"{cls_name}({coords}).{dst_flow}: target task "
                            f"instance does not exist in the class space"))
                        continue
                    m.succ[node.idx].append(dst_idx)
                    m.edges.append(Edge(node.idx, dst_idx, spec.name,
                                        dst_flow))
                    m.produced.add((node.idx, spec.name, dst_idx, dst_flow))

    # pass 3: consumer-side (ins) — collection reads; the lint resolves
    # the In.src expectations against m.produced
    for node in m.nodes:
        tc, p = node.tc, node.coords
        for spec in tc.spec_list:
            try:
                dep = tc._active_in(g, spec, p)
            except RuntimeError as exc:
                m.problems.append((
                    "ambiguous-guards", node.label, spec.name, str(exc)))
                continue
            if dep is None or dep.data is None:
                continue
            dc, key = dep.data(g, *p)
            tk = _reg_tile(dc, key)
            acc = TileAccess(node.idx, spec.name, tk, spec.access, "read")
            m.reads.setdefault(tk, []).append(acc)

    # pass 4: affinity targets + touched tiles (owner-computes check).
    # "Touched" = any tile a flow declares it works on (FlowSpec.tile),
    # plus collection reads/writes — a task placed on ANY of those is
    # owner-computes-reasonable (e.g. geqrf's TSMQR sits on its trailing
    # A2 tile while its C1 pipeline hand-off writes the row tile).
    for node in m.nodes:
        touch = m.node_touch.setdefault(node.idx, set())
        touch.update(m.node_writes.get(node.idx, ()))
        for spec in node.tc.spec_list:
            if spec.tile is not None:
                dc, key = spec.tile(g, *node.coords)
                touch.add(_reg_tile(dc, key))
        aff = getattr(node.tc, "affinity", None)
        if aff is None:
            continue
        dc, key = aff(g, *node.coords)
        m.node_affinity[node.idx] = _reg_tile(dc, key)
    for tk, accs in m.reads.items():
        for a in accs:
            m.node_touch.setdefault(a.node, set()).add(tk)

    return m
