"""Static dataflow lint over PTG/JDF taskpools.

PaRSEC's correctness story rests on the JDF/PTG dependency declarations
fully determining the execution order; the reference audits the claim
with ``jdf_sanity_checks`` (jdf.c) at compile time, the grapher/DOT
output, and the iterators_checker PINS module at runtime.  This module
is the static half of that tooling here: it materializes the bounded
instance DAG (analysis/model.py) and reports, with the exact task
class, flow and coordinates:

- **undeclared-producer** — an ``In(src=...)`` edge whose named source
  instance does not exist, or whose flow never emits to this consumer;
- **waw-hazard** — two *unordered* task instances both write the same
  collection tile (the final tile value is schedule-dependent);
- **war-hazard** — a collection read unordered against a writer of the
  same tile (the reader may observe either version);
- **access-violation** — data flowing through a flow whose declared
  :class:`~parsec_tpu.core.task.FlowAccess` forbids it (CTL flows
  carrying payloads, terminal write-backs through READ flows, reads
  into WRITE-only flows) — the static cross-check of the WRITE/RW
  return-arity contract ``core/task.py`` documents (the dynamic half
  lives in analysis/dfsan.py);
- **cycle** — a dependency cycle among task instances (the taskpool can
  never quiesce);
- **phantom-target** / **ambiguous-guards** — an ``Out`` aimed at a
  nonexistent class/instance; overlapping In guards;
- **dangling-output** (warning) — a produced WRITE/RW value that no
  active dep consumes or writes back (silently dropped — suppressed for
  flows tiled onto ``scratch`` collections, which are intra-DAG
  temporaries by declaration);
- **affinity-mismatch** (warning) — owner-computes violations: a task
  terminally writes tiles but its affinity names none of them, forcing
  an avoidable remote write-back.

Entry points: :func:`lint_taskpool`, ``Taskpool.validate()`` (method on
the core taskpool), the ``analysis.lint = off|warn|error`` MCA knob
checked at taskpool registration, and ``python -m parsec_tpu.analysis``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Tuple

from ..core.task import FlowAccess
from .model import Model, _norm, build_model

ERROR = "error"
WARNING = "warning"
NOTE = "note"


@dataclass
class Finding:
    """One lint finding, anchored to a task instance / flow / tile."""
    rule: str
    severity: str
    task: str                  # "CLASS(coords)" primary site
    flow: str = ""
    tile: str = ""
    message: str = ""
    # for hazard findings: the second task instance of the unordered pair
    other: str = ""

    def __str__(self) -> str:
        return f"[{self.severity}] {self.rule}: {self.message}"


@dataclass
class LintReport:
    """All findings of one lint run plus the model they refer to."""
    taskpool: str
    findings: List[Finding] = field(default_factory=list)
    model: Optional[Model] = None
    truncated: bool = False
    skipped_classes: List[str] = field(default_factory=list)

    @property
    def errors(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == ERROR]

    @property
    def warnings(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == WARNING]

    @property
    def ok(self) -> bool:
        return not self.errors

    def by_rule(self, rule: str) -> List[Finding]:
        return [f for f in self.findings if f.rule == rule]

    def summary(self) -> str:
        n = len(self.model.nodes) if self.model is not None else 0
        parts = [f"{self.taskpool}: {n} task instances",
                 f"{len(self.errors)} errors",
                 f"{len(self.warnings)} warnings"]
        if self.truncated:
            parts.append("TRUNCATED (analysis.lint_max_tasks)")
        if self.skipped_classes:
            parts.append(f"skipped non-PTG classes: "
                         f"{', '.join(self.skipped_classes)}")
        return "; ".join(parts)

    def __str__(self) -> str:
        lines = [self.summary()]
        lines.extend(f"  {f}" for f in self.findings)
        return "\n".join(lines)

    # -- visual report ------------------------------------------------------
    def to_dot(self) -> str:
        """DOT rendering of the instance DAG with edges colored by
        FlowAccess and hazard edges marked — the lint's visual report
        (profiling/grapher.py does the rendering; satellite of the
        reference's --dot grapher)."""
        from ..profiling.grapher import Grapher
        gr = Grapher()
        if self.model is None:
            return gr.to_dot()
        for node in self.model.nodes:
            gr.add_node(node.label, node.tc.name)
        for e in self.model.edges:
            access = self.model.nodes[e.dst].tc.flow_by_name[e.dst_flow].access
            gr.add_edge(self.model.nodes[e.src].label,
                        self.model.nodes[e.dst].label,
                        e.dst_flow, access)
        for f in self.findings:
            if f.rule in ("waw-hazard", "war-hazard") and f.other:
                gr.mark_hazard(f.task, f.other, f.flow, f.rule)
            elif f.rule == "cycle" and f.other:
                gr.mark_hazard(f.task, f.other, f.flow, f.rule)
        return gr.to_dot()


class HazardError(ValueError):
    """Raised by ``taskpool.validate()`` / the ``analysis.lint=error``
    registration check when the lint reports error-severity findings."""

    def __init__(self, report: LintReport):
        super().__init__(str(report))
        self.report = report


def _tile_str(tk: Tuple[str, Tuple]) -> str:
    return f"{tk[0]}{tk[1]}"


def _check_structural(tp, report: LintReport) -> None:
    """Per-class spec checks that need no instance enumeration (always
    run, even past the lint_max_tasks cap)."""
    for tc in tp.task_classes:
        for spec in getattr(tc, "spec_list", ()):
            is_ctl = bool(spec.access & FlowAccess.CTL)
            writes = bool(spec.access & FlowAccess.WRITE)
            reads = bool(spec.access & FlowAccess.READ)
            for dep in spec.ins:
                if is_ctl and (dep.data is not None or dep.new is not None):
                    report.findings.append(Finding(
                        "access-violation", ERROR, tc.name, spec.name,
                        message=f"{tc.name}.{spec.name}: CTL flow declares "
                                f"a data/NEW input — control flows carry "
                                f"no payload"))
                if writes and not reads and not is_ctl and (
                        dep.src is not None or dep.data is not None):
                    report.findings.append(Finding(
                        "access-violation", ERROR, tc.name, spec.name,
                        message=f"{tc.name}.{spec.name}: WRITE-only flow "
                                f"consumes an input value (declare RW, or "
                                f"use NEW for a fresh value)"))
            for dep in spec.outs:
                if is_ctl and dep.data is not None:
                    report.findings.append(Finding(
                        "access-violation", ERROR, tc.name, spec.name,
                        message=f"{tc.name}.{spec.name}: CTL flow declares "
                                f"a terminal collection write-back"))
                if reads and not writes and not is_ctl and \
                        dep.data is not None:
                    report.findings.append(Finding(
                        "access-violation", ERROR, tc.name, spec.name,
                        message=f"{tc.name}.{spec.name}: READ flow declares "
                                f"a terminal collection write-back — the "
                                f"body cannot produce a value for it "
                                f"(core.task: only WRITE/RW flows are "
                                f"output flows)"))


def _check_undeclared_producers(m: Model, report: LintReport) -> None:
    g = m.taskpool.g
    for node in m.nodes:
        tc, p = node.tc, node.coords
        for spec in tc.spec_list:
            try:
                dep = tc._active_in(g, spec, p)
            except RuntimeError:
                continue        # already reported as ambiguous-guards
            if dep is None or dep.src is None:
                continue
            src_cls, src_params_fn, src_flow = dep.src
            sp = src_params_fn(g, *p)
            if dep.gather:
                raw = [sp] if isinstance(sp, tuple) else sp
                coords = sorted({_norm(c) for c in raw})
            else:
                coords = [_norm(sp)]
            for coord in coords:
                src_label = f"{src_cls}({', '.join(map(str, coord))})"
                src_idx = m.index.get((src_cls, coord))
                if src_idx is None:
                    report.findings.append(Finding(
                        "undeclared-producer", ERROR, node.label, spec.name,
                        message=f"{node.label}.{spec.name} <- "
                                f"{src_label}.{src_flow}: the named "
                                f"producer instance does not exist"))
                    continue
                if (src_idx, src_flow, node.idx, spec.name) not in m.produced:
                    report.findings.append(Finding(
                        "undeclared-producer", ERROR, node.label, spec.name,
                        other=m.nodes[src_idx].label,
                        message=f"{node.label}.{spec.name} <- "
                                f"{src_label}.{src_flow}: the producer "
                                f"exists but its flow {src_flow!r} never "
                                f"emits to {node.label}.{spec.name} (no "
                                f"matching Out declaration)"))


def _check_dangling_outputs(m: Model, report: LintReport) -> None:
    g = m.taskpool.g
    for node in m.nodes:
        tc, p = node.tc, node.coords
        for spec in tc.spec_list:
            if not (spec.access & FlowAccess.WRITE) or \
                    (spec.access & FlowAccess.CTL):
                continue
            if any(dep.active(g, p) for dep in spec.outs):
                continue
            # scratch-tiled flows are intra-DAG temporaries: dropping the
            # last wave's value is their declared contract
            if spec.tile is not None:
                dc, _key = spec.tile(g, *p)
                if getattr(dc, "scratch", False):
                    continue
            report.findings.append(Finding(
                "dangling-output", WARNING, node.label, spec.name,
                message=f"{node.label}.{spec.name}: WRITE flow has no "
                        f"active output dep — the produced value is "
                        f"silently dropped"))


def _check_hazards(m: Model, report: LintReport) -> None:
    """WAW (unordered writers) and WAR/RAW (read unordered with a write)
    hazards per collection tile. Writers of one tile must form a total
    order: checking consecutive pairs of a topological linearization is
    sufficient — any unordered pair leaves some consecutive pair
    unordered."""
    order, _ = m.topo_order()
    topo_pos = {idx: i for i, idx in enumerate(order)}

    def pos(i: int) -> int:
        return topo_pos.get(i, len(m.nodes))

    for tk, accs in m.writes.items():
        writers = sorted({a.node for a in accs}, key=pos)
        flow_of = {a.node: a.flow for a in accs}
        for a, b in zip(writers, writers[1:]):
            if not m.ordered(a, b):
                report.findings.append(Finding(
                    "waw-hazard", ERROR, m.nodes[a].label,
                    flow_of[a], _tile_str(tk), other=m.nodes[b].label,
                    message=f"WAW hazard on tile {_tile_str(tk)}: "
                            f"{m.nodes[a].label}.{flow_of[a]} and "
                            f"{m.nodes[b].label}.{flow_of[b]} both write "
                            f"it with no dependency path ordering them — "
                            f"the final value is schedule-dependent"))
        readers = m.reads.get(tk, ())
        for r in readers:
            for w in writers:
                if w == r.node:
                    continue
                if not m.ordered(r.node, w):
                    report.findings.append(Finding(
                        "war-hazard", ERROR, m.nodes[r.node].label,
                        r.flow, _tile_str(tk), other=m.nodes[w].label,
                        message=f"read/write hazard on tile "
                                f"{_tile_str(tk)}: "
                                f"{m.nodes[r.node].label}.{r.flow} reads "
                                f"it unordered against writer "
                                f"{m.nodes[w].label}.{flow_of[w]} — the "
                                f"reader may observe either version"))


def _check_cycles(m: Model, report: LintReport) -> None:
    cyc = m.find_cycle()
    if cyc is None:
        return
    labels = [m.nodes[i].label for i in cyc]
    report.findings.append(Finding(
        "cycle", ERROR, labels[0], other=labels[1] if len(labels) > 1 else "",
        message=f"dependency cycle: {' -> '.join(labels)} — these tasks "
                f"can never become ready (deps_goal unreachable)"))


def _check_affinity(m: Model, report: LintReport) -> None:
    """Owner-computes: a task's affinity tile should be one the task
    actually works on (any flow's declared tile, read or write) —
    placing it elsewhere makes EVERY data movement remote.  A terminal
    write landing off-affinity is fine when the task also works on its
    affinity tile (pipeline hand-offs like geqrf TSMQR's row tile)."""
    for idx, aff in m.node_affinity.items():
        written = m.node_writes.get(idx)
        if not written:
            continue
        touched = m.node_touch.get(idx, ())
        if aff in written or aff in touched:
            continue
        node = m.nodes[idx]
        report.findings.append(Finding(
            "affinity-mismatch", WARNING, node.label, tile=_tile_str(aff),
            message=f"{node.label}: owner-computes mismatch — affinity "
                    f"places the task on {_tile_str(aff)}, a tile it "
                    f"never touches, while it terminally writes "
                    f"{', '.join(_tile_str(t) for t in written)}; every "
                    f"transfer becomes remote"))


def lint_taskpool(tp, max_tasks: int = 0) -> LintReport:
    """Run every static check over ``tp`` and return the report.

    Works on any core taskpool; task classes without closed-form PTG
    specs (DTD, hand-built vtables) are listed in
    ``report.skipped_classes`` — their ordering is runtime state, which
    the dynamic sanitizer (analysis/dfsan.py) covers instead.
    """
    report = LintReport(taskpool=tp.name)
    if any(getattr(tc, "ranged", False) for tc in tp.task_classes):
        # the model holds ONE tile a flow; a pool with a flow over a
        # range of tiles (dsl/ptg.py) is check_taskpool's and dfsan's
        report.skipped_classes = [tc.name for tc in tp.task_classes]
        report.findings.append(Finding(
            "ranged", NOTE, tp.name,
            message=f"{tp.name}: a ranged data flow (gather/scatter) is "
                    f"not modelled — no static check ran; "
                    f"ptg.check_taskpool cross-validates its edges and "
                    f"the dfsan sanitizer its reads"))
        return report
    _check_structural(tp, report)
    m = build_model(tp, max_tasks=max_tasks)
    report.model = m
    report.truncated = m.truncated
    report.skipped_classes = m.skipped_classes
    for rule, task, flow, msg in m.problems:
        report.findings.append(Finding(rule, ERROR, task, flow, message=msg))
    if m.truncated:
        report.findings.append(Finding(
            "truncated", NOTE, tp.name,
            message=f"{tp.name}: task space exceeds analysis.lint_max_tasks"
                    f" — instance-level checks skipped (structural checks "
                    f"still ran); raise the MCA param to lint fully"))
        return report
    if not m.nodes:
        return report
    _check_undeclared_producers(m, report)
    _check_dangling_outputs(m, report)
    _check_cycles(m, report)
    _check_hazards(m, report)
    _check_affinity(m, report)
    return report


def validate(tp, mode: str = "error", max_tasks: int = 0) -> LintReport:
    """``taskpool.validate()`` implementation (core/taskpool.py binds
    it): lint and, per ``mode``, raise :class:`HazardError` on errors
    (``"error"``) or log them (``"warn"``)."""
    report = lint_taskpool(tp, max_tasks=max_tasks)
    if mode == "error" and not report.ok:
        raise HazardError(report)
    if mode == "warn" and report.findings:
        from ..utils.debug import warning
        for f in report.findings:
            warning("analysis", "%s", f)
    return report


# ---------------------------------------------------------------------------
# hot-path config-lookup lint (source-level, AST)
# ---------------------------------------------------------------------------

#: scheduler entry points that run once per task on every worker — an
#: uncached registry read here is a cross-worker serialization point
#: (PR 15 found exactly this in wfq select(): the full mca_param.get
#: takes the global registry lock and re-resolves the environment)
_HOT_FUNCS = frozenset({"select", "steal", "try_steal", "schedule",
                        "pop_front", "pop_back"})

#: mca_param entry points that are SAFE on the hot path
_CACHED_READS = frozenset({"cached_get"})


def _scan_hot_config_source(src: str, filename: str) -> List[Finding]:
    """AST scan of one source file for uncached ``mca_param.get`` /
    ``mca_param.registry`` calls on hot paths: anywhere inside a
    scheduler hot function (``_HOT_FUNCS``), or inside any loop of any
    other function (the worker-main shape — a one-time read in the
    preamble is fine, the same read per loop iteration is not)."""
    import ast
    findings: List[Finding] = []
    try:
        tree = ast.parse(src, filename=filename)
    except SyntaxError as exc:
        findings.append(Finding(
            "hot-config-read", NOTE, filename,
            message=f"{filename}: unparseable, skipped ({exc})"))
        return findings

    def is_config_read(call: "ast.Call") -> Optional[str]:
        fn = call.func
        if not isinstance(fn, ast.Attribute):
            return None
        if fn.attr in _CACHED_READS:
            return None
        if fn.attr not in ("get", "registry"):
            return None
        base = fn.value
        if isinstance(base, ast.Name) and base.id == "mca_param":
            return f"mca_param.{fn.attr}"
        if isinstance(base, ast.Attribute) and base.attr == "mca_param":
            return f"mca_param.{fn.attr}"
        return None

    def scan_func(fn_node, qual: str) -> None:
        hot_everywhere = fn_node.name in _HOT_FUNCS
        # (node, loop_depth) walk that does NOT descend into nested
        # function definitions (they get their own scan_func pass)
        stack = [(child, 0) for child in fn_node.body]
        while stack:
            node, depth = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            d = depth + 1 if isinstance(
                node, (ast.For, ast.While, ast.AsyncFor)) else depth
            if isinstance(node, ast.Call):
                read = is_config_read(node)
                if read is not None and (hot_everywhere or depth > 0):
                    where = ("scheduler hot function" if hot_everywhere
                             else "loop body")
                    findings.append(Finding(
                        "hot-config-read", ERROR,
                        f"{qual} ({filename}:{node.lineno})",
                        message=f"{filename}:{node.lineno}: {read} in "
                                f"{where} {qual}() — a full registry "
                                f"read (global lock + env resolve) "
                                f"once per task serializes the "
                                f"workers; hoist it or use "
                                f"mca_param.cached_get"))
            for child in ast.iter_child_nodes(node):
                stack.append((child, d))

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scan_func(node, node.name)
    return findings


def lint_hot_config(paths: Optional[List[str]] = None) -> List[Finding]:
    """Scan the scheduler package and the worker loop (the shipped hot
    paths) — or an explicit file list — for uncached config reads.
    The shipped tree is the rule's zero-false-positive contract
    (enforced by the analysis CLI self-check)."""
    import glob
    import os
    if paths is None:
        pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        paths = sorted(glob.glob(os.path.join(pkg, "sched", "*.py")))
        paths.append(os.path.join(pkg, "core", "context.py"))
    findings: List[Finding] = []
    for path in paths:
        try:
            with open(path) as fh:
                src = fh.read()
        except OSError as exc:
            findings.append(Finding(
                "hot-config-read", NOTE, path,
                message=f"{path}: unreadable, skipped ({exc})"))
            continue
        findings.extend(
            _scan_hot_config_source(src, os.path.basename(path)))
    return findings
