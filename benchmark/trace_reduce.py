"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

The reduction is the benchmark's, so every PR computes the same number in
the same way. It works on plain intervals ``(start, end)`` in seconds on
the trace's one clock:

* a chip is **busy** while any operation runs on it: the union of the
  intervals of the events on its ``XLA Ops`` line;
* the **window** is the traced steps and nothing between them: the union
  of the benchmark-side spans ``bench:step`` inside the span
  ``bench:traced`` that ``run.py`` puts around the traced part of the run.
  Making the next input and freeing the last result belong to the
  harness, not to the system, and are left out; idle share is
  1 − busy ÷ window;
* the spans a driver opens inside a step (``bench:insert``,
  ``bench:wait``) label what the host was doing during each idle gap (the
  shortest span that covers the gap's midpoint);
* a **collective** is exposed while it runs and no other operation does
  on that chip.

``load`` needs nothing but JAX (``jax.profiler.ProfileData``).
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]

SPAN_PREFIX = "bench:"
WINDOW_SPAN = "traced"
STEP_SPAN = "step"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
_COLLECTIVES = (r"(?:all-gather|all-reduce|reduce-scatter|collective-permute|"
                r"all-to-all|collective-broadcast|send|recv)(?:-start|-done)?")
# the trace names an operation by its HLO text, "%name = shape opcode(...)":
# the opcode decides, never an operand that happens to be called all-gather
_COLLECTIVE_OPCODE = re.compile(r"[}\]\)] " + _COLLECTIVES + r"\(")
_COLLECTIVE_NAME = re.compile(r"^%?" + _COLLECTIVES + r"(?:[.\d]|$)")


def is_collective(name: str) -> bool:
    head, eq, rest = name.partition(" = ")
    return bool(_COLLECTIVE_OPCODE.search(rest) if eq
                else _COLLECTIVE_NAME.match(head))


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[List[float]] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(hi - lo for lo, hi in intervals)


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The points of sorted disjoint ``a`` that sorted disjoint ``b``
    does not cover."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > lo:
                out.append((lo, b[k][0]))
            lo = max(lo, b[k][1])
            k += 1
        if hi > lo:
            out.append((lo, hi))
    return out


# ---------------------------------------------------------------------------
# the trace as the reduction sees it
# ---------------------------------------------------------------------------

@dataclass
class Trace:
    """``ops[chip]``: ``(name, start, end)`` of every event on that
    chip's operations line; ``spans``: ``(name, start, end)`` of the
    benchmark-side host spans, prefix stripped."""
    ops: Dict[int, List[Tuple[str, float, float]]] = field(
        default_factory=dict)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)


def load(path: str) -> Trace:
    """Read an ``.xplane.pb``: every ``/device:TPU:<n>`` plane's
    ``XLA Ops`` line, and the ``bench:`` spans of the host planes."""
    from jax.profiler import ProfileData
    trace = Trace()
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    trace.ops[int(m.group(1))] = [
                        (e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        trace.spans.append(
                            (e.name[len(SPAN_PREFIX):], e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9))
    trace.spans.sort(key=lambda s: s[1])
    return trace


def leaves(ops: Sequence[Tuple[str, float, float]]
           ) -> List[Tuple[str, float, float]]:
    """The events that contain no other event: a ``while`` or a
    ``conditional`` spans the operations of its body, and only those did
    the work."""
    out: List[Tuple[str, float, float]] = []
    stack: List[List] = []               # [event, has_child]
    for ev in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0][2] <= ev[1]:
            done, has_child = stack.pop()
            if not has_child:
                out.append(done)
        if stack and ev[2] <= stack[-1][0][2]:
            stack[-1][1] = True          # nested; a mere overlap is not
        stack.append([ev, False])
    out.extend(ev for ev, has_child in stack if not has_child)
    return out


def label_gap(gap: Interval, spans: Sequence[Tuple[str, float, float]]
              ) -> str:
    """What the host was doing in ``gap``: the shortest benchmark span
    (the span around all traced steps aside) that covers its midpoint."""
    mid = 0.5 * (gap[0] + gap[1])
    best = None
    for name, lo, hi in spans:
        if name != WINDOW_SPAN and lo <= mid < hi and \
                (best is None or hi - lo < best[1]):
            best = (name, hi - lo)
    return best[0] if best else "outside_spans"


def op_family(name: str) -> str:
    """The name a table of operations sums under. XLA numbers its
    operations (``%fusion.123``), and ten numbered fusions say less than
    their sum: the number goes, a plain fusion keeps its kind and a
    custom call its target."""
    head, _eq, rest = name.partition(" = ")
    family = re.sub(r"(\.\d+|\.remat\d*|\.clone)+$", "", head.lstrip("%")) \
        or head
    if family == "fusion":
        kind = re.search(r"kind=(\w+)", rest)
        return f"fusion({kind.group(1)})" if kind else family
    if family == "custom-call":
        target = re.search(r'custom_call_target="([^"]+)"', rest)
        return f"custom-call({target.group(1)})" if target else family
    return family


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

def reduce(trace: Trace, top: int = 10) -> Dict[str, object]:
    """Everything the device-trace metrics read, or ``{}`` when the
    trace holds no window span or no device operation inside it."""
    traced = [(lo, hi) for name, lo, hi in trace.spans
              if name == WINDOW_SPAN]
    if not traced or not trace.ops:
        return {}
    window = union((lo, hi) for name, lo, hi in trace.spans
                   if name == STEP_SPAN and lo >= traced[0][0]
                   and hi <= traced[0][1])
    if not window:
        return {}
    window_s, n_steps = total(window), len(window)

    busy_s, exposed_s = {}, {}
    by_name: Dict[str, float] = defaultdict(float)
    gap_sum: Dict[str, float] = defaultdict(float)
    gap_count: Dict[str, int] = defaultdict(int)
    longest: List[Tuple[float, str]] = []
    for chip, ops in trace.ops.items():
        leaf = [(n, lo, hi) for n, lo0, hi0 in leaves(ops)
                for lo, hi in intersect([(lo0, hi0)], window)]
        busy = union((lo, hi) for _n, lo, hi in leaf)
        busy_s[chip] = total(busy)
        coll = union((lo, hi) for n, lo, hi in leaf if is_collective(n))
        rest = union((lo, hi) for n, lo, hi in leaf
                     if not is_collective(n))
        exposed_s[chip] = total(subtract(coll, rest))
        for n, lo, hi in leaf:
            by_name[op_family(n)] += hi - lo
        for gap in subtract(window, busy):
            label = label_gap(gap, trace.spans)
            gap_sum[label] += gap[1] - gap[0]
            gap_count[label] += 1
            longest.append((gap[1] - gap[0], label))
    if not any(busy_s.values()):
        return {}
    chips = len(trace.ops)
    mean_busy = sum(busy_s.values()) / chips
    longest.sort(reverse=True)
    idle_gaps = [[f"sum:{label} ({gap_count[label]} gaps)", s / chips]
                 for label, s in sorted(gap_sum.items(),
                                        key=lambda kv: -kv[1])[:top // 2]]
    idle_gaps += [[f"longest:{label}", s] for s, label in longest[:top // 2]]
    return {
        "window_s": window_s,
        "busy_s": mean_busy,
        "busy_s_by_chip": busy_s,
        "idle_share": 1.0 - mean_busy / window_s,
        "steps": n_steps,
        "device_step_s": mean_busy / n_steps,
        "collective_exposed_share":
            sum(exposed_s.values()) / chips / window_s,
        "busy_max_over_min": (max(busy_s.values()) / min(busy_s.values())
                              if min(busy_s.values()) > 0 else None),
        "device_ops": [[n, s / chips] for n, s in
                       sorted(by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": idle_gaps,
    }
