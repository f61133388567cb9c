"""A launch taken apart, the module's turn, and the chip's idle time put
down to the host stage that left it idle.

The program opens three spans beside the ones ``program_spans`` reads,
on the same flag and the same clock (``core/spans.py``):

* ``parsec:turn``: a worker that holds a ready task waits for its
  module's turn (the acquisition alone; outside every other span);
* ``parsec:exec_wait``: the host waits for the chip, inside a launch's
  ``parsec:exec``: for the module's last group, or for the oldest lone
  launch once ``GROUP_BYTES`` of new outputs are queued;
* ``parsec:exec_call``: the jitted call until it returns, inside the same
  ``parsec:exec``. What is left of ``exec`` is staging and attaching the
  outputs.

Over the traced steps (``trace_reduce``'s window: the ``bench:step`` spans
inside ``bench:traced``), in thread-seconds as ``program_spans`` counts:

* ``launch_call_us``: sum of ``exec_call`` / ``exec_call`` spans that
  start inside the steps: one trip through jit dispatch, whatever it
  carries;
* ``chip_wait_us_per_task``: sum of ``exec_wait`` / tasks;
* ``turn_wait_share``: sum of ``turn`` / (worker threads seen x traced
  step time), as ``workers_parked_share`` is made.

**The partition.** The chip is idle inside the traced steps wherever no
leaf of its ``XLA Ops`` line runs (``trace_reduce``'s busy time). Every
idle instant goes to the FIRST class that applies, so the five classes'
seconds sum to the idle seconds. ``idle_<class>_share`` is a class's
seconds as a share of the traced STEP time (mean over chips), not of the
idle time: the five sum to the chip's idle share of the step, and one
falls when its stage is repaired whatever the others do (shares of the
idle time would rise wherever another class shrank); a class's share of
the idle time is its share over the five's sum. The classes:

1. ``launching``: some thread is inside ``parsec:exec`` and not inside
   its own ``exec_wait`` (staging, the call, attaching): the chip waits
   for a launch on its way;
2. ``releasing``: else some thread is inside ``parsec:release``;
3. ``front_end``: else some thread is inside ``insert``, ``dtd_flush``,
   ``ptg_startup``, ``select``, or ``dispatch`` less the ``exec`` nested
   in it on that thread (as ``program_spans.reduce`` takes it);
4. ``completion``: else some thread is inside ``exec_wait``: the chip is
   idle and all the host does is wait for the chip, so it hears late;
5. ``unaccounted``: no thread is inside any of these (waits for the turn
   with nobody working, parks, hand-offs of the GIL between spans).

Nothing idle reads ``None`` for all five, as does a trace without a TPU
plane (a CPU rehearsal) and a program without ``exec_call`` spans (then
the three span figures are ``None`` too: ``exec`` would hold its waits).

**One clock, checked.** Host spans and device events share the
profiler's clock, but the device's timestamps have been seen 0.1-1 ms
before the host's, which is the size of a launch. The trace bounds the
offset: the k-th program named ``jit_parsec_*`` to start on a chip's
``XLA Modules`` line cannot have started before k ``exec_call`` spans had
started, whichever thread made which call, both counted within one
traced step (a step begins with the chip's queue empty and ends in
``block_until_ready``; a program belongs to the step it starts nearest
to). The least of (k-th program start - k-th call start) over all steps
is printed as ``[clock] device_minus_host_us``; where it is negative the
device's intervals are moved later by it before they are intersected with
the host's (``shifted=1``), after which no program starts before its
call. The bound is the least shift causality asks for: the device may
still be early by less than the time a call takes to reach the chip. A
program launched outside ``exec_call`` (a chip module's first run of a
new program) would make the bound too large; none runs inside the window
(``compiles_in_window`` reads 0).

How early the device may still be is not bounded here, and it matters
to one class: a program that ends later by the device's true clock leaves
less of its successor's wait idle, so idle time moves from ``completion``
to ``launching`` as the clock moves. The metrics are read at the least
shift, where ``completion`` is at its LARGEST: ``idle_completion_share``
is an upper bound (PERF.md section 7).

``reduced()`` parses a trace once a file and prints ``[clock]`` and
``[idle_by_stage]`` (the five classes and their sum in seconds a step,
the step's seconds, and the spans counted).
"""

from __future__ import annotations

import bisect
import functools
import os
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark import program_spans
from benchmark.readers.device_seconds_by_program import (MODULES_LINE,
                                                         PROGRAM)
from benchmark.trace_reduce import (DEVICE_PLANE, OPS_LINE, SPAN_PREFIX,
                                    STEP_SPAN, WINDOW_SPAN, Interval,
                                    intersect, leaves, subtract, total,
                                    union)

CLASSES = ("launching", "releasing", "front_end", "completion",
           "unaccounted")
FRONT_END = ("insert", "dtd_flush", "ptg_startup", "select")
Events = Dict[int, List[Tuple[str, float, float]]]


def steps_of(bench) -> List[Interval]:
    """The traced steps: ``bench:step`` inside the first ``bench:traced``."""
    traced = [(lo, hi) for name, lo, hi in bench if name == WINDOW_SPAN]
    if not traced:
        return []
    return union((lo, hi) for name, lo, hi in bench
                 if name == STEP_SPAN and lo >= traced[0][0]
                 and hi <= traced[0][1])


def host_classes(threads) -> Dict[str, List[Interval]]:
    """Where some thread is inside a class's spans, for the four classes
    that have spans; ``threads`` as ``program_spans.Spans.threads``."""
    found: Dict[str, List[Interval]] = {c: [] for c in CLASSES[:-1]}
    for stages in threads.values():
        own = {s: union(iv) for s, iv in stages.items()}
        execs, waits = own.get("exec", []), own.get("exec_wait", [])
        found["launching"] += subtract(execs, waits)
        found["releasing"] += own.get("release", [])
        found["front_end"] += subtract(own.get("dispatch", []), execs)
        for stage in FRONT_END:
            found["front_end"] += own.get(stage, [])
        found["completion"] += waits
    return {c: union(iv) for c, iv in found.items()}


def partition(idle: Sequence[Interval], classes: Dict[str, List[Interval]]
              ) -> Dict[str, float]:
    """Seconds of sorted disjoint ``idle`` by class, each instant in the
    first class of ``CLASSES`` that covers it."""
    seconds, rest = {}, list(idle)
    for c in CLASSES[:-1]:
        seconds[c] = total(intersect(rest, classes[c]))
        rest = subtract(rest, classes[c])
    seconds[CLASSES[-1]] = total(rest)
    return seconds


def _step_index(steps: Sequence[Interval]):
    """``t -> index`` of the step ``t`` lies nearest to: the cuts are the
    middles of the gaps between steps, so a clock a little early or late
    moves no start over to a neighbour."""
    cuts = [0.5 * (a[1] + b[0]) for a, b in zip(steps, steps[1:])]
    return lambda t: bisect.bisect_right(cuts, t)


def clock_bound(programs: Sequence[float], calls: Sequence[float],
                steps: Sequence[Interval]) -> Tuple[Optional[float], int]:
    """``(least of k-th program start - k-th call start, pairs)``, both
    counted within a step; ``(None, 0)`` without a pair."""
    step_of = _step_index(steps)

    def by_step(starts):
        out: List[List[float]] = [[] for _ in steps]
        for t in sorted(starts):
            out[step_of(t)].append(t)
        return out

    diffs = [p - c for ps, cs in zip(by_step(programs), by_step(calls))
             for p, c in zip(ps, cs)]
    return (min(diffs), len(diffs)) if diffs else (None, 0)


def reduce(spans: program_spans.Spans, ops: Events, modules: Events,
           tasks_per_step: int) -> Optional[Dict[str, object]]:
    """Every figure of this module over the traced steps. ``ops`` and
    ``modules``: ``{chip: [(name, start, end), ...]}`` of the chips'
    ``XLA Ops`` and ``XLA Modules`` lines. ``None`` without a traced step
    or an ``exec_call`` span; the ``idle_*`` shares (of the traced step
    time) are ``None`` where no chip's operations are in the trace or
    nothing is idle."""
    steps = steps_of(spans.bench)
    if not steps or not tasks_per_step or not any(
            "exec_call" in stages for stages in spans.threads.values()):
        return None
    window_s, tasks = total(steps), tasks_per_step * len(steps)

    def inside(t):
        return any(lo <= t < hi for lo, hi in steps)

    seconds = dict.fromkeys(("turn", "exec_wait", "exec_call"), 0.0)
    starts: Dict[str, List[float]] = {s: [] for s in seconds}
    workers = 0
    for stages in spans.threads.values():
        workers += "select" in stages or program_spans.PARK in stages
        for s in seconds:
            seconds[s] += total(intersect(union(stages.get(s, [])), steps))
            starts[s] += [lo for lo, _hi in stages.get(s, ()) if inside(lo)]
    started = {s: len(found) for s, found in starts.items()}
    calls = starts["exec_call"]
    out: Dict[str, object] = {
        "launch_call_us": (1e6 * seconds["exec_call"] / started["exec_call"]
                           if started["exec_call"] else None),
        "chip_wait_us_per_task": 1e6 * seconds["exec_wait"] / tasks,
        "turn_wait_share": (100.0 * seconds["turn"] / (workers * window_s)
                            if workers else None),
        "steps": len(steps), "window_s": window_s, "spans": started,
        "clock_us": None, "shifted": 0, "pairs": 0, "idle_s": None}
    out.update((f"idle_{c}_share", None) for c in CLASSES)

    chips = {chip: ev for chip, ev in ops.items() if ev}
    if not chips:
        return out
    least, pairs = None, 0
    for events in modules.values():
        found, n = clock_bound([lo for name, lo, _hi in events
                                if PROGRAM.match(name)], calls, steps)
        pairs += n
        if found is not None and (least is None or found < least):
            least = found
    shift = -least if least is not None and least < 0 else 0.0
    out.update(clock_us=None if least is None else 1e6 * least,
               shifted=int(shift > 0), pairs=pairs)
    classes = host_classes(spans.threads)
    idle = dict.fromkeys(CLASSES, 0.0)
    for events in chips.values():
        busy = union((lo + shift, hi + shift)
                     for _n, lo, hi in leaves(events))
        for c, found in partition(subtract(steps, busy), classes).items():
            idle[c] += found / len(chips)
    out["idle_s"] = dict(idle, all=sum(idle.values()))
    if out["idle_s"]["all"] > 0:
        out.update((f"idle_{c}_share", 100.0 * idle[c] / window_s)
                   for c in CLASSES)
    return out


def load(path: str) -> Tuple[program_spans.Spans, Events, Events]:
    """``(spans, ops, modules)`` of an ``.xplane.pb`` in one pass: the
    host planes as ``program_spans.load`` reads them (a line is a
    thread), and of every ``/device:TPU:<n>`` plane the events of its
    ``XLA Ops`` and of its ``XLA Modules`` line
    (``device_seconds_by_program``'s, a program's run as one event)."""
    from jax.profiler import ProfileData
    spans, ops, modules = program_spans.Spans(), {}, {}
    for plane in ProfileData.from_file(path).planes:
        chip = DEVICE_PLANE.match(plane.name)
        if not chip and not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            into = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
            if chip and into is None:
                continue
            events = [(e.name, e.start_ns * 1e-9,
                       (e.start_ns + e.duration_ns) * 1e-9)
                      for e in line.events]
            if chip:
                into[int(chip.group(1))] = events
                continue
            for name, lo, hi in events:
                if name.startswith(program_spans.PROGRAM_PREFIX):
                    spans.threads.setdefault(f"{plane.name}#{i}", {}) \
                        .setdefault(name[len(program_spans.PROGRAM_PREFIX):],
                                    []).append((lo, hi))
                elif name.startswith(SPAN_PREFIX):
                    spans.bench.append((name[len(SPAN_PREFIX):], lo, hi))
    spans.bench.sort(key=lambda s: s[1])
    return spans, ops, modules


@functools.lru_cache(maxsize=4)
def _reduced(path: str, _mtime: float, tasks_per_step: int):
    out = reduce(*load(path), tasks_per_step)
    if out is None:
        return None
    if out["pairs"]:
        print(f"[clock] device_minus_host_us={out['clock_us']:.3f} "
              f"shifted={out['shifted']} pairs={out['pairs']}", flush=True)
    print("[idle_by_stage] " + " ".join(
        [f"{c}_s={s / out['steps']:.6f}"
         for c, s in (out["idle_s"] or {}).items()] +
        [f"step_s={out['window_s'] / out['steps']:.6f}"] +
        [f"{s}_spans={n}" for s, n in out["spans"].items()] +
        [f"steps={out['steps']}"]), flush=True)
    return out


def reduced(checkout: str, cell: str, tasks_per_step: int
            ) -> Optional[Dict[str, object]]:
    """``reduce`` of the trace ``run.py`` left for ``cell`` under
    ``checkout``; parsed once a file (the harness loads a reader anew
    for every metric)."""
    path = program_spans.find(checkout, cell)
    if path is None:
        return None
    return _reduced(path, os.path.getmtime(path), tasks_per_step)
