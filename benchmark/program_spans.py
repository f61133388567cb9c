"""From the traced run's ``.xplane.pb`` to the runtime's stages per task.

The program opens a span at each of its stage-timer sites whenever a
profiler session is live (``parsec:insert``, ``parsec:select``,
``parsec:park``, ``parsec:dispatch``, ``parsec:exec`` nested in it,
``parsec:release``; ``core.context.StageSpan``). They are written into
the profiler's own trace, so they are on one clock with the device's
operations and with the harness's ``bench:`` spans.

The harness hands a reader its ``record`` and nothing from inside the
program, so the route here is the file: ``run.py --trace 1`` leaves the
trace at ``<checkout>/.benchmark_trace/<cell>/plugins/profile/*/*.xplane.pb``
and ``stages()`` opens it there. The window is the one ``trace_reduce``
uses: the ``bench:step`` spans inside ``bench:traced``. Everything is
plain interval arithmetic (``reduce``), in **thread-seconds**: a span
counts the time its thread waited for the GIL too, which is why
``host_threads_busy_mean`` is read beside the per-task figures (1.0 means
the interpreter ran one stage at a time and the worker threads bought
nothing).

``stages()`` returns ``None`` where there is nothing to read: no trace, a
trace older than this process (a window too short to start the tracer
leaves the last run's file in place), or no ``parsec:`` span in it (a
cell that bypasses the host runtime, or a program without the spans).
"""

from __future__ import annotations

import functools
import glob
import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from benchmark.trace_reduce import (SPAN_PREFIX, STEP_SPAN, WINDOW_SPAN,
                                    Interval, intersect, subtract, total,
                                    union)

PROGRAM_PREFIX = "parsec:"
BUSY = ("insert", "select", "dispatch", "exec", "release")
PARK = "park"


@dataclass
class Spans:
    """``threads[thread][stage]``: the ``(start, end)`` of that thread's
    ``parsec:<stage>`` spans; ``bench``: ``(name, start, end)`` of the
    harness's spans, prefix stripped. Seconds on the trace's clock."""
    threads: Dict[str, Dict[str, List[Interval]]] = field(
        default_factory=dict)
    bench: List[Tuple[str, float, float]] = field(default_factory=list)


def load(path: str) -> Spans:
    """Read the host planes of an ``.xplane.pb``; a line is a thread."""
    from jax.profiler import ProfileData
    spans = Spans()
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                lo = e.start_ns * 1e-9
                hi = (e.start_ns + e.duration_ns) * 1e-9
                if e.name.startswith(PROGRAM_PREFIX):
                    spans.threads.setdefault(f"{plane.name}#{i}", {}) \
                        .setdefault(e.name[len(PROGRAM_PREFIX):], []) \
                        .append((lo, hi))
                elif e.name.startswith(SPAN_PREFIX):
                    spans.bench.append((e.name[len(SPAN_PREFIX):], lo, hi))
    spans.bench.sort(key=lambda s: s[1])
    return spans


def reduce(spans: Spans, tasks_per_step: int) -> Optional[Dict[str, float]]:
    """The stage metrics over the traced steps, or ``None`` when the
    trace holds no traced step or no ``parsec:`` span."""
    traced = [(lo, hi) for name, lo, hi in spans.bench
              if name == WINDOW_SPAN]
    if not traced or not spans.threads or not tasks_per_step:
        return None
    window = union((lo, hi) for name, lo, hi in spans.bench
                   if name == STEP_SPAN and lo >= traced[0][0]
                   and hi <= traced[0][1])
    if not window:
        return None
    window_s, tasks = total(window), tasks_per_step * len(window)

    def inside(intervals):
        return intersect(union(intervals), window)

    seconds = dict.fromkeys((*BUSY, PARK), 0.0)
    running: List[Interval] = []         # any thread dispatching/releasing
    workers = dispatch_spans = 0
    for stages in spans.threads.values():
        clipped = {s: inside(iv) for s, iv in stages.items()}
        # a thread's exec spans lie inside its dispatch spans: dispatch
        # is what is left of them (data lookup, incarnation walk)
        if "dispatch" in clipped:
            clipped["dispatch"] = subtract(clipped["dispatch"],
                                           clipped.get("exec", []))
        for s, iv in clipped.items():
            if s in seconds:
                seconds[s] += total(iv)
        workers += "select" in stages or PARK in stages
        dispatch_spans += sum(
            1 for lo, _hi in stages.get("dispatch", [])
            if any(w0 <= lo < w1 for w0, w1 in window))
        running += stages.get("dispatch", []) + stages.get("release", [])
    inserting = inside((lo, hi) for name, lo, hi in spans.bench
                       if name == "insert")
    out = {f"{s}_us_per_task": 1e6 * seconds[s] / tasks for s in BUSY}
    out["exec_enqueue_us_per_task"] = out.pop("exec_us_per_task")
    out.update(
        host_threads_busy_mean=sum(seconds[s] for s in BUSY) / window_s,
        workers_parked_share=(100.0 * seconds[PARK] / (workers * window_s)
                              if workers else None),
        insert_overlap_share=(
            100.0 * total(intersect(inserting, union(running)))
            / total(inserting) if inserting else None),
        steps=len(window), window_s=window_s, workers=workers,
        dispatch_spans=dispatch_spans)
    return out


def find(checkout: str, cell: str) -> Optional[str]:
    """The trace ``run.py`` left for ``cell``, if this process wrote it."""
    files = glob.glob(os.path.join(checkout, ".benchmark_trace", cell,
                                   "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        return None
    path = max(files, key=os.path.getmtime)
    return path if os.path.getmtime(path) >= _process_started() else None


def _process_started() -> float:
    """Wall-clock second this process started (``/proc``: start time in
    clock ticks since boot); where there is no ``/proc``, no file is old."""
    try:
        with open("/proc/self/stat") as fh:
            ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        since_boot = ticks / os.sysconf("SC_CLK_TCK")
        return time.time() - (time.clock_gettime(time.CLOCK_BOOTTIME)
                              - since_boot) - 1.0     # a tick of slack
    except (OSError, ValueError, IndexError, AttributeError):
        return 0.0


@functools.lru_cache(maxsize=4)
def _stages(path: str, _mtime: float, tasks_per_step: int):
    out = reduce(load(path), tasks_per_step)
    if out is not None:
        print("[program_spans] " + " ".join(
            f"{k}={v}" for k, v in out.items()
            if k in ("steps", "window_s", "workers", "dispatch_spans")),
            flush=True)
    return out


def stages(checkout: str, cell: str, tasks_per_step: int
           ) -> Optional[Dict[str, float]]:
    """``reduce`` of ``cell``'s trace under ``checkout``; parsed once per
    file (the harness loads a reader anew for every metric)."""
    path = find(checkout, cell)
    if path is None:
        return None
    return _stages(path, os.path.getmtime(path), tasks_per_step)
