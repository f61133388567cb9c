"""Device seconds inside the traced steps by the task class a launch's
program is named for, and two figures made of them.

The program names every program it launches on a chip for its task class
(``device/tpu.py``: ``jit_parsec_<class>_x<tasks>``), and a device trace
keeps a program's run as one event on the chip's ``XLA Modules`` line. So
the seconds of a class are the union of its programs' events, cut to the
``bench:step`` spans inside ``bench:traced`` (``trace_reduce``'s window),
mean over chips. By ``params``:

``{"roofline": cls}``: the class's kernel's share of its roofline in
percent: tasks of the class in the traced steps × the least time one
task's operations and bytes take at the published peaks ÷ the class's
device seconds. The driver leaves, in the dict its ``setup()`` returned,
``kernels``: ``{class: [tasks a step, operations a task, least bytes a
task]}``. The ``[kernels]`` line prints every class's figures.

``{"share": [cls, ...]}``: the classes' device seconds ÷ the device-busy
seconds of the traced steps, in percent.

``None`` where there is nothing to read: a run without a trace, a trace
without a device plane (a CPU rehearsal), a program that does not name
its programs so (no event matches), a driver that leaves no ``kernels``.
"""

import functools
import os
import re

from benchmark import ops, program_spans
from benchmark.trace_reduce import (DEVICE_PLANE, STEP_SPAN, WINDOW_SPAN,
                                    intersect, total, union)

MODULES_LINE = "XLA Modules"
PROGRAM = re.compile(r"^jit_parsec_(\w+)_x(\d+)(?:\(|$)")

# the trace lies in the tree this reader was loaded from
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def seconds_by_class(modules, bench):
    """``modules``: ``{chip: [(name, start, end), ...]}`` of the chips'
    ``XLA Modules`` lines; ``bench``: the harness's spans ``(name, start,
    end)``. ``({class: seconds, mean over chips}, steps traced)``, or
    ``None`` without a traced step or a named program."""
    traced = [(lo, hi) for name, lo, hi in bench if name == WINDOW_SPAN]
    if not traced or not modules:
        return None
    window = union((lo, hi) for name, lo, hi in bench
                   if name == STEP_SPAN and lo >= traced[0][0]
                   and hi <= traced[0][1])
    by_class = {}
    for events in modules.values():
        found = {}
        for name, lo, hi in events:
            m = PROGRAM.match(name)
            if m:
                found.setdefault(m.group(1), []).append((lo, hi))
        for cls, iv in found.items():
            inside = total(intersect(union(iv), window))
            if inside:
                by_class[cls] = by_class.get(cls, 0.0) + \
                    inside / len(modules)
    if not window or not by_class:
        return None
    return by_class, len(window)


@functools.lru_cache(maxsize=4)
def _load(path, _mtime):
    from jax.profiler import ProfileData
    modules = {}
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == MODULES_LINE:
                modules[int(m.group(1))] = [
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events]
    return seconds_by_class(modules, program_spans.load(path).bench)


def rooflines(seconds, steps, kernels, peaks):
    """``{class: percent}`` for the classes that ran and have counts."""
    out = {}
    for cls, (tasks, n_ops, n_bytes) in kernels.items():
        if seconds.get(cls):
            least, _bound = ops.roofline_seconds(
                n_ops, n_bytes, peaks["bf16_flops_per_s"],
                peaks["hbm_bytes_per_s"])
            out[cls] = 100.0 * tasks * steps * least / seconds[cls]
    return out


def read(record, params):
    path = program_spans.find(_CHECKOUT, record["cell"])
    found = _load(path, os.path.getmtime(path)) if path else None
    if found is None:
        return None
    seconds, steps = found
    if "share" in params:
        busy = record["trace"].get("busy_s")
        return 100.0 * sum(seconds.get(c, 0.0) for c in params["share"]) \
            / busy if busy else None
    kernels = record["setup"].get("kernels")
    if not kernels or not record["peaks"]:
        return None
    shares = rooflines(seconds, steps, kernels, record["peaks"])
    print("[kernels] " + " ".join(
        f"{cls}: tasks={kernels[cls][0] * steps if cls in kernels else '?'}"
        f" device_s={s:.6f} roofline_pct="
        f"{shares[cls] if cls in shares else float('nan'):.2f}"
        for cls, s in sorted(seconds.items())), flush=True)
    return shares.get(params["roofline"])
