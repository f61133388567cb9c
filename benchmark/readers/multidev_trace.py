"""Device seconds a step of the multi-device cell, put together from the
device durations of its programs; ``params["key"]`` says which figure.

Why not ``device_step_s`` (busy seconds of the traced steps / steps): on
the four-chip host the profiler keeps the device trace of this cell for
the first 1.3 to 1.6 s only ("Trace Buffers Dropped ... to accommodate
proto serialization limit (2GB)": four chips' 4096-tile kernels write
about 0.8 GB of raw trace a busy second), which is less than ONE step,
while the host's spans cover two or three. Busy seconds over the steps
traced then read a half or a third of the truth, and a roofline share
made of them twice or three times it.

What a cut trace still gives without bias is how long one program of
each task class runs on the chip: the program-name rule
(``jit_parsec_<class>_x1`` on a chip's ``XLA Modules`` line), every
event of the class on every chip, whenever it ran. The graph says how
many tasks of each class a chip runs in a step
(``record["setup"]["multidev"]["tasks_by_chip_class"]``, from the
driver), so a chip's device seconds a step are the sum over the classes
of tasks x the class's mean duration:

``device_step_s``: that, mean over the chips.
``tile_roofline``: the least time for the algorithm's operations and
bytes, a quarter to a chip, at the published peaks / it, in percent.
``busy_max_over_min``: the busiest chip's such seconds / the least
busy chip's.

The copies between chips are no programs and are not in it. ``None``
where there is nothing to read: a run without a trace, a trace without a
device plane (a CPU rehearsal), no program named so, a class of the
graph without an event, a driver that leaves no counts.
"""

import functools
import os
import re

from benchmark import ops, program_spans
from benchmark.trace_reduce import DEVICE_PLANE

MODULES_LINE = "XLA Modules"
PROGRAM = re.compile(r"^jit_parsec_(\w+)_x1(?:\(|$)")

# the trace lies in the tree this reader was loaded from
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def seconds_by_chip(durations, tasks_by_chip_class):
    """``durations``: ``{class: [seconds of one program, ...]}``;
    ``tasks_by_chip_class``: ``{chip: {class: tasks a step}}``.
    ``{chip: device seconds a step}``, or ``None`` where a class that
    has tasks has no event."""
    mean = {cls: sum(d) / len(d) for cls, d in durations.items() if d}
    out = {}
    for chip, by_class in tasks_by_chip_class.items():
        if any(n and cls not in mean for cls, n in by_class.items()):
            return None
        out[chip] = sum(n * mean[cls] for cls, n in by_class.items() if n)
    return out or None


@functools.lru_cache(maxsize=4)
def _durations(path, _mtime):
    from jax.profiler import ProfileData
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for e in line.events:
                m = PROGRAM.match(e.name)
                if m:
                    found.setdefault(m.group(1), []).append(
                        e.duration_ns * 1e-9)
    return found


def read(record, params):
    counts = (record["setup"].get("multidev") or {}).get(
        "tasks_by_chip_class")
    path = program_spans.find(_CHECKOUT, record["cell"])
    if not counts or path is None:
        return None
    found = _durations(path, os.path.getmtime(path))
    by_chip = seconds_by_chip(found, counts) if found else None
    if by_chip is None:
        return None
    mean = sum(by_chip.values()) / len(by_chip)
    key = params["key"]
    if key == "device_step_s":
        print("[multidev_trace] " + " ".join(
            f"{cls}: programs={len(d)} mean_ms={1e3 * sum(d) / len(d):.3f}"
            for cls, d in sorted(found.items())) + " device_s_by_chip=" +
            ",".join(f"{s:.4f}" for _c, s in sorted(by_chip.items())),
            flush=True)
        return mean
    if key == "busy_max_over_min":
        return max(by_chip.values()) / min(by_chip.values())
    if key == "tile_roofline":
        peaks, chips = record["peaks"], record["chips"]
        if not peaks:
            return None
        least, _bound = ops.roofline_seconds(
            record["driver"]["ops_per_step"] / chips,
            record["driver"]["bytes_per_step"] / chips,
            peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
        return 100.0 * least / mean
    raise ValueError(f"key={key!r} is none of this reader's")
