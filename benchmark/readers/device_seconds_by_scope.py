"""Device seconds inside the traced steps of the operations under one
``jax.named_scope``, as a share of the device-busy seconds, in percent:
``params["scope"]`` names the scope (``parsec:lu_swap``).

A device trace keeps every operation's run as one event on the chip's
``XLA Ops`` line, and XLA keeps the name stack an operation was traced
under (``jit(parsec_SSSSM_x1)/parsec:lu_swap/gather``) in the event's
metadata, as the stat ``tf_op``. ``jax.profiler.ProfileData`` gives an
event's own stats and not its metadata's, so this reader walks the
``.xplane.pb`` file's wire format itself (``tsl/profiler/protobuf/
xplane.proto``: XSpace > XPlane > XLine > XEvent, an XPlane's
``event_metadata`` and ``stat_metadata`` maps), the chips' planes alone.
The seconds of a scope are the union of its operations' events, cut to
the ``bench:step`` spans inside ``bench:traced`` (``trace_reduce``'s
window), mean over chips. A fusion is named for one of the operations
XLA fused into it, so an operation fused with its neighbour counts wholly
to one side.

``None`` where there is nothing to read: a run without a trace, a trace
without a device plane (a CPU rehearsal), a program that opens no such
scope (no operation's name stack holds it).
"""

import functools
import os

from benchmark import program_spans
from benchmark.trace_reduce import (DEVICE_PLANE, STEP_SPAN, WINDOW_SPAN,
                                    intersect, total, union)

OPS_LINE = "XLA Ops"
NAME_STACK = "tf_op"

# the trace lies in the tree this reader was loaded from
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(view):
    key = value = None
    for no, v in fields(view):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def name_stacks(plane):
    """``{event metadata id: the operation's name stack}`` of one XPlane
    (a memoryview of it), and its name and its lines."""
    name, lines, stat_names, events = "", [], {}, []
    for no, v in fields(plane):
        if no == 2:
            name = _text(v)
        elif no == 3:
            lines.append(v)
        elif no == 5:           # stat_metadata: id -> XStatMetadata
            key, meta = _map_entry(v)
            for n2, v2 in fields(meta):
                if n2 == 2:
                    stat_names[key] = _text(v2)
        elif no == 4:           # event_metadata: id -> XEventMetadata
            events.append(_map_entry(v))
    wanted = {i for i, n in stat_names.items() if n == NAME_STACK}
    stacks = {}
    for key, meta in events:
        for no, v in fields(meta):
            if no != 5:         # XEventMetadata.stats
                continue
            stat = dict(fields(v))
            if stat.get(1) in wanted:
                # a string, or a reference to a stat metadata's name
                stacks[key] = _text(stat[5]) if 5 in stat else \
                    stat_names.get(stat.get(7), "")
    return name, lines, stacks


def scope_intervals(path, scope):
    """``{chip: [(start_s, end_s), ...]}`` of the ``XLA Ops`` events
    whose name stack holds ``scope``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    found = {}
    for no, plane in fields(space):
        if no != 1:
            continue
        name, lines, stacks = name_stacks(plane)
        m = DEVICE_PLANE.match(name)
        under = {i for i, s in stacks.items() if scope in s.split("/")}
        if not m or not under:
            continue
        for line in lines:
            msg = list(fields(line))
            if not any(no == 2 and _text(v) == OPS_LINE for no, v in msg):
                continue
            t0 = next((v for no, v in msg if no == 3), 0)
            iv = found.setdefault(int(m.group(1)), [])
            for no, v in msg:
                if no != 4:
                    continue
                ev = dict(fields(v))
                if ev.get(1) in under:
                    lo = t0 * 1e-9 + ev.get(2, 0) * 1e-12
                    iv.append((lo, lo + ev.get(3, 0) * 1e-12))
    return found


def seconds_in_steps(by_chip, bench):
    """Mean over the chips of the union of each chip's intervals inside
    the traced steps; ``None`` without a traced step or an interval."""
    traced = [(lo, hi) for name, lo, hi in bench if name == WINDOW_SPAN]
    if not traced or not by_chip:
        return None
    window = union((lo, hi) for name, lo, hi in bench
                   if name == STEP_SPAN and lo >= traced[0][0]
                   and hi <= traced[0][1])
    if not window:
        return None
    return sum(total(intersect(union(iv), window))
               for iv in by_chip.values()) / len(by_chip)


@functools.lru_cache(maxsize=4)
def _load(path, _mtime, scope):
    return seconds_in_steps(scope_intervals(path, scope),
                            program_spans.load(path).bench)


def read(record, params):
    path = program_spans.find(_CHECKOUT, record["cell"])
    busy = record["trace"].get("busy_s")
    if not path or not busy:
        return None
    seconds = _load(path, os.path.getmtime(path), params["scope"])
    return None if seconds is None else 100.0 * seconds / busy
