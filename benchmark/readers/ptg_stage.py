"""The PTG cell's figures that no accepted reader gives, by ``params``:

``{"stage": key}``: a figure ``program_spans.reduce`` gives of the
runtime's stage spans over the traced steps (what ``program_stage`` reads
in the DTD cells), under the PTG cell's own name.

``{"span": name, "per": "task" | "step_time"}``: thread-seconds of
``parsec:<name>`` (the front end's ``ptg_startup`` and ``ptg_unfold``)
inside the traced steps, in microseconds per task of those steps or as a
share of the traced step time in percent. The window is the one
``program_spans.reduce`` uses: the ``bench:step`` spans inside
``bench:traced``.

``{"over": [...], "under": [...]}``: 100 x the sum of the window's
program counters named ``over`` / the sum of those named ``under`` (a
name that ends in ``*`` takes every counter that starts so). The driver
leaves them, first reading taken from the last, in the dict its
``setup()`` returned as ``program_counters``: ``record["setup"]`` holds
that dict.

``None`` where there is nothing to read: a run without a trace, a program
that opens no such span or counts no such thing (a trace without the span
reads ``None``, not 0), a divisor of 0.
"""

import os

from benchmark import program_spans
from benchmark.trace_reduce import (STEP_SPAN, WINDOW_SPAN, intersect,
                                    total, union)

# the trace lies in the tree this reader was loaded from
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def span_seconds(spans, span, per, tasks_per_step):
    """``spans``: a ``program_spans.Spans``."""
    traced = [(lo, hi) for name, lo, hi in spans.bench
              if name == WINDOW_SPAN]
    if not traced:
        return None
    window = union((lo, hi) for name, lo, hi in spans.bench
                   if name == STEP_SPAN and lo >= traced[0][0]
                   and hi <= traced[0][1])
    found = [stages[span] for stages in spans.threads.values()
             if span in stages]
    if not window or not found:
        return None
    seconds = sum(total(intersect(union(iv), window)) for iv in found)
    if per == "step_time":
        return 100.0 * seconds / total(window)
    if per == "task":
        tasks = tasks_per_step * len(window)
        return 1e6 * seconds / tasks if tasks else None
    raise ValueError(f"per={per!r} is neither 'task' nor 'step_time'")


def counter_share(counts, over, under):
    """``counts``: ``{name: n}`` over the window, or ``None``."""
    def summed(names):
        return sum(n for key, n in counts.items() for name in names
                   if key == name or
                   (name.endswith("*") and key.startswith(name[:-1])))
    if not counts or not summed(under):
        return None
    return 100.0 * summed(over) / summed(under)


def read(record, params):
    if "over" in params:
        return counter_share(record["setup"].get("program_counters"),
                             params["over"], params["under"])
    tasks = record["driver"]["tasks_per_step"]
    if "stage" in params:
        stages = program_spans.stages(_CHECKOUT, record["cell"], tasks)
        return None if stages is None else stages[params["stage"]]
    path = program_spans.find(_CHECKOUT, record["cell"])
    if path is None:
        return None
    return span_seconds(program_spans.load(path), params["span"],
                        params["per"], tasks)
