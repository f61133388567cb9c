"""Tasks of the traced steps over the launches that carried them: the
``parsec:exec`` spans (one per launch on a device module, whatever the
number of tasks in it) that start inside a traced ``bench:step``. 1.0
where every task is launched alone. ``None`` where the trace holds no
such span: a cell that bypasses the host runtime, a program that opens
none, a run without a trace."""

import functools
import os

from benchmark import program_spans
from benchmark.trace_reduce import STEP_SPAN, WINDOW_SPAN

# the trace lies in the tree this reader was loaded from
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def reduce(spans, tasks_per_step):
    """``spans``: a ``program_spans.Spans``. The window is the one
    ``program_spans.reduce`` uses: the steps inside the first
    ``bench:traced`` span."""
    traced = [(lo, hi) for name, lo, hi in spans.bench
              if name == WINDOW_SPAN]
    if not traced or not tasks_per_step:
        return None
    steps = [(lo, hi) for name, lo, hi in spans.bench
             if name == STEP_SPAN and lo >= traced[0][0]
             and hi <= traced[0][1]]
    launches = sum(
        1 for stages in spans.threads.values()
        for lo, _hi in stages.get("exec", ())
        if any(s0 <= lo < s1 for s0, s1 in steps))
    return tasks_per_step * len(steps) / launches if launches else None


@functools.lru_cache(maxsize=4)
def _reduced(path, _mtime, tasks_per_step):
    return reduce(program_spans.load(path), tasks_per_step)


def read(record, params):
    path = program_spans.find(_CHECKOUT, record["cell"])
    if path is None:
        return None
    return _reduced(path, os.path.getmtime(path),
                    record["driver"]["tasks_per_step"])
