"""One figure of the runtime's own stage spans (``parsec:*``) over the
traced steps; ``params["key"]`` says which (``program_spans.reduce``).
``None`` where the trace holds none: a cell that bypasses the host
runtime, a program that opens no such span, a run without a trace."""

import os

from benchmark import program_spans

# the trace lies in the tree this reader was loaded from
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(record, params):
    stages = program_spans.stages(_CHECKOUT, record["cell"],
                                  record["driver"]["tasks_per_step"])
    return None if stages is None else stages[params["key"]]
