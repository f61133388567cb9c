"""One figure of ``benchmark/idle_by_stage.py`` over the traced steps;
``params["key"]`` says which: ``launch_call_us``,
``chip_wait_us_per_task``, ``turn_wait_share`` (the program's
``parsec:exec_call``, ``parsec:exec_wait`` and ``parsec:turn`` spans), or
one of the five ``idle_<class>_share`` (the chip's idle time inside the
traced steps by the host stage that left it idle, as a share of the step
time: ``launching``, ``releasing``, ``front_end``, ``completion``,
``unaccounted``, the first that applies, on a clock the trace itself
bounds). The definitions, the
order of precedence and the clock rule are that module's docstring.
``None`` where there is nothing to read: a run without a trace, a program
that opens no ``parsec:exec_call`` span, and for the ``idle_*`` shares a
trace without a TPU plane (a CPU rehearsal) or a chip that never idles."""

import os

from benchmark import idle_by_stage

# the trace lies in the tree this reader was loaded from
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(record, params):
    out = idle_by_stage.reduced(_CHECKOUT, record["cell"],
                                record["driver"]["tasks_per_step"])
    return None if out is None else out[params["key"]]
