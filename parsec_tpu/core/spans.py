"""Stage spans: the runtime's stage-timer sites as a profiler trace shows
them. A module below ``core/context.py``, the DSLs and the device modules,
which all open them (``core.context`` re-exports every name)."""

from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

# the runtime's stages as they appear in a profiler trace: one span per
# stage-timer site, constant names (benchmark/program_spans.py and an
# operator's TensorBoard read them beside the device's operations)
SPAN_INSERT = "parsec:insert"
SPAN_SELECT = "parsec:select"
SPAN_PARK = "parsec:park"
SPAN_DISPATCH = "parsec:dispatch"
SPAN_EXEC = "parsec:exec"
SPAN_RELEASE = "parsec:release"
# a worker that holds a ready task and waits for its module's one group
# in flight (Context._take_turn: the acquisition alone, outside
# every other span), and a launch taken apart (device/tpu.py, both inside
# the thread's parsec:exec): the host waiting for the chip (the last
# group's output; the oldest lone launch once GROUP_BYTES are queued),
# and the jitted call until it returns. What is left of exec is staging
# the leaves and attaching the outputs
SPAN_TURN = "parsec:turn"
SPAN_EXEC_WAIT = "parsec:exec_wait"
SPAN_EXEC_CALL = "parsec:exec_call"
# the making of this chip's copy of a tile that lies on another chip
# (device/tpu.py ``_copy_here``, inside the thread's parsec:exec too): a
# span a copy made, none where a copy already here serves the read
SPAN_STAGE_IN = "parsec:stage_in"
# the PTG front end's own stages (dsl/ptg.py names them on its taskpool
# and task classes; a front end that names none has none)
SPAN_PTG_STARTUP = "parsec:ptg_startup"
SPAN_PTG_UNFOLD = "parsec:ptg_unfold"
# the DTD front end's flushes (dsl/dtd.py): one span a call of flush,
# flush_tile or flush_all, what the inserter's thread pays for it
SPAN_DTD_FLUSH = "parsec:dtd_flush"


class StageSpan:
    """One pass through a stage-timer site, opened only where
    ``context.stage_timers`` is on: a ``TraceAnnotation`` (a span on the
    profiler's own clock, beside the ``/device:TPU:n`` planes, when a
    session is live; next to nothing when none is) and the seconds it
    took, which the site adds to its ``es.stats`` / ``insert_s`` sum."""

    __slots__ = ("_ann", "_t0", "seconds")

    def __init__(self, name: str):
        self._ann = TraceAnnotation(name)

    def __enter__(self) -> "StageSpan":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
