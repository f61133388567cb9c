"""Weighted-fair scheduler across live taskpools (``sched=wfq``).

The reference schedulers arbitrate between THREADS (steal orders); every
taskpool's tasks land in the same queues, so one tenant inserting faster
than another simply owns the runtime — the starvation mode ROADMAP item 4
names. This module arbitrates between TASKPOOLS: each live pool keeps its
own FIFO ring and the selector runs stride scheduling (Waldspurger-style)
over them — pool p is charged ``STRIDE1 / weight(p)`` virtual time per
selected task, and select() always picks the backlogged pool with the
least virtual time. Long-run service is proportional to
``Taskpool.fair_weight`` regardless of insertion rates, and a freshly
backlogged pool joins at the current virtual floor (start-time fairness:
it cannot retro-claim idle time and monopolize the streams).

Starvation is measurable, not anecdotal: per-pool counters (enqueued /
selected / pending / virtual pass, plus the last-selected wall clock) are
exported via :meth:`WFQScheduler.pool_stats` and surfaced by the
``tenant`` PINS module and ``ServingRuntime.report()``.

One global lock serializes the queue set. That is the right trade for the
serving shape this scheduler exists for — many concurrent tenants whose
task bodies dwarf the pop — and keeps selection O(live pools). The
per-thread schedulers (lfq & co) remain the default elsewhere.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, Optional, Sequence

from .base import Scheduler
from ..core.task import Task
from ..utils import mca_param

#: virtual-time quantum charged to a weight-1.0 pool per selected task
_STRIDE1 = 1 << 20

mca_param.register("serving.kv_prefill_interleave", 4,
                   help="wfq per-pool prefill-lane cadence: when a pool "
                        "has BOTH decode and prefill (priority < 0) "
                        "tasks queued, every Nth selection from that "
                        "pool serves the prefill lane — long chunked "
                        "prefills make progress without starving the "
                        "pool's decode p99 (0/1 = strict alternation, "
                        "no decode preference)")


def lane_choice(ndq: int, npq: int, nsel: int, interleave: int) -> str:
    """Pure per-pool lane-selection semantics of :meth:`select` —
    which lane ("decode" | "prefill") serves the pool's next slot,
    given the lane backlogs, the pool's selection counter AFTER its
    increment, and ``serving.kv_prefill_interleave``.

    Factored out so the protocol models (analysis/protomodels.py) check
    the EXACT function the scheduler runs: when both lanes are
    backlogged the prefill lane gets every Nth slot of the pool's
    service — long prompts make progress, decode keeps its p99 —
    and ``interleave<=1`` clamps to strict alternation ("no decode
    preference"), never starvation.
    """
    if not ndq:
        return "prefill"
    if not npq:
        return "decode"
    if nsel % max(interleave, 2) == 0:
        return "prefill"
    return "decode"


class _PoolQueue:
    __slots__ = ("dq", "pq", "nsel", "vpass", "enqueued", "selected",
                 "last_selected_t")

    def __init__(self, vfloor: float):
        self.dq = deque()            # default (decode) lane
        self.pq = deque()            # prefill lane: priority < 0 tasks
        self.nsel = 0                # per-pool selection cadence counter
        self.vpass = vfloor
        self.enqueued = 0
        self.selected = 0
        self.last_selected_t = 0.0

    def backlogged(self) -> bool:
        return bool(self.dq) or bool(self.pq)

    def __len__(self) -> int:
        return len(self.dq) + len(self.pq)


class WFQScheduler(Scheduler):
    """Weighted-fair (stride) selection across live taskpools."""

    name = "wfq"
    # weighted-fair arbitration must SEE every task to charge virtual
    # time and populate pool_stats — DTD pools under wfq therefore stay
    # on the instrumented Python path even when runtime.native_dtd is
    # on (the documented serving-side arm of the fallback rule)
    native_dtd_capable = False

    def install(self, context) -> None:
        super().install(context)
        self._lock = threading.Lock()
        self._queues: Dict[object, _PoolQueue] = {}   # taskpool -> queue
        # global virtual clock: the vpass the last selection served at.
        # Non-decreasing (select always takes the minimum pass), and it
        # PERSISTS across idle instants — a pool created or rejoining
        # after the queues momentarily drained joins HERE, not at 0,
        # which would let it monopolize selection until it caught up
        # with the long-lived pools' accumulated vpass.
        self._vclock = 0.0

    def flow_init(self, es) -> None:
        es.sched_obj = None          # no per-stream structure

    def _vfloor_locked(self) -> float:
        """Join point for pools becoming backlogged: the global virtual
        clock (see install) — never 0-reset by an idle instant."""
        return self._vclock

    def schedule(self, es, tasks: Sequence[Task], distance: int = 0) -> None:
        with self._lock:
            floor = self._vfloor_locked()
            for t in tasks:
                q = self._queues.get(t.taskpool)
                if q is None:
                    q = self._queues[t.taskpool] = _PoolQueue(floor)
                elif not q.backlogged():
                    # idle pool rejoining: forfeit accumulated lag so it
                    # cannot burst past active pools (start-time fairness)
                    q.vpass = max(q.vpass, floor)
                # prefill lane (ISSUE 15): chunked-prefill tasks insert
                # at priority < 0 — within the pool they yield to decode
                # tasks at the kv_prefill_interleave cadence
                (q.pq if getattr(t, "priority", 0) < 0
                 else q.dq).append(t)
                q.enqueued += 1

    def _drop_cancelled_locked(self, tp, q: _PoolQueue) -> None:
        n = len(q)
        q.dq.clear()
        q.pq.clear()
        del self._queues[tp]
        for _ in range(n):
            # idempotent-termination contract: the cancelled pool already
            # force-terminated; these decrements only drain its counters
            tp.addto_nb_tasks(-1)

    def select(self, es) -> Optional[Task]:
        # cached_get: select() runs once per task on every worker — a
        # full registry get (global lock + env resolve) here would be
        # a cross-worker serialization point
        interleave = int(mca_param.cached_get(
            "serving.kv_prefill_interleave", 4))
        with self._lock:
            # a persistent serving context sees thousands of pools over
            # its lifetime: drop the bookkeeping of finished ones here
            # (empty queue + terminated pool) or _queues grows forever
            done = [tp for tp, q in self._queues.items()
                    if not q.backlogged() and (tp.completed
                                               or tp.cancelled)]
            for tp in done:
                del self._queues[tp]
            while True:
                best_tp, best_q = None, None
                for tp, q in self._queues.items():
                    if not q.backlogged():
                        continue
                    if tp.cancelled:
                        self._drop_cancelled_locked(tp, q)
                        break        # dict mutated: rescan
                    if best_q is None or q.vpass < best_q.vpass:
                        best_tp, best_q = tp, q
                else:
                    if best_q is None:
                        return None
                    best_q.nsel += 1
                    lane = lane_choice(len(best_q.dq), len(best_q.pq),
                                       best_q.nsel, interleave)
                    task = (best_q.pq if lane == "prefill"
                            else best_q.dq).popleft()
                    if best_q.vpass > self._vclock:
                        self._vclock = best_q.vpass
                    w = max(float(getattr(best_tp, "fair_weight", 1.0)),
                            1e-6)
                    best_q.vpass += _STRIDE1 / w
                    best_q.selected += 1
                    best_q.last_selected_t = time.monotonic()
                    return task

    def pending_tasks(self) -> int:
        with self._lock:
            return sum(len(q) for q in self._queues.values())

    def pool_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-pool service accounting keyed by taskpool name — the
        starvation evidence (selected vs enqueued vs pending, and how
        stale the pool's last service is)."""
        now = time.monotonic()
        out: Dict[str, Dict[str, float]] = {}
        with self._lock:
            for tp, q in self._queues.items():
                key = tp.name
                if key in out:
                    # Taskpool names are not unique: suffix collisions
                    # so no pool's starvation row shadows another's
                    key = f"{tp.name}#{tp.taskpool_id}"
                out[key] = {
                    "tenant": getattr(tp, "tenant_name", None),
                    "weight": float(getattr(tp, "fair_weight", 1.0)),
                    "enqueued": q.enqueued,
                    "selected": q.selected,
                    "pending": len(q),
                    "prefill_pending": len(q.pq),
                    "vpass": q.vpass,
                    "since_selected_s": (
                        round(now - q.last_selected_t, 6)
                        if q.last_selected_t else None),
                }
        return out

    def remove(self, context) -> None:
        with self._lock:
            self._queues.clear()
