"""Batched dependency release + bypass-slot chaining
(runtime.bypass_chain): the host-runtime critical-path rework.

Covers the PR-3 tentpole contracts:
- `_PendingDeps.update_batch` is semantically identical to per-dep
  `update` (counter and mask modes, value accumulation, priority max,
  duplicate-bit detection) while taking each stripe lock once;
- `Taskpool.activate_deps` returns exactly the successors whose goal
  completes, with merged input values;
- `complete_task` bypass chaining is deterministic: the FIRST maximal-
  priority successor takes the stream's bypass slot, everything else
  reaches the scheduler (and nothing is lost with the knob off);
- no lost wakeups: a concurrent DTD stress (chains + wide fan-out,
  two schedulers and both engines) always drains.
"""

import threading

import pytest

import parsec_tpu as parsec
from parsec_tpu.core.task import DeviceType, Flow, FlowAccess
from parsec_tpu.core.taskpool import (DEPS_COUNTER, DEPS_MASK, SuccessorRef,
                                      Taskpool, TaskClass, _PendingDeps)
from parsec_tpu.data import LocalCollection
from parsec_tpu import dtd
from parsec_tpu.utils import mca_param


def _python_pending():
    """A _PendingDeps forced onto the pure-Python striped-lock path (the
    native table has its own per-key synchronization)."""
    mca_param.set("runtime.native_deps", False)
    try:
        return _PendingDeps()
    finally:
        mca_param.unset("runtime.native_deps")


def test_update_batch_counter_mode_matches_serial():
    pd = _python_pending()
    # two deps of task A (goal 2), one of task B (goal 2, stays pending)
    items = [("A", "x", 11, 0, 2, DEPS_COUNTER, 1),
             ("B", "x", 22, 0, 2, DEPS_COUNTER, 0),
             ("A", "y", 33, 1, 2, DEPS_COUNTER, 5)]
    done = pd.update_batch(items)
    assert len(done) == 1
    i, ent = done[0]
    assert i == 2                       # the dep that reached the goal
    assert ent["data"] == {"x": 11, "y": 33}
    assert ent["priority"] == 5         # max over contributing deps
    assert len(pd) == 1                 # B still parked
    # B's second dep via the serial path completes it identically
    ent_b = pd.update("B", "y", 44, 1, 2, DEPS_COUNTER, 3)
    assert ent_b is not None and ent_b["data"] == {"x": 22, "y": 44}
    assert len(pd) == 0


def test_update_batch_mask_mode_and_duplicate_bit():
    pd = _python_pending()
    goal = 0b11
    done = pd.update_batch([("K", "a", 1, 0, goal, DEPS_MASK, 0),
                            ("K", "b", 2, 1, goal, DEPS_MASK, 0)])
    assert [i for i, _ in done] == [1]
    pd.update_batch([("K", "a", 1, 0, goal, DEPS_MASK, 0)])
    with pytest.raises(RuntimeError, match="satisfied twice"):
        pd.update_batch([("K", "a", 9, 0, goal, DEPS_MASK, 0)])


def test_activate_deps_returns_completed_successors():
    mca_param.set("runtime.native_deps", False)
    try:
        tp = Taskpool("t")
        tc = tp.new_task_class("S", params=("i",),
                               flows=[Flow("x", FlowAccess.READ),
                                      Flow("y", FlowAccess.READ)])
        tc.deps_goal = lambda locals: 2
        refs = [SuccessorRef(tc, (0,), "x", value=10, dep_index=0),
                SuccessorRef(tc, (1,), "x", value=20, dep_index=0),
                SuccessorRef(tc, (0,), "y", value=30, dep_index=1,
                             priority=7)]
        ready = tp.activate_deps(refs)
        assert len(ready) == 1
        (task,) = ready
        assert task.locals == (0,)
        assert task.data == {"x": 10, "y": 30}
        assert task.priority == 7
        # successor (1,) completes later through the single-ref path
        ready = tp.activate_deps([SuccessorRef(tc, (1,), "y", value=40,
                                               dep_index=1)])
        assert len(ready) == 1 and ready[0].data == {"x": 20, "y": 40}
    finally:
        mca_param.unset("runtime.native_deps")


def _bypass_fixture(nb_cores=2):
    """A context whose workers are parked (never started) plus a
    producer task whose class fans out to prio-tagged successors —
    complete_task can then be driven synchronously from the test
    thread."""
    ctx = parsec.init(nb_cores=nb_cores)
    tp = Taskpool("byp")
    prod_tc = tp.new_task_class("PROD", params=(), flows=[])
    succ_tc = tp.new_task_class("SUCC", params=("i",),
                                flows=[Flow("x", FlowAccess.READ)])
    succ_tc.deps_goal = lambda locals: 1
    # priorities 3, 9, 9, 1 — the bypass slot must take the FIRST 9
    prios = {0: 3, 1: 9, 2: 9, 3: 1}
    prod_tc.iterate_successors = lambda task: [
        SuccessorRef(succ_tc, (i,), "x", value=i, dep_index=0,
                     priority=prios[i]) for i in range(4)]
    # hold a runtime action so the empty pool doesn't terminate before
    # the test feeds it tasks (the DTD pattern)
    tp.on_enqueue = lambda tp_: tp_.addto_runtime_actions(1)
    ctx.add_taskpool(tp)
    from parsec_tpu.core.task import Task
    prod = Task(tp, prod_tc, ())
    tp.addto_nb_tasks(1 + 4)    # producer + the successors it releases
    return ctx, tp, prod


def test_bypass_chain_takes_first_maximal_successor():
    ctx, tp, prod = _bypass_fixture()
    try:
        assert ctx._bypass_chain
        es = ctx.streams[0]
        ctx.complete_task(es, prod)
        assert es.next_task is not None
        assert es.next_task.priority == 9
        assert es.next_task.locals == (1,)      # first of the two 9s
        assert ctx.scheduler.pending_tasks() == 3
    finally:
        parsec.fini(ctx)


def test_bypass_chain_off_queues_everything():
    mca_param.set("runtime.bypass_chain", 0)
    try:
        ctx, tp, prod = _bypass_fixture()
    finally:
        mca_param.unset("runtime.bypass_chain")
    try:
        assert not ctx._bypass_chain
        es = ctx.streams[0]
        ctx.complete_task(es, prod)
        assert es.next_task is None
        assert ctx.scheduler.pending_tasks() == 4
    finally:
        parsec.fini(ctx)


def test_batched_release_matches_a_plain_reference():
    """What one batched pass over the producer's successors releases,
    against a plain numpy reading of the same fan-out: every successor
    once, with its value and priority, the first maximal one in the
    bypass slot and the rest in the scheduler."""
    import numpy as np
    prios = np.array([3, 9, 9, 1])      # _bypass_fixture's fan-out
    ctx, tp, prod = _bypass_fixture()
    try:
        es = ctx.streams[0]
        ctx.complete_task(es, prod)
        first = int(np.argmax(prios))   # numpy's argmax is the first
        assert es.next_task.locals == (first,)
        queued = []
        while ctx.scheduler.pending_tasks():
            queued.append(ctx.scheduler.select(es))
        released = {t.locals[0]: (t.data["x"], t.priority)
                    for t in [es.next_task, *queued]}
        assert len(queued) == len(prios) - 1
        assert released == {i: (i, int(p)) for i, p in enumerate(prios)}
    finally:
        parsec.fini(ctx)


def test_steal_order_cached_without_self():
    ctx = parsec.init(nb_cores=4, scheduler="lfq")
    try:
        es = sorted(ctx.streams, key=lambda e: e.th_id)[1]
        assert ctx.scheduler.select(es) is None     # populates the cache
        order = es._steal_order
        assert order is not None and es not in order
        assert len(order) == 3
    finally:
        parsec.fini(ctx)


def _count_body(x):
    return x + 1


def _null_body():
    return None


@pytest.mark.parametrize("scheduler,native", [
    ("lfq", 0), ("gd", 0), ("lfq", 1)])
def test_no_lost_wakeups_concurrent_complete(scheduler, native):
    """Chains (serial last-writer links) + wide fan-out draining through
    4 workers: every completion releases successors concurrently with
    further insertion. A lost wakeup or a dropped activation hangs
    wait() / loses a chain increment. The native=1 arm drives the same
    shape through the runtime.native_dtd engine (ISSUE 10): chain links
    become native successor edges, the fan-out drains through the
    per-worker plifo queues + steal."""
    from parsec_tpu import _native
    if native and not _native.available():
        pytest.skip("native core unavailable")
    mca_param.set("runtime.native_dtd", native)
    try:
        ctx = parsec.init(nb_cores=4, scheduler=scheduler)
        ctx.start()
        n_chain, n_fan = 60, 400
        S = LocalCollection("S", {("c", j): 0 for j in range(4)})
        tp = dtd.Taskpool("wakeups")
        ctx.add_taskpool(tp)
        # 4 interleaved serial chains through tile last-writer links
        for i in range(n_chain):
            tp.insert_tasks(
                _count_body,
                [(dtd.TileArg(S, ("c", j), dtd.INOUT),)
                 for j in range(4)],
                device=DeviceType.CPU)
        # wide independent fan-out, batch-inserted
        tp.insert_tasks(_null_body, [() for _ in range(n_fan)],
                        device=DeviceType.CPU)
        tp.wait()
        assert all(S.data_of(("c", j)) == n_chain for j in range(4))
        assert (tp._native is not None) == bool(native)
        parsec.fini(ctx)
    finally:
        mca_param.unset("runtime.native_dtd")
