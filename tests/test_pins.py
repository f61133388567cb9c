"""PINS module tests (reference mca/pins/): task_profiler, print_steals,
alperf, iterators_checker, and the ptg_to_dtd cross-check harness."""

import numpy as np
import pytest

import parsec_tpu as parsec
from parsec_tpu.dsl import ptg
from parsec_tpu.data import LocalCollection, TiledMatrix
from parsec_tpu.algorithms.potrf import build_potrf
from parsec_tpu.profiling import (Alperf, IteratorsChecker, PrintSteals,
                                  TaskProfiler, install_selected, new_module,
                                  replay_ptg_through_dtd)
from parsec_tpu.utils import mca_param
from conftest import spd_matrix


def _chain_tp(n, store):
    tp = ptg.Taskpool("chain", N=n, S=store)
    T = tp.task_class(
        "T", params=("i",),
        space=lambda g: ((i,) for i in range(g.N)),
        flows=[ptg.FlowSpec(
            "X", ptg.RW,
            tile=lambda g, i: (g.S, ("x",)),
            ins=[ptg.In(data=lambda g, i: (g.S, ("x",)),
                        guard=lambda g, i: i == 0),
                 ptg.In(src=("T", lambda g, i: (i - 1,), "X"),
                        guard=lambda g, i: i > 0)],
            outs=[ptg.Out(dst=("T", lambda g, i: (i + 1,), "X"),
                          guard=lambda g, i: i < g.N - 1),
                  ptg.Out(data=lambda g, i: (g.S, ("x",)),
                          guard=lambda g, i: i == g.N - 1)])])

    @T.body
    def body(task, x):
        return x + 1
    return tp


def test_alperf_counts_per_class(ctx):
    mod = Alperf().install(ctx)
    store = LocalCollection("S", {("x",): 0})
    tp = _chain_tp(15, store)
    ctx.add_taskpool(tp)
    assert ctx.wait(timeout=30)
    rep = mod.report()
    assert rep["T"]["count"] == 15
    assert rep["T"]["time_s"] >= 0.0
    mod.uninstall()


def test_counters_accumulates_rusage_deltas(ctx):
    """papi-analog counters module: per-class rusage/wall deltas at
    EXEC begin/end (pins_papi.c contract: sample, delta, aggregate)."""
    from parsec_tpu.profiling import Counters
    mod = Counters().install(ctx)
    store = LocalCollection("S", {("x",): 0})
    ctx.add_taskpool(_chain_tp(12, store))
    assert ctx.wait(timeout=30)
    rep = mod.report()
    assert rep["T"]["tasks"] == 12
    assert rep["T"]["wall_s"] >= 0.0
    for field in ("utime_s", "stime_s", "minflt", "majflt",
                  "nvcsw", "nivcsw"):
        assert field in rep["T"]
    mod.uninstall()


def test_task_profiler_traces_tasks(ctx):
    mod = TaskProfiler().install(ctx)
    store = LocalCollection("S", {("x",): 0})
    ctx.add_taskpool(_chain_tp(10, store))
    assert ctx.wait(timeout=30)
    counts = mod.report()
    assert counts.get("task:end", 0) == 10


def test_print_steals_reports_streams(ctx):
    mod = PrintSteals().install(ctx)
    store = LocalCollection("S", {("x",): 0})
    ctx.add_taskpool(_chain_tp(10, store))
    assert ctx.wait(timeout=30)
    rep = mod.report()
    assert set(rep) == {es.th_id for es in ctx.streams}
    for row in rep.values():
        assert row["stolen"] >= 0


def test_iterators_checker_clean_run(ctx):
    mod = IteratorsChecker().install(ctx)
    A_host = spd_matrix(np.random.default_rng(3), 64)
    A = TiledMatrix.from_array(A_host.copy(), 16, 16, name="A")
    ctx.add_taskpool(build_potrf(A))
    assert ctx.wait(timeout=60)
    assert mod.checked == mod.report()["tasks_checked"] > 0


def test_mca_selection_installs_modules():
    mca_param.set("pins", "alperf,print_steals")
    try:
        c = parsec.init(nb_cores=2)
        names = sorted(m.name for m in c.pins_modules)
        assert names == ["alperf", "print_steals"]
        parsec.fini(c)
    finally:
        mca_param.set("pins", "")


def test_new_module_rejects_unknown():
    with pytest.raises(ValueError):
        new_module("nonesuch")


def test_ptg_to_dtd_replay_chain(ctx):
    store = LocalCollection("S", {("x",): 0})
    tp = _chain_tp(12, store)
    replay_ptg_through_dtd(tp, ctx)
    assert store.data_of(("x",)) == 12


def test_ptg_to_dtd_replay_orders_war(ctx):
    """A reader and the tile's next writer are unordered in the PTG
    dataflow DAG (values travel with activations); the replay must insert
    the reader first or DTD serializes them backwards (WAR hazard)."""
    S = LocalCollection("S", {("x",): 0, ("r",): -1})
    tp = ptg.Taskpool("war", S=S)
    tp.task_class(
        "P", params=("i",), space=lambda g: ((0,),),
        flows=[ptg.FlowSpec(
            "X", ptg.RW,
            tile=lambda g, i: (g.S, ("x",)),
            ins=[ptg.In(data=lambda g, i: (g.S, ("x",)))],
            outs=[ptg.Out(dst=("R", lambda g, i: (0,), "X")),
                  ptg.Out(dst=("W", lambda g, i: (0,), "X"))])])
    tp.task_class(
        "R", params=("i",), space=lambda g: ((0,),),
        flows=[
            ptg.FlowSpec("X", ptg.READ,
                         tile=lambda g, i: (g.S, ("x",)),
                         ins=[ptg.In(src=("P", lambda g, i: (0,), "X"))]),
            ptg.FlowSpec("Rt", ptg.WRITE,
                         tile=lambda g, i: (g.S, ("r",)),
                         outs=[ptg.Out(data=lambda g, i: (g.S, ("r",)))])])
    tp.task_class(
        "W", params=("i",), space=lambda g: ((0,),),
        flows=[ptg.FlowSpec(
            "X", ptg.RW,
            tile=lambda g, i: (g.S, ("x",)),
            ins=[ptg.In(src=("P", lambda g, i: (0,), "X"))],
            outs=[ptg.Out(data=lambda g, i: (g.S, ("x",)))])])

    @tp.get_task_class("P").body
    def p_body(task, x):
        return 10

    @tp.get_task_class("R").body
    def r_body(task, x, rt):
        return x          # must observe P's value (10), never W's (20)

    @tp.get_task_class("W").body
    def w_body(task, x):
        return x * 2

    # topo_order must place R before W via the WAR edge
    from parsec_tpu.profiling.ptg_to_dtd import topo_order
    order = [f"{tc.name}{p}" for tc, p in topo_order(tp)]
    assert order.index("R(0,)") < order.index("W(0,)")

    replay_ptg_through_dtd(tp, ctx)
    assert S.data_of(("r",)) == 10
    assert S.data_of(("x",)) == 20


def test_ptg_to_dtd_replay_body_gets_locals(ctx):
    """Bodies that read task.locals (part of the hook contract) must work
    under replay via the _ReplayTask shim."""
    S = LocalCollection("S", {(i,): 0 for i in range(5)})
    tp = ptg.Taskpool("loc", S=S)
    T = tp.task_class(
        "T", params=("i",),
        space=lambda g: ((i,) for i in range(5)),
        flows=[ptg.FlowSpec(
            "X", ptg.RW,
            tile=lambda g, i: (g.S, (i,)),
            ins=[ptg.In(data=lambda g, i: (g.S, (i,)))],
            outs=[ptg.Out(data=lambda g, i: (g.S, (i,)))])])

    @T.body
    def body(task, x):
        return x + task.locals[0]

    replay_ptg_through_dtd(tp, ctx)
    for i in range(5):
        assert S.data_of((i,)) == i


def test_ptg_to_dtd_replay_potrf(ctx, rng):
    """The reference's headline cross-check: the same POTRF DAG through
    both front ends must produce the same factor."""
    A_host = spd_matrix(rng, 96)
    A_ptg = TiledMatrix.from_array(A_host.copy(), 24, 24, name="Ap")
    A_dtd = TiledMatrix.from_array(A_host.copy(), 24, 24, name="Ad")

    ctx.add_taskpool(build_potrf(A_ptg))
    assert ctx.wait(timeout=60)

    replay_ptg_through_dtd(build_potrf(A_dtd), ctx)

    np.testing.assert_allclose(A_ptg.to_array(), A_dtd.to_array(),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("how", ["another_thread", "group_launch"])
def test_counters_rusage_deltas_stay_on_one_thread(how):
    """A task completed from another thread (a device that returns
    ASYNC) must not mix per-thread rusage across threads: counted as
    async_tasks, wall time only. The members of a group launch begin
    and end on the worker that formed the group: none is async."""
    import threading

    from parsec_tpu.core.task import DeviceType, HookReturn
    from parsec_tpu.device.base import Device
    from parsec_tpu.profiling import Counters

    class AsyncDevice(Device):
        device_type = DeviceType.TPU
        name = "async-test"

        def execute(self, es, task, chore):
            def finish():
                task.output["X"] = chore.hook(task, *task.input_values())
                self.release_load()
                ctx.complete_task(None, task)

            threading.Thread(target=finish, daemon=True).start()
            return HookReturn.ASYNC

    mca_param.set("device.tpu.max_devices", 1)
    ctx = mod = None
    try:
        ctx = parsec.init(nb_cores=1)
        if how == "another_thread":
            ctx.devices.add(AsyncDevice()).weight = 1000.0   # wins selection
        mod = Counters().install(ctx)
        NT = 8
        store = LocalCollection(
            "S", {("x", i): np.full((8, 8), float(i), np.float32)
                  for i in range(NT)} | {("y", i): None
                                         for i in range(NT)})
        tp = ptg.Taskpool("wide", N=NT, S=store)
        W = tp.task_class(
            "W", params=("i",),
            space=lambda g: ((i,) for i in range(g.N)),
            flows=[ptg.FlowSpec(
                "X", ptg.RW,
                ins=[ptg.In(data=lambda g, i: (g.S, ("x", i)))],
                outs=[ptg.Out(data=lambda g, i: (g.S, ("y", i)))])])

        # a hand-batched form: the worker that selects four of these
        # ready tasks has the module issue them as one launch
        @W.body(batch_hook=lambda xs: xs * 3.0)
        def w_body(task, X):
            import jax.numpy as jnp
            return jnp.asarray(X) * 3.0

        ctx.add_taskpool(tp)        # all ready before the worker starts
        assert ctx.wait(timeout=120)
        for i in range(NT):
            assert float(store.data_of(("y", i))[0, 0]) == 3.0 * i
        rep = mod.report()["W"]
        assert rep["tasks"] == NT
        assert rep["wall_s"] > 0.0
        if how == "another_thread":
            # END fires on the completing thread: wall time, no
            # cross-thread rusage delta
            assert rep["async_tasks"] == NT, rep
        else:
            grouped = sum(d.stats["batched_tasks"] for d in
                          ctx.devices.by_type(DeviceType.TPU)
                          if "batched_tasks" in d.stats)
            assert grouped == NT and not rep.get("async_tasks"), rep
    finally:
        if mod is not None:
            mod.uninstall()
        if ctx is not None:
            parsec.fini(ctx)
        mca_param.unset("device.tpu.max_devices")
