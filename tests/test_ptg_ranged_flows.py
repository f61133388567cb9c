"""Ranged data flows of the PTG front end (dsl/ptg.py): a flow whose value
is an ordered LIST of tiles, gathered from a list of producers or of
collection tiles (``In(gather=True)`` on a data flow) and handed out
element by element (``Out(scatter=True)``). One activation an element, one
schedule when the consumer's count is met; the position of an element in
the consumer's list is its producer's place in the consumer's own
``params_fn`` list, never the order of arrival."""

import types

import pytest

import parsec_tpu as parsec
from parsec_tpu.core.task import Task
from parsec_tpu.core.taskpool import DEPS_COUNTER, DataRef, SuccessorRef
from parsec_tpu.data import LocalCollection
from parsec_tpu.dsl import ptg
from parsec_tpu.utils import mca_param

N = 5


def _store(n=N):
    return LocalCollection("S", {("in", i): 10 * i for i in range(n)})


def _pool(store, n=N, short=False, guard_tail=True):
    """SRC(0) gathers n collection tiles and scatters element i to MID(i)
    (a plain flow); SINK(0) gathers the MIDs' values in REVERSED order,
    writes element 0 back and scatters the rest to TAIL(0), which gathers
    them from that ONE producer (named once an element) and writes its
    list back tile by tile."""
    tp = ptg.Taskpool("ranged", N=n, S=store)
    tp.task_class(
        "SRC", params=("k",), space=lambda g: ((0,),),
        flows=[ptg.FlowSpec(
            "X", ptg.RW,
            tile=lambda g, k: [(g.S, ("in", i)) for i in range(g.N)],
            ins=[ptg.In(data=lambda g, k: [(g.S, ("in", i))
                                           for i in range(g.N)],
                        gather=True)],
            outs=[ptg.Out(dst=("MID", lambda g, k: [(i,) for i in
                                                    range(g.N)], "V"),
                          scatter=True)])])
    tp.task_class(
        "MID", params=("i",), space=lambda g: ((i,) for i in range(g.N)),
        flows=[ptg.FlowSpec(
            "V", ptg.RW,
            ins=[ptg.In(src=("SRC", lambda g, i: (0,), "X"))],
            outs=[ptg.Out(dst=("SINK", lambda g, i: (0,), "L"))])])
    tp.task_class(
        "SINK", params=("k",), space=lambda g: ((0,),),
        flows=[ptg.FlowSpec(
            "L", ptg.RW,
            ins=[ptg.In(src=("MID", lambda g, k: [(g.N - 1 - i,) for i in
                                                  range(g.N)], "V"),
                        gather=True)],
            outs=[ptg.Out(data=lambda g, k: [(g.S, ("first",))] +
                          [None] * (g.N - 1), scatter=True),
                  ptg.Out(dst=("TAIL", lambda g, k: [[]] + [[(0,)]] *
                               (g.N - 1), "L"),
                          scatter=True,
                          guard=lambda g, k: guard_tail)])])
    tp.task_class(
        "TAIL", params=("k",),
        space=lambda g: ((0,),) if guard_tail else (),
        flows=[ptg.FlowSpec(
            "L", ptg.RW,
            ins=[ptg.In(src=("SINK", lambda g, k: [(0,)] * (g.N - 1), "L"),
                        gather=True)],
            outs=[ptg.Out(data=lambda g, k: [(g.S, ("tail", i)) for i in
                                             range(g.N - 1)],
                          scatter=True)])])

    @tp.get_task_class("SRC").body_cpu
    def src(task, X):
        assert isinstance(X, list)
        return {"X": [x + 1 for x in X][:len(X) - short]}

    @tp.get_task_class("MID").body_cpu
    def mid(task, V):
        return 2 * V

    @tp.get_task_class("SINK").body_cpu
    def sink(task, L):
        return {"L": list(L)}

    @tp.get_task_class("TAIL").body_cpu
    def tail(task, L):
        return {"L": [v + 1000 for v in L]}

    return tp


def _refs(tp, name, locals_, **output):
    tc = tp.get_task_class(name)
    task = Task(tp, tc, locals_)
    task.output.update(output)
    return [r for r in tc.iterate_successors(task)]


# -- declarations ----------------------------------------------------------

def test_a_class_with_a_gathered_data_flow_counts():
    tp = _pool(_store())
    src, mid, sink, tail = (tp.get_task_class(n)
                            for n in ("SRC", "MID", "SINK", "TAIL"))
    assert src.ranged and sink.ranged and tail.ranged and not mid.ranged
    assert [tc.deps_mode for tc in (src, sink, tail)] == [DEPS_COUNTER] * 3
    assert src.deps_goal((0,)) == 0          # collection tiles: no deps
    assert mid.deps_goal((2,)) == 1 << 0     # a plain flow: mask mode
    assert sink.deps_goal((0,)) == N         # one activation an element
    assert tail.deps_goal((0,)) == N - 1     # a producer named 4 times
    assert ptg.taskpool_has_ranged_flows(tp)


def test_what_a_ranged_flow_cannot_be_declared_with():
    from parsec_tpu.core.reshape import UPPER_TILE, ReshapeSpec
    tp = ptg.Taskpool("t", S=_store())
    one = lambda g, k: (k,)                                     # noqa: E731

    def cls(**spec):
        return tp.task_class("X", params=("k",), space=lambda g: [(0,)],
                             flows=[ptg.FlowSpec("T", **spec)])
    with pytest.raises(ValueError, match="write-back"):
        cls(access=ptg.RW, outs=[ptg.Out(dst=("X", one, "T"),
                                         region=UPPER_TILE)])
    with pytest.raises(ValueError, match="every one of its ins"):
        cls(access=ptg.RW, ins=[
            ptg.In(src=("X", one, "T"), gather=True,
                   guard=lambda g, k: k > 0),
            ptg.In(data=lambda g, k: (g.S, ("in", 0)),
                   guard=lambda g, k: k == 0)])
    with pytest.raises(ValueError, match="no reshape"):
        cls(access=ptg.RW, outs=[ptg.Out(
            dst=("X", one, "T"), scatter=True,
            reshape=ReshapeSpec(dtype="float32"))])
    with pytest.raises(ValueError, match="nothing to\\s+scatter"):
        cls(access=ptg.CTL, outs=[ptg.Out(dst=("X", one, "T"),
                                          scatter=True)])
    with pytest.raises(ValueError, match="gather requires a src"):
        cls(access=ptg.CTL, ins=[ptg.In(data=lambda g, k: [], gather=True)])
    with pytest.raises(ValueError, match="src producer list or a data"):
        cls(access=ptg.RW, ins=[ptg.In(new=lambda g, k: 0, gather=True)])


# -- the rule, without a context: any order of arrival --------------------

@pytest.mark.parametrize("order", ["forward", "reversed", "shuffled"])
def test_a_gathered_list_keeps_the_consumers_order(order):
    tp = _pool(_store())
    sink = tp.get_task_class("SINK")
    mids = list(range(N))
    if order == "reversed":
        mids.reverse()
    elif order == "shuffled":
        mids = [3, 0, 4, 1, 2]
    ready = []
    for n, i in enumerate(mids):
        (ref,) = _refs(tp, "MID", (i,), V=100 + i)
        # the element travels beside the activation, at its place
        assert ref.value is None and ref.element is None
        assert ref.task_class is sink and ref.flow_name == "L"
        got = tp.activate_deps([ref])
        assert bool(got) == (n == N - 1)     # ONE schedule, at the count
        ready += got
    (task,) = ready
    sink.data_lookup(task)
    # SINK's own list names MID(N-1) first
    assert task.data["L"] == [100 + (N - 1 - i) for i in range(N)]
    assert not sink._parts                   # handed over, not kept


def test_a_scatter_sends_element_i_to_consumer_i():
    tp = _pool(_store())
    refs = _refs(tp, "SRC", (0,), X=["a", "b", "c", "d", "e"])
    assert [(r.task_class.name, r.locals, r.flow_name, r.value, r.element)
            for r in refs] == [("MID", (i,), "V", "abcde"[i], i)
                               for i in range(N)]
    # element 0 to its tile, the rest to ONE consumer that gathers them
    refs = _refs(tp, "SINK", (0,), L=[7, 8, 9, 10, 11])
    (back,) = [r for r in refs if isinstance(r, DataRef)]
    assert (back.key, back.value) == (("first",), 7)
    acts = [r for r in refs if isinstance(r, SuccessorRef)]
    assert [(r.locals, r.element, r.value) for r in acts] == \
        [((0,), i, None) for i in range(1, N)]
    tail = tp.get_task_class("TAIL")
    (task,) = tp.activate_deps(acts)
    tail.data_lookup(task)
    assert task.data["L"] == [8, 9, 10, 11]  # in the order scattered


def test_a_guard_that_is_off_scatters_nothing():
    tp = _pool(_store(), guard_tail=False)
    refs = _refs(tp, "SINK", (0,), L=[7, 8, 9, 10, 11])
    assert [type(r) for r in refs] == [DataRef]
    ptg.check_taskpool(tp)


def test_a_ranged_dst_without_scatter_still_broadcasts_one_value():
    tp = ptg.Taskpool("b", N=3)
    tp.task_class("A", params=("k",), space=lambda g: ((0,),), flows=[
        ptg.FlowSpec("T", ptg.WRITE, outs=[ptg.Out(
            dst=("B", lambda g, k: [(i,) for i in range(g.N)], "T"))])])
    tp.task_class("B", params=("i",), space=lambda g: ((i,) for i in
                                                       range(g.N)),
                  flows=[ptg.FlowSpec("T", ptg.READ, ins=[ptg.In(
                      src=("A", lambda g, i: (0,), "T"))])])
    refs = _refs(tp, "A", (0,), T=[1, 2, 3])
    assert [r.value for r in refs] == [[1, 2, 3]] * 3
    assert not ptg.taskpool_has_ranged_flows(tp)


def test_a_length_mismatch_raises_at_the_task_by_name():
    tp = _pool(_store())
    with pytest.raises(ValueError, match=r"SRC\(0\): flow X holds 4 "
                       r"values and is scattered over 5 MID targets"):
        _refs(tp, "SRC", (0,), X=[1, 2, 3, 4])
    with pytest.raises(ValueError, match=r"SINK\(0\): flow L holds no "
                       r"list of values and is scattered over 5 tiles"):
        _refs(tp, "SINK", (0,), L=3.0)


def test_an_element_nobody_named_raises():
    tp = _pool(_store())
    tail = tp.get_task_class("TAIL")
    sink = Task(tp, tp.get_task_class("SINK"), (0,))
    for nth in range(N - 1):
        tail._put("L", (0,), sink, nth, nth)
    with pytest.raises(RuntimeError, match=r"does not name SINK\(0\) 5"):
        tail._put("L", (0,), sink, N - 1, 0)
    with pytest.raises(RuntimeError, match="gathers no list"):
        tp.get_task_class("MID")._part("V", (0,))


# -- both views of every edge ---------------------------------------------

def test_check_taskpool_on_a_ranged_pool():
    tp = _pool(_store())
    ptg.check_taskpool(tp)
    assert not any(tc._parts for tc in tp.task_classes)
    # a consumer that names a producer once too few
    bad = _pool(_store())
    spec = bad.get_task_class("TAIL").specs["L"]
    spec.ins[0].src = ("SINK", lambda g, k: [(0,)] * (g.N - 2), "L")
    with pytest.raises(RuntimeError, match=r"TAIL.*does not name SINK"):
        ptg.check_taskpool(bad)
    # and one that names a producer that sends it nothing
    bad = _pool(_store())
    spec = bad.get_task_class("TAIL").specs["L"]
    spec.ins[0].src = ("SINK", lambda g, k: [(0,)] * g.N, "L")
    with pytest.raises(AssertionError, match="TAIL"):
        ptg.check_taskpool(bad)


def test_the_static_lint_says_it_does_not_model_such_a_pool():
    report = _pool(_store()).validate(mode="error")
    assert report.ok and report.model is None
    assert [f.rule for f in report.findings] == ["ranged"]
    assert set(report.skipped_classes) == {"SRC", "MID", "SINK", "TAIL"}


def test_both_compiled_executors_refuse_it_and_say_why():
    from parsec_tpu.compiled.panels import PanelExecutor
    from parsec_tpu.compiled.wavefront import plan_taskpool
    tp = _pool(_store())
    with pytest.raises(ValueError, match="ranged data flow.*host runtime"):
        plan_taskpool(tp)
    with pytest.raises(ValueError, match="ranged data flow.*host runtime"):
        PanelExecutor(types.SimpleNamespace(taskpool=tp))


# -- through a Context ------------------------------------------------------

def test_the_pool_runs_through_the_scheduler(ctx):
    store = _store()
    tp = _pool(store)
    ctx.add_taskpool(tp)
    assert tp.wait_completed(60)
    mids = [2 * (10 * i + 1) for i in range(N)]
    assert store.data_of(("first",)) == mids[N - 1]
    assert [store.data_of(("tail", i)) for i in range(N - 1)] == \
        [1000 + mids[N - 2 - i] for i in range(N - 1)]
    # SRC's 5 elements and SINK's 4: one activation each
    assert sum(es.stats["ranged_scatters"] for es in ctx.streams) == \
        N + N - 1
    assert not any(tc._parts for tc in tp.task_classes)


def test_a_body_that_returns_a_short_list_fails_the_pool_by_name(ctx):
    tp = _pool(_store(), short=True)
    ctx.add_taskpool(tp)
    with pytest.raises(RuntimeError, match=r"SRC\(0\): flow X holds 4"):
        tp.wait_completed(60)


def test_the_written_tile_is_the_lists_first_and_places_the_task():
    mca_param.set("device.tpu.max_devices", 2)
    ctx = parsec.init(nb_cores=2)
    try:
        ctx.start()
        chips = ctx.devices.chips
        assert len(chips) == 2
        store = _store()
        tp = _pool(store)
        src = tp.get_task_class("SRC")
        task = Task(tp, src, (0,))
        assert src.written_tile(task) == (store, ("in", 0))
        assert ctx.devices.preferred(task) is None      # nobody advised
        store.device_advice = lambda key: 1 if key == ("in", 0) else 0
        assert ctx.devices.preferred(task) is chips[1]
        # a class without a tile of its own names none
        sink = tp.get_task_class("SINK")
        assert sink.written_tile(Task(tp, sink, (0,))) is None
    finally:
        parsec.fini(ctx)
        mca_param.unset("device.tpu.max_devices")


def test_the_race_sanitizer_sees_every_elements_read():
    mca_param.set("pins", "dfsan")
    ctx = parsec.init(nb_cores=4)
    try:
        ctx.start()
        store = _store()
        tp = _pool(store)
        before = ctx.dfsan.stats["reads"]
        ctx.add_taskpool(tp)
        assert tp.wait_completed(60)
        assert ctx.dfsan.stats["reads"] - before == N   # SRC's list
        assert not ctx.dfsan.races
    finally:
        parsec.fini(ctx)
        mca_param.unset("pins")


def test_several_ranks_are_refused_by_name():
    tp = _pool(_store())
    tp.context = types.SimpleNamespace(my_rank=0, nb_ranks=2)
    with pytest.raises(NotImplementedError, match="ranged data flow"):
        tp.startup_hook(tp)
