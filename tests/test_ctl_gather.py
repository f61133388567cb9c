"""CTL-gather tests (reference tests/dsl/ptg/controlgather/ctlgat.jdf,
PARSEC_HAS_CTL_GATHER): one task fans in control deps from N producers
through a single CTL flow."""

import threading

import pytest

import parsec_tpu as parsec
from parsec_tpu.data import LocalCollection
from parsec_tpu.dsl import ptg


def _gather_tp(store, n):
    """W(i) each bump their own slot, then GATHER(0) runs after ALL of
    them (a barrier expressed as dataflow)."""
    tp = ptg.Taskpool("ctlgat", N=n, S=store)
    tp.task_class(
        "W", params=("i",),
        space=lambda g: ((i,) for i in range(g.N)),
        flows=[
            ptg.FlowSpec(
                "X", ptg.RW,
                ins=[ptg.In(data=lambda g, i: (g.S, (i,)))],
                outs=[ptg.Out(data=lambda g, i: (g.S, (i,)))]),
            ptg.FlowSpec(
                "C", ptg.CTL,
                outs=[ptg.Out(dst=("GATHER", lambda g, i: (0,), "C"))]),
        ])
    tp.task_class(
        "GATHER", params=("j",),
        space=lambda g: ((0,),),
        flows=[
            ptg.FlowSpec(
                "C", ptg.CTL,
                ins=[ptg.In(src=("W", lambda g, j: [(i,) for i in
                                                    range(g.N)], "C"),
                            gather=True)]),
            ptg.FlowSpec(
                "R", ptg.WRITE,
                outs=[ptg.Out(data=lambda g, j: (g.S, ("sum",)))]),
        ])

    @tp.get_task_class("W").body_cpu
    def w_body(task, x):
        return x + 1

    @tp.get_task_class("GATHER").body_cpu
    def gather_body(task, r):
        # by the gather contract every W has completed and written back
        return {"R": sum(store.data_of((i,))
                         for i in range(tp.g.N))}

    return tp


def test_ctl_gather_checker():
    store = LocalCollection("S", {(i,): 0 for i in range(6)})
    store.write_tile(("sum",), None)
    tp = _gather_tp(store, 6)
    assert tp.get_task_class("GATHER").deps_mode == "counter"
    assert tp.get_task_class("GATHER").deps_goal((0,)) == 6
    ptg.check_taskpool(tp)


@pytest.mark.parametrize("n", [1, 7, 32])
def test_ctl_gather_runs_after_all(ctx, n):
    store = LocalCollection("S", {(i,): 10 * i for i in range(n)})
    store.write_tile(("sum",), None)
    ctx.add_taskpool(_gather_tp(store, n))
    assert ctx.wait(timeout=60)
    assert store.data_of(("sum",)) == sum(10 * i + 1 for i in range(n))


CTLGAT_JDF = """
N [ type = int ]
S [ type = collection ]

W(i)
  i = 0 .. N-1
  RW X <- S(i)
       -> S(i)
  CTL C -> C GATHER(0)
BODY
  X = X + 1
END

GATHER(j)
  j = 0 .. 0
  CTL C <- C W(0 .. N-1)
  WRITE R -> S(N)
BODY
  R = 1
END
"""


def test_ctl_gather_from_jdf(ctx):
    """The ctlgat.jdf syntax: a ranged IN dep on a CTL flow compiles to
    a gather barrier."""
    from parsec_tpu.dsl.jdf import compile_jdf
    n = 9
    store = LocalCollection("S", {(i,): 0 for i in range(n + 1)})
    tp = compile_jdf(CTLGAT_JDF, name="ctlgat").taskpool(N=n, S=store)
    assert tp.get_task_class("GATHER").deps_goal((0,)) == n
    ptg.check_taskpool(tp)
    ctx.add_taskpool(tp)
    assert ctx.wait(timeout=60)
    assert store.data_of((n,)) == 1                     # barrier fired
    assert all(store.data_of((i,)) == 1 for i in range(n))


def test_jdf_ranged_in_on_data_flow_rejected():
    """Ranged IN on a non-CTL flow must fail at compile time with line
    info (like the rest of the JDF semantic checks)."""
    from parsec_tpu.dsl.jdf import JDFSemanticError, compile_jdf
    bad = """
N [ type = int ]
S [ type = collection ]

W(i)
  i = 0 .. N-1
  RW X <- S(i)
       -> X G(0)
BODY
  X = X
END

G(j)
  j = 0 .. 0
  RW X <- X W(0 .. N-1)
BODY
  X = X
END
"""
    with pytest.raises(JDFSemanticError, match="CTL"):
        compile_jdf(bad)


def test_gather_bare_tuple_is_one_coordinate():
    """A gather params_fn returning a bare tuple names ONE producer
    (the Out-dst convention), not one producer per element."""
    from parsec_tpu.dsl.ptg import PTGTaskClass
    assert PTGTaskClass._coord_set((1, 2)) == {(1, 2)}
    assert PTGTaskClass._coord_set([(1, 2), (3, 4)]) == {(1, 2), (3, 4)}
    assert PTGTaskClass._coord_set([1, 2]) == {(1,), (2,)}


def test_gather_on_a_data_flow_is_a_list_since_pr_43():
    """Until PR 43 a gather was CTL-only ("data fan-in needs one flow per
    producer"); a data flow that gathers now holds the ordered list of
    its producers' values (tests/test_ptg_ranged_flows.py), and a CTL
    gather still counts a coordinate named twice once."""
    store = LocalCollection("S", {(0,): 0})
    tp = ptg.Taskpool("lists", S=store)
    tc = tp.task_class(
        "B", params=("i",), space=lambda g: ((0,),),
        flows=[ptg.FlowSpec(
            "X", ptg.RW,
            ins=[ptg.In(src=("B", lambda g, i: [(0,), (0,)], "X"),
                        gather=True)]),
            ptg.FlowSpec(
            "C", ptg.CTL,
            ins=[ptg.In(src=("B", lambda g, i: [(0,), (0,)], "C"),
                        gather=True)])])
    assert tc.ranged and tc.deps_mode == "counter"
    assert tc.deps_goal((0,)) == 2 + 1      # two elements, one CTL
    with pytest.raises(ValueError, match="gather requires a src"):
        tp.task_class(
            "D", params=("i",), space=lambda g: ((0,),),
            flows=[ptg.FlowSpec(
                "X", ptg.RW, ins=[ptg.In(new=lambda g, i: 0,
                                         gather=True)])])
