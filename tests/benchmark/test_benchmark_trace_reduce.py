"""The reduction from a profiler trace to busy time, idle share, exposed
collectives and labelled gaps: on hand-made intervals, where the answer is
known, and on small traces recorded on the v5e in the PR that defined the
benchmark (tests/benchmark/data)."""

import gzip
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


# -- interval arithmetic ------------------------------------------------------

@pytest.mark.parametrize("given,want", [
    ([], []),
    ([(0, 1)], [(0, 1)]),
    ([(2, 3), (0, 1)], [(0, 1), (2, 3)]),
    ([(0, 2), (1, 3)], [(0, 3)]),
    ([(0, 1), (1, 2)], [(0, 2)]),              # touching intervals merge
    ([(0, 5), (1, 2), (3, 4)], [(0, 5)]),      # nested
    ([(1, 1), (2, 1)], []),                    # empty and inverted
])
def test_union(given, want):
    assert tr.union(given) == want
    assert tr.total(tr.union(given)) == sum(hi - lo for lo, hi in want)


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 3), (5, 7)], [(2, 3), (5, 7)]),
    ([(0, 2), (4, 6)], [(1, 5)], [(1, 2), (4, 5)]),
    ([(0, 1)], [(1, 2)], []),
    ([], [(0, 1)], []),
])
def test_intersect(a, b, want):
    assert tr.intersect(a, b) == want
    assert tr.intersect(b, a) == want


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(0, 10)], []),
    ([(0, 2), (4, 6)], [(1, 5)], [(0, 1), (5, 6)]),
    ([(0, 2), (4, 6)], [(-1, 0), (6, 9)], [(0, 2), (4, 6)]),
    ([(0, 4)], [(-1, 1), (3, 5)], [(1, 3)]),
])
def test_subtract(a, b, want):
    assert tr.subtract(a, b) == want
    # a = (a − b) ∪ (a ∩ b), disjointly
    assert tr.total(want) + tr.total(tr.intersect(a, tr.union(b))) == \
        pytest.approx(tr.total(a))


def test_leaves_drop_the_operations_that_only_contain_others():
    ops = [("while.1", 0.0, 10.0), ("fusion.1", 1.0, 2.0),
           ("cond.2", 3.0, 6.0), ("fusion.2", 3.5, 4.0),
           ("all-reduce.1", 4.0, 5.5), ("copy.1", 11.0, 12.0)]
    assert sorted(n for n, _lo, _hi in tr.leaves(ops)) == [
        "all-reduce.1", "copy.1", "fusion.1", "fusion.2"]
    assert tr.leaves([]) == []


FUSION = ("%fusion.185 = f32[1024,21504]{1,0:T(8,128)S(1)} fusion(f32[40960,"
          "40960]{1,0:T(8,128)} %maximum_dynamic-update-slice_fusion.18), "
          "kind=kOutput, calls=%fused_computation")
CUSTOM = ('%custom-call.54 = f32[2,128,128]{1,2,0:T(8,128)S(1)} custom-call('
          'f32[2,128,128]{2,1,0} %get-tuple-element.156), custom_call_target='
          '"InvertDiagBlocksLowerTriangular", operand_layout_constraints={}')
GATHER = ("%all-gather-start.3 = (f32[256,1024]{1,0}, f32[1024,1024]{1,0}) "
          "all-gather-start(f32[256,1024]{1,0} %fusion.7), dimensions={0}")
FED_BY_GATHER = ("%fusion.9 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} "
                 "%all-gather-done.3), kind=kLoop, calls=%fused_computation.2")


@pytest.mark.parametrize("name,family", [
    ("fusion.123", "fusion"), ("copy", "copy"),
    ("all-gather-start.7", "all-gather-start"),
    ("dynamic-update-slice.4.1", "dynamic-update-slice"), ("7", "7"),
    ("%broadcast.228.remat = u32[40,1024,40960]{2,1,0} broadcast(u32[] %c)",
     "broadcast"),
    ("%convolution_add_fusion = f32[4096,4096]{1,0} fusion(f32[4096,4096]"
     "{1,0} %fv_2_.1), kind=kOutput, calls=%fused_computation",
     "convolution_add_fusion"),
    (FUSION, "fusion(kOutput)"),
    (CUSTOM, "custom-call(InvertDiagBlocksLowerTriangular)"),
    (GATHER, "all-gather-start")])
def test_op_family(name, family):
    assert tr.op_family(name) == family


@pytest.mark.parametrize("name,is_collective", [
    ("all-gather-start.7", True), ("all-reduce.2", True),
    ("collective-permute-done", True), ("reduce-scatter.1", True),
    ("all-to-all.9", True), ("recv-done.1", True), ("send.3", True),
    ("fusion.7", False), ("reduce.3", False), ("copy.2", False),
    ("dynamic-update-slice.1", False), ("all-reduce_fusion", False),
    (GATHER, True), (FED_BY_GATHER, False), (FUSION, False),
    ("%all-reduce.5 = f32[8]{0} all-reduce(f32[8]{0} %x), to_apply=%add",
     True),
    ("%collective-permute-done.1 = f32[8]{0} collective-permute-done(("
     "f32[8]{0}, f32[8]{0}) %collective-permute-start.1)", True)])
def test_which_operations_are_collectives(name, is_collective):
    assert tr.is_collective(name) is is_collective


def test_gaps_are_labelled_by_the_shortest_span_over_their_middle():
    spans = [("traced", 0.0, 100.0), ("step", 10.0, 20.0),
             ("insert", 10.0, 13.0), ("wait", 13.0, 20.0)]
    assert tr.label_gap((11.0, 12.0), spans) == "insert"
    assert tr.label_gap((12.0, 16.0), spans) == "wait"
    assert tr.label_gap((30.0, 31.0), spans) == "outside_spans"


# -- the reduction on a trace whose answer is known ---------------------------

def _two_chip_trace():
    """Two steps of 10 s each. Chip 0 is busy 8 s of each step, 2 s of
    them in a collective of which 1 s overlaps a fusion; chip 1 is busy
    4 s of each step. A generate between the steps keeps both chips busy
    and must not count."""
    t = tr.Trace()
    t.spans = [("traced", 0.0, 40.0), ("generate", 1.0, 4.0),
               ("step", 5.0, 15.0), ("insert", 5.0, 8.0),
               ("wait", 8.0, 15.0), ("generate", 16.0, 19.0),
               ("step", 20.0, 30.0), ("insert", 20.0, 23.0),
               ("wait", 23.0, 30.0), ("step", 50.0, 60.0)]
    t.ops[0], t.ops[1] = [], []
    for s in (5.0, 20.0):
        t.ops[0] += [("fusion.1", s + 1.0, s + 7.0),          # 6 s
                     ("all-reduce.1", s + 6.0, s + 8.0),      # 1 s exposed
                     ("while.1", s + 8.0, s + 9.0),           # parent of:
                     ("fusion.2", s + 8.0, s + 9.0)]
        t.ops[1] += [("fusion.1", s + 2.0, s + 6.0)]
    t.ops[0] += [("rng.1", 1.0, 4.0), ("rng.1", 16.0, 19.0)]
    t.ops[1] += [("rng.1", 1.0, 4.0), ("rng.1", 16.0, 19.0)]
    return t


def test_reduce_busy_idle_collectives_and_gaps():
    red = tr.reduce(_two_chip_trace())
    assert red["steps"] == 2                  # the third is outside traced
    assert red["window_s"] == pytest.approx(20.0)
    assert red["busy_s_by_chip"] == {0: pytest.approx(16.0),
                                     1: pytest.approx(8.0)}
    assert red["busy_s"] == pytest.approx(12.0)
    assert red["idle_share"] == pytest.approx(0.4)
    assert red["device_step_s"] == pytest.approx(6.0)
    assert red["busy_max_over_min"] == pytest.approx(2.0)
    # chip 0: 1 s of each step's all-reduce runs beside nothing
    assert red["collective_exposed_share"] == pytest.approx(2.0 / 2 / 20.0)
    ops = dict(red["device_ops"])
    assert ops["fusion"] == pytest.approx((14.0 + 8.0) / 2)
    assert ops["all-reduce"] == pytest.approx(4.0 / 2)
    assert "while" not in ops and "rng" not in ops
    # per step chip 0 idles 1 s in insert and 1 s at the end of wait,
    # chip 1 idles 2 s in insert and 4 s at the end of wait
    assert red["idle_gaps"][:2] == [
        ["sum:wait (4 gaps)", pytest.approx((2.0 + 8.0) / 2)],
        ["sum:insert (4 gaps)", pytest.approx((2.0 + 4.0) / 2)]]
    assert red["idle_gaps"][2:4] == [["longest:wait", pytest.approx(4.0)]] * 2
    assert red["idle_gaps"][4] == ["longest:insert", pytest.approx(2.0)]
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10


def test_reduce_returns_nothing_where_there_is_nothing_to_read():
    assert tr.reduce(tr.Trace()) == {}
    t = _two_chip_trace()
    t.spans = [s for s in t.spans if s[0] != "traced"]
    assert tr.reduce(t) == {}
    t = _two_chip_trace()
    t.ops = {0: [("fusion.1", 100.0, 101.0)]}        # nothing in a step
    assert tr.reduce(t) == {}


# -- the reduction on traces recorded on the v5e ------------------------------

def _recorded(tmp_path, name):
    """tests/benchmark/data/<name>.xplane.pb.gz, unpacked: a few steps of
    small problems through the benchmark's own drivers and spans,
    recorded with jax.profiler on the chip (PERF.md §6, PR 22)."""
    path = tmp_path / (name + ".xplane.pb")
    with gzip.open(os.path.join(DATA, name + ".xplane.pb.gz")) as fh:
        path.write_bytes(fh.read())
    return tr.load(str(path))


def test_recorded_one_chip_trace(tmp_path):
    """Two panel factorizations (N=2048, NB=256) and two DTD GEMMs
    (1024 × 1024 × 512 in 256-tiles: 32 tasks each) on one v5e chip."""
    trace = _recorded(tmp_path, "v5e_1chip")
    assert sorted(trace.ops) == [0]
    assert len(trace.ops[0]) == 1208
    names = [n for n, _lo, _hi in trace.spans]
    assert names.count("step") == 4 and names.count("traced") == 1
    assert names.count("insert") == names.count("wait") == 2
    red = tr.reduce(trace)
    assert red["steps"] == 4
    assert red["window_s"] == pytest.approx(0.027265488, rel=1e-6)
    assert red["busy_s"] == pytest.approx(0.00104474, rel=1e-5)
    assert red["busy_s_by_chip"] == {0: red["busy_s"]}
    assert red["idle_share"] == pytest.approx(0.9616827, rel=1e-5)
    assert red["device_step_s"] == pytest.approx(red["busy_s"] / 4)
    assert red["collective_exposed_share"] == 0.0
    assert red["busy_max_over_min"] == 1.0
    # operations of one chip's line never overlap: busy is their sum
    assert sum(s for _n, s in tr.reduce(trace, top=10 ** 6)["device_ops"]) \
        == pytest.approx(red["busy_s"])
    families = [n for n, _s in red["device_ops"]]
    assert families[:2] == ["custom-call(Cholesky)",
                            "custom-call(InvertDiagBlocksLowerTriangular)"]
    assert "convolution_add_fusion" in families      # the DTD tile GEMM
    assert "fusion(kOutput)" in families             # the trailing updates
    assert len(red["device_ops"]) == 10 and len(red["idle_gaps"]) <= 10
    gaps = {n.split(" ")[0]: s for n, s in red["idle_gaps"]}
    # the chip idles while the host inserts and while it waits
    assert gaps["sum:wait"] == pytest.approx(0.017128733, rel=1e-5)
    assert gaps["sum:insert"] == pytest.approx(0.007593616, rel=1e-5)
    assert gaps["sum:step"] == pytest.approx(0.001498399, rel=1e-5)
    assert sum(s for n, s in red["idle_gaps"] if n.startswith("sum:")) \
        == pytest.approx(red["window_s"] - red["busy_s"])


def test_recorded_four_chip_trace(tmp_path):
    """Two panel factorizations at N=4096, NB=256 with the state sharded
    P("rows") over the four chips of a v5e 2x2 host: the collectives GSPMD
    puts in are most of the step."""
    trace = _recorded(tmp_path, "v5e_4chip")
    assert sorted(trace.ops) == [0, 1, 2, 3]
    assert [len(trace.ops[c]) for c in range(4)] == [4462, 4452, 4452, 4452]
    kinds = {tr.op_family(n) for n, _lo, _hi in trace.ops[0]
             if tr.is_collective(n)}
    assert kinds == {"collective-permute-start", "collective-permute-done",
                     "all-gather", "all-to-all", "all-reduce"}
    red = tr.reduce(trace)
    assert red["steps"] == 2
    assert red["window_s"] == pytest.approx(0.01537656, rel=1e-6)
    assert red["busy_s"] == pytest.approx(0.0134070167, rel=1e-6)
    assert red["busy_s"] == pytest.approx(
        sum(red["busy_s_by_chip"].values()) / 4)
    assert red["idle_share"] == pytest.approx(0.1280874, rel=1e-5)
    assert red["device_step_s"] == pytest.approx(0.0067035084, rel=1e-6)
    # every chip waits for the others, so all are "busy" alike ...
    assert red["busy_max_over_min"] == pytest.approx(1.000123, rel=1e-5)
    # ... and most of that is collectives with nothing running beside
    assert red["collective_exposed_share"] == pytest.approx(0.5913647,
                                                            rel=1e-5)
    collectives = sum(s for n, s in tr.reduce(trace, top=10 ** 6)[
        "device_ops"] if tr.is_collective(n))
    assert collectives / red["window_s"] == pytest.approx(
        red["collective_exposed_share"])
    assert [n for n, _s in red["device_ops"][:4]] == [
        "collective-permute-done", "all-reduce", "all-gather", "all-to-all"]
    assert red["idle_gaps"][0][0] == "sum:step (14049 gaps)"
    assert red["idle_gaps"][0][1] == pytest.approx(0.00196954325, rel=1e-5)
