"""DPLASMA ``dpotrf`` by task insertion (``insert_potrf_dtd``: the loop of
``testing_zpotrf_dtd.c``) and what it needed of ``dsl/dtd.py``: the
stacked form of a pure body carried from the insert API to the chore, the
per-tile flush that returns without waiting and ends a tile's tracking at
its last writer's retire, and the front end's counters. CPU platform,
seeded matrices, both task engines where the engine does not matter and
the Python one (the one a chip gets) where the device module's launches
do."""

import os
import sys
import threading

import numpy as np
import pytest

import parsec_tpu as parsec
from parsec_tpu import dtd
from parsec_tpu.algorithms import build_potrf, insert_potrf_dtd
from parsec_tpu.algorithms.potrf import (POTRF_DTD_PRIORITY, _potrf_dtd_trsm,
                                         _potrf_stacked, _trsm_stacked)
from parsec_tpu.core.task import DeviceType
from parsec_tpu.data.matrix import SymTwoDimBlockCyclic, TiledMatrix
from parsec_tpu.utils import mca_param

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import generate  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402

REF = Manifest(ROOT).reference("dpotrf_dtd_reference")
NB = 32
CLASSES = {"POTRF": "_potrf_dtd_potrf", "TRSM": "_potrf_dtd_trsm",
           "SYRK": "_potrf_dtd_syrk", "GEMM": "_potrf_dtd_gemm"}


@pytest.fixture
def make_ctx():
    made = []
    knobs = {"device.tpu.max_devices": 1, "potrf.trsm_hook": "gemm"}

    def make(nb_cores=1, engine=0, **params):
        knobs.update(params, **{"runtime.native_dtd": engine})
        for knob, value in knobs.items():
            mca_param.set(knob, value)
        ctx = parsec.init(nb_cores=nb_cores, scheduler="lfq")
        ctx.start()
        # as beside a real chip: every accelerator body on the module
        ctx.devices.devices[0].weight = 0.01
        made.append(ctx)
        return ctx

    yield make
    for ctx in made:
        parsec.fini(ctx)
    for knob in knobs:
        mca_param.unset(knob)


def _matrix(nt, seed=7, step=1, name="A"):
    """The benchmark's SPD matrix for (seed, step), its lower triangle
    alone stored; and A0 whole."""
    import jax.numpy as jnp
    n = nt * NB
    key = generate.step_key(seed, step)
    a0 = REF.dense_a0(key, n, NB)
    A = TiledMatrix(n, n, NB, NB, name=name,
                    dist=SymTwoDimBlockCyclic(1, 1, uplo="lower"))
    for j in range(nt):
        for i in range(j, nt):
            A.write_tile((i, j), jnp.asarray(
                a0[i * NB:(i + 1) * NB, j * NB:(j + 1) * NB], jnp.float32))
    return A, a0, key


def _lower(A):
    return [(i, j) for j in range(A.nt) for i in range(j, A.nt)]


def _residual(A, key):
    """The plain reference's blocked probe over the collection's tiles."""
    import jax
    import jax.numpy as jnp
    n = A.n
    with jax.default_matmul_precision("highest"):
        x = REF.probe_vectors(key, n)
        y, z, y2 = (jnp.zeros_like(x) for _ in range(3))
        for j in range(A.nt):
            y = REF.probe_input_row(j, key, x, y, n=n, nb=NB)
        for i, j in _lower(A):
            z = REF.probe_factor_t(i, j, A.data_of((i, j)), x, z)
        for i, j in _lower(A):
            y2 = REF.probe_factor(i, j, A.data_of((i, j)), z, y2)
        return REF.residual(y, y2)


def _factor(ctx, A):
    tp = dtd.Taskpool("potrf_dtd")
    ctx.add_taskpool(tp)
    insert_potrf_dtd(tp, A)
    assert tp.wait(timeout=120.0), "the pool did not drain"
    return tp


# -- the factor ------

@pytest.mark.parametrize("engine", [0, "auto"], ids=["python", "auto"])
@pytest.mark.parametrize("workers", [1, 4])
@pytest.mark.parametrize("nt", [1, 2, 3, 8])
def test_the_factor_agrees_with_the_reference_and_with_build_potrf(
        make_ctx, nt, workers, engine):
    ctx = make_ctx(nb_cores=workers, engine=engine)
    A, a0, key = _matrix(nt)
    tp = _factor(ctx, A)
    assert (tp._native is None) == (engine == 0)
    assert sorted(A._tiles) == sorted(_lower(A))
    assert _residual(A, key) < 1e-6
    want = np.linalg.cholesky(a0)
    P, _, _ = _matrix(nt, name="P")
    ctx.add_taskpool(build_potrf(P))
    assert ctx.wait(timeout=120.0)
    for i, j in _lower(A):
        got, ptg = (np.asarray(M.data_of((i, j))) for M in (A, P))
        if i == j:
            got, ptg = np.tril(got), np.tril(ptg)
        ref = want[i * NB:(i + 1) * NB, j * NB:(j + 1) * NB]
        assert np.abs(got - ref).max() <= 1e-4 * np.abs(want).max()
        # one arithmetic: the PTG classes' kernels, argument for argument
        assert np.abs(got - ptg).max() <= 1e-5 * np.abs(want).max()


# -- the tester's loop ------

class _Recorder:
    """What ``insert_potrf_dtd`` calls on a pool, in order."""

    def __init__(self):
        self.calls = []

    def insert_task(self, fn, *args, priority=0, pure=False, stacked=None):
        self.calls.append(("task", fn, args, priority, pure, stacked))

    def insert_tasks(self, fn, rows, *, priorities=None, pure=False,
                     stacked=None):
        assert len(rows) == len(priorities) > 0
        for args, priority in zip(rows, priorities):
            self.calls.append(("task", fn, args, priority, pure, stacked))

    def flush_tile(self, collection, key):
        self.calls.append(("flush", key))

    def flush_all(self, collection=None):
        self.calls.append(("flush_all", collection))


def _tester(nt):
    """``testing_zpotrf_dtd.c``'s loop, written down independently:
    ``(class, tiles, priority)`` and ``("flush", key)`` in its order."""
    out = []
    for k in range(nt):
        out.append(("POTRF", [(k, k)], (nt - k) ** 3))
        for m in range(k + 1, nt):
            out.append(("TRSM", [(k, k), (m, k)], (nt - m) ** 3 + 3 * (
                (2 * nt) - k - m - 1) * (m - k)))
        if k + 1 < nt:
            out.append(("flush", (k, k)))
        for m in range(k + 1, nt):
            out.append(("SYRK", [(m, k), (m, m)],
                        (nt - m) ** 3 + 3 * (m - k)))
            for n in range(m + 1, nt):
                out.append(("GEMM", [(n, k), (m, k), (n, m)],
                            (nt - m) ** 3 + 3 * ((2 * nt) - m - n - 3) * (
                                m - n) + 6 * (m - k)))
            out.append(("flush", (m, k)))
    return out


@pytest.mark.parametrize("nt", [1, 2, 3, 8, 24])
def test_the_loop_is_the_testers(nt):
    A = TiledMatrix(nt * NB, nt * NB, NB, NB, name="A")
    rec = _Recorder()
    insert_potrf_dtd(rec, A)
    assert rec.calls[-1] == ("flush_all", A)
    names = {fn: cls for cls, fn in CLASSES.items()}
    got = []
    for call in rec.calls[:-1]:
        if call[0] == "flush":
            got.append(call)
            continue
        _, fn, args, priority, pure, stacked = call
        assert pure and all(a.collection is A for a in args)
        # the last tile is the one written, and the task's affinity
        assert [a.access for a in args] == \
            [dtd.INPUT] * (len(args) - 1) + [dtd.INOUT]
        assert [a.affinity for a in args] == \
            [False] * (len(args) - 1) + [True]
        cls = names[fn.__name__]
        # the two classes that have a batch_hook in build_potrf
        assert stacked == {"TRSM": (_trsm_stacked, (0,)),
                           "POTRF": (_potrf_stacked, ())}.get(cls)
        got.append((cls, [a.key for a in args], priority))
    assert got == _tester(nt)
    # task counts by class are those of build_potrf's graph; the diagonal
    # tile of the last column has no reader left, so flush_all takes it
    counts = {cls: sum(1 for c in got if c[0] == cls) for cls in CLASSES}
    tp = build_potrf(A)
    assert counts == {tc.name: sum(1 for _ in tc.space(tp.g))
                      for tc in tp.task_classes}
    assert sum(1 for c in got if c[0] == "flush") == \
        nt * (nt + 1) // 2 - 1
    if nt == 24:
        assert sum(counts.values()) == 2600


def test_the_priorities_are_the_testers_and_not_build_potrfs():
    """``build_potrf`` orders its classes by quadratic expressions of its
    own: the two POTRF deployments do not order a ready set alike."""
    nt = 24
    prio = POTRF_DTD_PRIORITY
    assert prio["POTRF"](nt, 0) == 24 ** 3
    assert prio["TRSM"](nt, 1, 0) == 23 ** 3 + 3 * 46
    assert prio["SYRK"](nt, 5, 2) == 19 ** 3 + 9
    assert prio["GEMM"](nt, 7, 5, 2) == 19 ** 3 + 3 * 33 * (-2) + 18
    tp = build_potrf(TiledMatrix(nt * NB, nt * NB, NB, NB, name="A"))
    by_name = {tc.name: tc for tc in tp.task_classes}
    assert by_name["POTRF"].priority_fn((0,)) == 3 * 24 ** 2 != \
        prio["POTRF"](nt, 0)
    # both put the diagonal task of a column over its TRSMs over the
    # GEMMs of that column's update
    for order in (
            [by_name["POTRF"].priority_fn((3,)),
             by_name["TRSM"].priority_fn((4, 3)),
             by_name["GEMM"].priority_fn((6, 4, 3))],
            [prio["POTRF"](nt, 3), prio["TRSM"](nt, 4, 3),
             prio["GEMM"](nt, 6, 4, 3)]):
        assert order == sorted(order, reverse=True)


# -- the stacked declaration ------

def _trsm_args(L, C, m):
    return (dtd.TileArg(L, (0, 0), dtd.INPUT),
            dtd.TileArg(C, (m, 0), dtd.INOUT))


def _trsm_operands(members):
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    low = np.tril(rng.standard_normal((NB, NB))) + 2 * NB * np.eye(NB)
    L = TiledMatrix(NB, NB, NB, NB, name="L")
    L.write_tile((0, 0), jnp.asarray(low, jnp.float32))
    C = TiledMatrix(members * NB, NB, NB, NB, name="C")
    c0 = rng.standard_normal((members * NB, NB)).astype(np.float32)
    for m in range(members):
        C.write_tile((m, 0), jnp.asarray(c0[m * NB:(m + 1) * NB]))
    want = np.linalg.solve(low, c0.T.astype(np.float64)).T.reshape(
        members, NB, NB)
    return L, C, want


@pytest.mark.parametrize("members,launches", [(1, 1), (3, 3), (4, 1),
                                              (8, 1), (11, 4)])
def test_a_group_of_trsms_runs_the_stacked_body_and_a_lone_one_its_own(
        make_ctx, members, launches):
    """One worker and one ``insert_tasks`` call, so the ready set is the
    call's rows: a bin of four or more leaves as launches of
    ``GROUP_SIZES`` members through the declaration's hook (the chore's
    ``batch_hook``), fewer leave alone through the body, and both give the
    same tiles to the configuration's tolerance."""
    ctx = make_ctx(nb_cores=1)
    (dev,) = ctx.devices.by_type(DeviceType.TPU)
    L, C, want = _trsm_operands(members)
    seen = []

    def stacked(Ls, Cs):
        seen.append((Ls.shape, Cs.shape))       # at trace time, a size once
        return _trsm_stacked(Ls, Cs)

    tp = dtd.Taskpool("trsm")
    ctx.add_taskpool(tp)
    tp.insert_tasks(_potrf_dtd_trsm,
                    [_trsm_args(L, C, m) for m in range(members)],
                    pure=True, device=DeviceType.TPU,
                    stacked=(stacked, [0]))
    assert tp.wait(timeout=120.0)
    (tc,) = [tc for tc in tp.task_classes if tc.name == "_potrf_dtd_trsm"]
    (chore,) = tc.incarnations
    assert chore.batch_hook is stacked and \
        chore.batch_hook_shared == ("f0",)
    groups = [s for s in (8, 4) if members >= s]
    assert dev.stats["tasks"] == members
    assert dev.stats["batches"] == (1 if groups else 0)
    # the stacked programs of every size are built when the first group
    # forms (none compiles in a later step), and never for lone tasks
    assert {c[0] for c in seen} == \
        ({(8, NB, NB), (4, NB, NB)} if groups else set())
    got = np.stack([np.asarray(C.data_of((m, 0))) for m in range(members)])
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    assert members - (groups[0] - 1 if groups else 0) == launches


def test_a_body_with_a_stacked_form_gets_no_unrolled_group_programs(
        make_ctx):
    """What the POTRF body's declaration is for: a pure body without one
    has its unrolled programs of every admitted size built, and run once,
    at first sight; with one the lone program alone, and the stacked
    sizes when a group first forms, which diagonal tiles never do."""
    ctx = make_ctx(nb_cores=1)
    (dev,) = ctx.devices.by_type(DeviceType.TPU)
    A, _, _ = _matrix(3)
    tp = _factor(ctx, A)
    sizes = {}
    for tc in tp.task_classes:
        for (_key, _sig, stacked), programs in \
                dev._table[id(tc.incarnations[0])].items():
            sizes[tc.name, stacked] = sorted(programs)
    assert sizes["_potrf_dtd_potrf", False] == [1]
    assert ("_potrf_dtd_potrf", True) not in sizes
    assert sizes["_potrf_dtd_trsm", False] == [1]
    assert sizes["_potrf_dtd_syrk", False] == [1, 4, 8]
    assert sizes["_potrf_dtd_gemm", False] == [1, 4, 8]


def test_a_group_that_does_not_share_its_factor_leaves_as_lone_tasks(
        make_ctx):
    import jax.numpy as jnp
    ctx = make_ctx(nb_cores=1)
    (dev,) = ctx.devices.by_type(DeviceType.TPU)
    L, C, want = _trsm_operands(4)
    L2 = TiledMatrix(4 * NB, NB, NB, NB, name="L2")
    for m in range(4):      # equal values, four tiles: no ONE object
        L2.write_tile((m, 0), jnp.array(L.data_of((0, 0))))
    tp = dtd.Taskpool("trsm")
    ctx.add_taskpool(tp)
    tp.insert_tasks(_potrf_dtd_trsm,
                    [(dtd.TileArg(L2, (m, 0), dtd.INPUT),
                      dtd.TileArg(C, (m, 0), dtd.INOUT)) for m in range(4)],
                    pure=True, device=DeviceType.TPU,
                    stacked=(_trsm_stacked, (0,)))
    assert tp.wait(timeout=120.0)
    assert (dev.stats["tasks"], dev.stats["batches"]) == (4, 0)
    got = np.stack([np.asarray(C.data_of((m, 0))) for m in range(4)])
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def test_a_stacked_form_is_of_a_pure_body(make_ctx):
    ctx = make_ctx()
    L, C, _ = _trsm_operands(1)
    tp = dtd.Taskpool("trsm")
    ctx.add_taskpool(tp)
    with pytest.raises(ValueError, match="pure=True"):
        tp.insert_task(_potrf_dtd_trsm, *_trsm_args(L, C, 0),
                       stacked=(_trsm_stacked, (0,)))
    with pytest.raises(ValueError, match="pure=True"):
        tp.insert_tasks(_potrf_dtd_trsm, [_trsm_args(L, C, 0)],
                        stacked=(_trsm_stacked, (0,)))
    tp.wait()


@pytest.mark.parametrize("batched", [False, True],
                         ids=["insert_task", "insert_tasks"])
def test_a_pool_without_a_stacked_body_builds_the_chores_it_built(
        make_ctx, batched):
    """The share-nothing case: no flush, no declaration, and the class's
    one chore is what ``_task_class_for`` made before there was either."""
    from parsec_tpu.algorithms.gemm import _gemm_dtd_body
    ctx = make_ctx()
    x = TiledMatrix(2 * NB, NB, NB, NB, name="x")
    args = (dtd.TileArg(x, (0, 0), dtd.INPUT),
            dtd.TileArg(x, (0, 0), dtd.INPUT),
            dtd.TileArg(x, (1, 0), dtd.INOUT),
            dtd.ValueArg(1.0), dtd.ValueArg(1.0))
    tp = dtd.Taskpool("plain")
    ctx.add_taskpool(tp)
    if batched:
        tp.insert_tasks(_gemm_dtd_body, [args], pure=True)
    else:
        tp.insert_task(_gemm_dtd_body, *args, pure=True)
    tp.insert_task(lambda a: None, dtd.TileArg(x, (0, 0), dtd.INPUT))
    assert tp.wait(timeout=120.0)
    pure, impure = (tc.incarnations[0] for tc in tp.task_classes)
    assert (pure.batchable, pure.batch_hook, pure.batch_hook_shared) == \
        (False, None, None)
    assert callable(pure.batch_sig) and callable(pure.batch_body)
    assert (impure.batchable, impure.batch_hook, impure.batch_sig,
            impure.batch_body) == (False, None, None, None)
    # the last of a key: the flows the class's tasks give to their
    # program (the pure body's INOUT tile, read by nobody before it)
    assert list(tp._classes) == [
        (_gemm_dtd_body, tp._shape_of(args), DeviceType.ALL, True, None,
         ("f2",)),
        (list(tp._classes)[1][0], (("tile", dtd.INPUT),), DeviceType.ALL,
         False, None, ())]
    assert (pure.donates, impure.donates) == (("f2",), None)
    # nothing counted, nothing flushed: the bank tracked both tiles to
    # the pool's end and let them go there
    assert tp.counters == {} and ctx.dtd_counters == {}
    assert tp.tiles.all() == [] and tp.tiles.retired == 0
    assert tp.tiles.peak == 2 and tp.tiles.dropped == 2


# -- the per-tile flush ------

def _bump(t):
    return t + 1.0


def _gate_body(gate):
    def body(t):
        assert gate.wait(60.0)
        return t + 1.0
    return body


@pytest.mark.parametrize("engine", [0, "auto"], ids=["python", "auto"])
def test_flush_tile_returns_at_once_and_the_last_writer_retires_the_tile(
        make_ctx, engine):
    ctx = make_ctx(nb_cores=2, engine=engine)
    x = TiledMatrix(2 * NB, NB, NB, NB, name="x")
    gate = threading.Event()
    tp = dtd.Taskpool("flush")
    ctx.add_taskpool(tp)
    try:
        tp.insert_task(_gate_body(gate), dtd.TileArg(x, (0, 0), dtd.INOUT))
        tp.insert_task(_bump, dtd.TileArg(x, (0, 0), dtd.INOUT))
        tp.insert_task(_bump, dtd.TileArg(x, (1, 0), dtd.INOUT))
        tp.flush(x.__class__(NB, NB, NB, NB, name="other"))   # no tile of it
        # a tile never inserted is not tracked, and looking makes none
        tp.flush_tile(x, (5, 5))
        assert tp.tiles.get(x, (5, 5)) is None
        # writers in flight behind the gate: the call returns, the tile
        # stays tracked, and a reader inserted now is linked to them
        tp.flush_tile(x, (0, 0))
        tile = tp.tiles.get(x, (0, 0))
        assert tile is not None and tile.flushed
        assert tile.last_writer is not None
        seen = []
        tp.insert_task(lambda t: seen.append(np.asarray(t)[0, 0]),
                       dtd.TileArg(x, (0, 0), dtd.INPUT))
    finally:
        gate.set()
    tp.flush(x)                         # the blocking form: now they ran
    assert tp.tiles.get(x, (0, 0)) is None and tp.tiles.retired == 1
    assert np.asarray(x.data_of((0, 0)))[0, 0] == 2.0 and seen == [2.0]
    # a tile without a writer goes at once; flushing it again is nothing
    assert tp.tiles.get(x, (1, 0)) is not None
    tp.flush_tile(x, (1, 0))
    tp.flush_tile(x, (1, 0))
    assert tp.tiles.get(x, (1, 0)) is None and tp.tiles.retired == 2
    # a later insert on a flushed tile reads the collection's current
    # version and tracks the tile anew
    x.write_tile((0, 0), np.full((NB, NB), 10.0, np.float32))
    tp.insert_task(_bump, dtd.TileArg(x, (0, 0), dtd.INOUT))
    assert tp.tiles.get(x, (0, 0)) is not tile
    assert tp.wait(timeout=60.0)
    assert np.asarray(x.data_of((0, 0)))[0, 0] == 11.0
    assert tp.tiles.peak == 2


@pytest.mark.parametrize("engine", [0, "auto"], ids=["python", "auto"])
def test_a_writer_inserted_after_the_flush_keeps_the_tile_tracked(
        make_ctx, engine):
    ctx = make_ctx(nb_cores=2, engine=engine)
    x = TiledMatrix(NB, NB, NB, NB, name="x")
    gate = threading.Event()
    tp = dtd.Taskpool("flush")
    ctx.add_taskpool(tp)
    try:
        tp.insert_task(_gate_body(gate), dtd.TileArg(x, (0, 0), dtd.INOUT))
        tp.flush_tile(x, (0, 0))
        tile = tp.tiles.get(x, (0, 0))
        tp.insert_task(_bump, dtd.TileArg(x, (0, 0), dtd.INOUT))
        assert not tile.flushed
    finally:
        gate.set()
    tp.flush(x)
    assert tp.tiles.get(x, (0, 0)) is tile and tp.tiles.retired == 0
    # and one inserted after the flush has taken the tile out puts the
    # tile it holds back: no second writer chain beside it
    tp.flush_tile(x, (0, 0))
    assert tp.tiles.get(x, (0, 0)) is None and tile.flushed
    with tile.lock:
        tp.tiles.readopt(tile)
    assert tp.tiles.get(x, (0, 0)) is tile and not tile.flushed
    tp.flush_all()
    assert tp.tiles.all() == []
    assert tp.wait(timeout=60.0)
    assert np.asarray(x.data_of((0, 0)))[0, 0] == 2.0


@pytest.mark.parametrize("engine", [0, "auto"], ids=["python", "auto"])
def test_flushes_racing_retires_lose_no_writer(make_ctx, engine):
    """The inserter flushes tiles whose writers are retiring on more
    workers than cores, the interpreter switching threads every 50 µs:
    every increment inserted, before a flush or after it, lands (a tile
    taken out while the inserter still held it would start a second
    writer chain beside the first), and the bank ends empty. The
    inserter gives the interpreter up between looking a tile up and
    linking to it, which is where a retire has to be able to land."""
    import sys as _sys
    import time
    workers = (os.cpu_count() or 4) + 4
    ctx = make_ctx(nb_cores=workers, engine=engine)
    tiles, rounds = 16, 30
    x = TiledMatrix(tiles * NB, NB, NB, NB, name="x")
    tp = dtd.Taskpool("race")
    ctx.add_taskpool(tp)
    tile_of = tp.tiles.tile_of

    def yielding(dc, key):
        tile = tile_of(dc, key)
        time.sleep(0)
        return tile

    tp.tiles.tile_of = yielding
    interval = _sys.getswitchinterval()
    _sys.setswitchinterval(5e-5)
    try:
        for r in range(rounds):
            for t in range(tiles):
                tp.insert_task(_bump, dtd.TileArg(x, (t, 0), dtd.INOUT))
                if (r + t) % 3 == 0:
                    tp.flush_tile(x, (t, 0))
                if (r + t) % 5 == 0:     # two writers right behind the flush
                    tp.insert_task(_bump, dtd.TileArg(x, (t, 0), dtd.INOUT))
                    tp.insert_task(_bump, dtd.TileArg(x, (t, 0), dtd.INOUT))
        tp.flush_all(x)
        assert tp.wait(timeout=120.0), "the pool did not drain"
    finally:
        _sys.setswitchinterval(interval)
    for t in range(tiles):
        want = rounds + 2 * sum(1 for r in range(rounds) if (r + t) % 5 == 0)
        assert np.asarray(x.data_of((t, 0)))[0, 0] == want, t
    assert tp.tiles.all() == []


@pytest.mark.parametrize("engine", [0, "auto"], ids=["python", "auto"])
@pytest.mark.parametrize("nt", [3, 8])
def test_the_factorizations_flushes_leave_the_bank_empty(
        make_ctx, nt, engine):
    ctx = make_ctx(nb_cores=4, engine=engine)
    A, _, key = _matrix(nt)
    tp = _factor(ctx, A)
    stored = nt * (nt + 1) // 2
    assert tp.tiles.all() == [] and tp.tiles.retired == stored
    assert tp.tiles.peak <= stored
    assert _residual(A, key) < 1e-6


# -- the counters and the span ------

def test_the_front_end_counts_under_the_stage_timers_alone(make_ctx):
    ctx = make_ctx(nb_cores=4)
    nt = 6
    tasks = nt + nt * (nt - 1) + nt * (nt - 1) * (nt - 2) // 6
    args = nt + 2 * (nt * (nt - 1) // 2) * 2 + \
        3 * (nt * (nt - 1) * (nt - 2) // 6)
    A, _, key = _matrix(nt)
    tp = _factor(ctx, A)
    assert tp.counters == {} and ctx.dtd_counters == {}
    ctx.set_stage_timers(True)
    for step in range(2):
        A, _, key = _matrix(nt, step=step)
        tp = _factor(ctx, A)
        c = tp.counters
        assert c["dtd_args_linked"] + c["dtd_args_snapshot"] == args
        # every tile's first use has nothing to link to
        assert c["dtd_args_snapshot"] >= nt * (nt + 1) // 2
        assert tp.insert_calls == tasks
    ctx.set_stage_timers(False)
    total = ctx.dtd_counters
    assert total["dtd_args_linked"] + total["dtd_args_snapshot"] == 2 * args
    assert total["dtd_tiles_flushed"] == 2 * (nt * (nt + 1) // 2)
    assert total["dtd_tiles_tracked_peak"] <= nt * (nt + 1) // 2
    assert "dtd_window_waits" not in total
    _factor(ctx, _matrix(nt)[0])
    assert ctx.dtd_counters == total


def test_the_inserters_parks_in_the_window_are_counted(make_ctx):
    ctx = make_ctx(nb_cores=2, **{"dtd.window_size": 8,
                                  "dtd.threshold_size": 4})
    ctx.set_stage_timers(True)
    x = TiledMatrix(NB, NB, NB, NB, name="x")
    tp = dtd.Taskpool("window")
    ctx.add_taskpool(tp)
    for _ in range(64):
        tp.insert_task(_bump, dtd.TileArg(x, (0, 0), dtd.INOUT))
    assert tp.wait(timeout=60.0)
    assert tp.counters["dtd_window_waits"] >= 1
    assert tp.counters["dtd_window_wait_s"] > 0
    assert ctx.dtd_counters["dtd_window_waits"] == \
        tp.counters["dtd_window_waits"]
    assert np.asarray(x.data_of((0, 0)))[0, 0] == 64.0


def test_a_flush_opens_its_span_under_the_stage_timers_alone(
        make_ctx, monkeypatch):
    from parsec_tpu.core.spans import SPAN_DTD_FLUSH
    from parsec_tpu.dsl import dtd as dtd_mod
    assert SPAN_DTD_FLUSH == "parsec:dtd_flush"
    opened = []

    class Span(dtd_mod.StageSpan):
        def __init__(self, name):
            opened.append(name)
            super().__init__(name)

    monkeypatch.setattr(dtd_mod, "StageSpan", Span)
    ctx = make_ctx()
    x = TiledMatrix(NB, NB, NB, NB, name="x")
    tp = dtd.Taskpool("span")
    ctx.add_taskpool(tp)
    tp.insert_task(_bump, dtd.TileArg(x, (0, 0), dtd.INOUT))
    tp.flush_tile(x, (0, 0))
    tp.flush_all(x)
    tp.flush(x)
    assert opened == []
    ctx.set_stage_timers(True)
    tp.insert_task(_bump, dtd.TileArg(x, (0, 0), dtd.INOUT))
    tp.flush_tile(x, (0, 0))
    tp.flush_all(x)
    tp.flush(x)
    assert opened == ["parsec:insert"] + [SPAN_DTD_FLUSH] * 3
    assert tp.wait(timeout=60.0)


# -- the storage ------

@pytest.mark.parametrize("workers", [1, 4])
def test_the_factorization_runs_in_the_matrixs_own_storage(
        make_ctx, workers):
    """Live tile buffers while the pool runs, sampled at every task's
    completion, over the stored triangle: the collection never holds a
    superseded version, so what is held twice is what tasks in flight
    hold (the configuration's limit is 1.35 at 300 tiles; eight workers'
    worth of tasks are a larger share of 36)."""
    import gc
    import jax
    nt = 8
    ctx = make_ctx(nb_cores=workers)
    A, _, key = _matrix(nt)
    stored = nt * (nt + 1) // 2
    tile_bytes = NB * NB * 4
    gc.collect()            # other tests' pools are cyclic garbage
    before = sum(1 for a in jax.live_arrays()
                 if a.shape == (NB, NB) and a.nbytes == tile_bytes)
    assert before >= stored
    most = [0]
    lock = threading.Lock()
    release = ctx._release_deps

    def counting(es, task):
        release(es, task)
        live = sum(1 for a in jax.live_arrays()
                   if a.shape == (NB, NB) and a.nbytes == tile_bytes)
        with lock:
            most[0] = max(most[0], live - (before - stored))

    ctx._release_deps = counting
    try:
        tp = _factor(ctx, A)
    finally:
        ctx._release_deps = release
    assert tp._native is None
    assert _residual(A, key) < 1e-6
    # every update replaced its tile: a second copy of the trailing
    # matrix (28 tiles here) would read 1.78
    assert stored <= most[0] <= stored + 2 * 8 + 4, most[0]
    del tp
    gc.collect()
    after = sum(1 for a in jax.live_arrays()
                if a.shape == (NB, NB) and a.nbytes == tile_bytes)
    # what outlives the pool beside the factor: the last task in each
    # worker's hands (its input is a superseded version) and the output
    # the module's next group would wait for
    assert stored <= after - (before - stored) <= stored + workers + 1
