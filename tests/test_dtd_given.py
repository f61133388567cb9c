"""The DTD front end gives an INOUT tile to its program where no reader
was inserted since the tile's last writer (``dtd._Tile.readers``,
``Taskpool._insert_one``): the task's class is the variant whose chore
names the flow in ``Chore.donates``, the chip module writes the new
version where the old one lies, and the launch holds nothing new. A tile
some reader may still hold is kept, and its writer runs as it always did.
A chip module over a CPU device, the Python engine (the one a chip
gets)."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parsec_tpu as parsec
import parsec_tpu.device.tpu as tpu_mod
from parsec_tpu import dtd
from parsec_tpu.algorithms import insert_gemm_dtd
from parsec_tpu.algorithms.potrf import insert_potrf_dtd
from parsec_tpu.core.task import GROUP_SIZES, DeviceType
from parsec_tpu.data.matrix import SymTwoDimBlockCyclic, TiledMatrix
from parsec_tpu.utils import mca_param

NB = 8
T, IN, INOUT = dtd.TileArg, dtd.INPUT, dtd.INOUT
TPU, CPU = DeviceType.TPU, DeviceType.CPU


@pytest.fixture
def make_ctx():
    made = []
    knobs = {"runtime.native_dtd": 0, "device.tpu.max_devices": 1}

    def make(nb_cores=1, timers=True):
        for knob, value in knobs.items():
            mca_param.set(knob, value)
        ctx = parsec.init(nb_cores=nb_cores)
        ctx.start()
        ctx.set_stage_timers(timers)     # the front end counts
        made.append(ctx)
        return ctx

    yield make
    for ctx in made:
        parsec.fini(ctx)
    for knob in knobs:
        mca_param.unset(knob)


def _module(ctx):
    (dev,) = ctx.devices.by_type(TPU)
    return dev


def _programs(dev):
    """Every program the module has built."""
    return [p for record in dev._table.values()
            for slot, programs in record.items() if slot != "pinned"
            for p in programs.values()]


def _pool(ctx, name="given"):
    tp = dtd.Taskpool(name)
    ctx.add_taskpool(tp)
    return tp


def _done(tp):
    assert tp.wait(timeout=120.0) and tp._native is None


def _tiles(n, name="x", value=0.0):
    """``n`` tiles in a row, every one its own device array."""
    x = TiledMatrix(NB, n * NB, NB, NB, name=name)
    for j in range(n):
        x.write_tile((0, j), jnp.full((NB, NB), value + j, jnp.float32))
    return x


def _inc(x):
    return x + 1.0


def _add(g, x):
    return x + g


class _Seen:
    """An impure CPU reader: what it was handed, as host copies."""

    def __init__(self):
        self.values = []

    def __call__(self, x):
        self.values.append(np.asarray(x).copy())


class _Gate:
    """A task that holds every task linked behind its tile until
    ``open()``: what is inserted meanwhile finds its writers in flight."""

    def __init__(self, tp, value=1.0):
        self.tile = _tiles(1, name=f"gate{id(self)}")
        self._evt, self._value = threading.Event(), value
        tp.insert_task(self._wait, T(self.tile, (0, 0), dtd.OUTPUT),
                       device=CPU)

    def _wait(self, _old):
        assert self._evt.wait(60.0)
        return np.full((NB, NB), self._value, np.float32)

    def arg(self):
        return T(self.tile, (0, 0), IN)

    def open(self):
        self._evt.set()


def _counts(ctx):
    c = ctx.dtd_counters
    return c.get("dtd_args_given", 0), c.get("dtd_args_kept", 0)


# -- (a) a chain of writers ---------------------------------------------------

@pytest.mark.parametrize("insert", ["insert_task", "insert_tasks"])
def test_a_writer_after_a_writer_is_given_its_tile(make_ctx, insert):
    ctx = make_ctx()
    dev, x = _module(ctx), _tiles(1)
    mine = x.data_of((0, 0))            # the collection's own array
    tp = _pool(ctx)
    for _ in range(3):
        if insert == "insert_task":
            tp.insert_task(_inc, T(x, (0, 0), INOUT), device=TPU, pure=True)
        else:
            tp.insert_tasks(_inc, [(T(x, (0, 0), INOUT),)], device=TPU,
                            pure=True)
    _done(tp)
    assert _counts(ctx) == (3, 0)
    (tc,) = tp.task_classes
    assert tc.dtd_given == ("f0",) == tc.incarnations[0].donates
    assert dev.stats["tasks"] == 3 == dev.stats["lone_in_place"]
    assert {p.held for p in _programs(dev)} == {0}
    # the pool's to overwrite: the array the caller put there is gone,
    # the collection holds the last version
    assert mine.is_deleted()
    assert not x.data_of((0, 0)).is_deleted()
    assert float(x.data_of((0, 0))[0, 0]) == 3.0


def test_groups_of_given_tasks_hold_nothing_new(make_ctx):
    ctx = make_ctx()
    dev, x = _module(ctx), _tiles(8)
    tp = _pool(ctx)
    gate = _Gate(tp)
    for _ in range(2):                  # two rounds of eight ready together
        tp.insert_tasks(_add, [(gate.arg(), T(x, (0, j), INOUT))
                               for j in range(8)], device=TPU, pure=True)
    gate.open()
    _done(tp)
    assert _counts(ctx) == (16, 0)
    assert dev.stats["batches"] >= 1
    assert dev.stats["groups_in_place"] == dev.stats["batches"]
    assert dev.stats["lone_in_place"] == 16 - dev.stats["batched_tasks"]
    assert all(p.held == 0 for p in _programs(dev))
    assert [float(x.data_of((0, j))[0, 0]) for j in range(8)] == \
        [j + 2.0 for j in range(8)]


# -- (b) a reader between two writers keeps its snapshot ----------------------

@pytest.mark.parametrize("first_writer", ["in flight", "retired"])
def test_a_reader_between_two_writers_keeps_its_version(
        make_ctx, first_writer):
    ctx = make_ctx(nb_cores=3)          # a closed gate holds a worker
    dev, x = _module(ctx), _tiles(1)
    seen = _Seen()
    tp = _pool(ctx)
    gate = _Gate(tp)
    tp.insert_task(_add, gate.arg(), T(x, (0, 0), INOUT), device=TPU,
                   pure=True)                               # W1: 0 -> 1
    if first_writer == "retired":
        gate.open()
        tp.flush(x)
    # the reader's own gate: it runs after the second writer's launch
    late = _Gate(tp)
    tp.insert_task(lambda _g, v: seen(v), late.arg(), T(x, (0, 0), IN),
                   device=CPU)
    w2 = tp.insert_task(_inc, T(x, (0, 0), INOUT), device=TPU, pure=True)
    gate.open()
    tp.flush(x)             # W2 has run: its input would be gone by now
    late.open()
    _done(tp)
    assert _counts(ctx) == (1, 1)       # W1 given, W2 kept
    assert w2.task_class.dtd_given == ()
    # W1's programs hold nothing new, W2's a tile a member
    assert {p.held for p in _programs(dev)} == \
        {0} | {size * NB * NB * 4 for size in (1, *GROUP_SIZES)}
    (value,) = seen.values              # no "Array has been deleted"
    assert value[0, 0] == 1.0
    assert float(x.data_of((0, 0))[0, 0]) == 2.0


# -- (c) a reader inserted after a giving writer sees its output --------------

@pytest.mark.parametrize("reader", ["linked", "snapshot"])
def test_a_reader_after_a_giving_writer_sees_its_output(make_ctx, reader):
    ctx = make_ctx()
    x, seen = _tiles(1), _Seen()
    tp = _pool(ctx)
    gate = _Gate(tp)
    for _ in range(2):
        tp.insert_task(_add, gate.arg(), T(x, (0, 0), INOUT), device=TPU,
                       pure=True)
    if reader == "snapshot":
        gate.open()
        tp.flush(x)
    before = dict(tp.counters)
    tp.insert_task(seen, T(x, (0, 0), IN), device=CPU)
    linked = tp.counters["dtd_args_linked"] - before["dtd_args_linked"]
    assert linked == (1 if reader == "linked" else 0)
    gate.open()
    _done(tp)
    assert _counts(ctx) == (2, 0)
    (value,) = seen.values
    assert value[0, 0] == 2.0


# -- (d) what is never given --------------------------------------------------

def _twice(a, b):
    return a + b


def _impure(x):
    return np.asarray(x) + 1.0


@pytest.mark.parametrize("why,insert", [
    ("the tile twice in one task",
     lambda tp, x: tp.insert_task(_twice, T(x, (0, 0), IN),
                                  T(x, (0, 0), INOUT), device=TPU,
                                  pure=True)),
    ("the tile twice, the writer first",
     lambda tp, x: tp.insert_task(_twice, T(x, (0, 0), INOUT),
                                  T(x, (0, 0), IN), device=TPU, pure=True)),
    ("an impure body",
     lambda tp, x: tp.insert_task(_impure, T(x, (0, 0), INOUT),
                                  device=TPU)),
    ("a CPU body",
     lambda tp, x: tp.insert_task(_inc, T(x, (0, 0), INOUT), device=CPU,
                                  pure=True)),
], ids=lambda v: v.replace(" ", "_") if isinstance(v, str) else "")
def test_it_is_kept(make_ctx, why, insert):
    ctx = make_ctx()
    dev, x = _module(ctx), _tiles(1, value=1.0)
    mine = x.data_of((0, 0))
    tp = _pool(ctx)
    task = insert(tp, x)
    _done(tp)
    assert _counts(ctx) == (0, 1)
    assert task.task_class.dtd_given == () and \
        task.task_class.incarnations[0].donates is None
    assert dev.stats["lone_in_place"] == 0 and not mine.is_deleted()
    assert float(x.data_of((0, 0))[0, 0]) == 2.0


def test_a_reader_flushed_away_still_counts(make_ctx):
    """``flush_tile`` takes a tile without a writer out of the bank; the
    tile made anew for the next writer knows that a reader holds its
    version."""
    ctx = make_ctx(nb_cores=2)          # a closed gate holds a worker
    x, seen = _tiles(1, value=5.0), _Seen()
    tp = _pool(ctx)
    late = _Gate(tp)
    tp.insert_task(lambda _g, v: seen(v), late.arg(), T(x, (0, 0), IN),
                   device=CPU)
    tp.flush_tile(x, (0, 0))
    assert tp.tiles.get(x, (0, 0)) is None and tp.tiles.retired == 1
    tp.insert_task(_inc, T(x, (0, 0), INOUT), device=TPU, pure=True)
    tp.flush(x)
    late.open()
    _done(tp)
    assert _counts(ctx) == (0, 1)
    assert seen.values[0][0, 0] == 5.0
    assert float(x.data_of((0, 0))[0, 0]) == 6.0


def test_readers_flushes_and_writers_interleaved_under_four_workers(make_ctx):
    """More workers than the loop needs on a short switch interval: a
    writer, a reader, a flush and two more writers on every tile, round
    after round, while the tasks inserted before retire under the
    inserter's feet. Every reader has to see the version of its place
    in the program: a count lost between a tile's flush and the tile
    made anew would hand a reader's snapshot to the next writer's
    program ("Array has been deleted")."""
    import sys
    ctx = make_ctx(nb_cores=4)
    x, rounds, seen = _tiles(6), 12, {}
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        tp = _pool(ctx)
        for r in range(rounds):
            for j in range(6):
                tp.insert_task(_inc, T(x, (0, j), INOUT), device=TPU,
                               pure=True)
                tp.insert_task(
                    lambda v, at=(r, j): seen.__setitem__(
                        at, float(np.asarray(v)[0, 0])),
                    T(x, (0, j), IN), device=CPU)
                tp.flush_tile(x, (0, j))
                tp.insert_tasks(_inc, [(T(x, (0, j), INOUT),)] * 2,
                                device=TPU, pure=True)
        _done(tp)
    finally:
        sys.setswitchinterval(old)
    assert seen == {(r, j): j + 3.0 * r + 1.0
                    for r in range(rounds) for j in range(6)}
    given, kept = _counts(ctx)
    # of a round's three writers the one after the reader is kept
    assert (given, kept) == (2 * 6 * rounds, 6 * rounds)
    assert [float(x.data_of((0, j))[0, 0]) for j in range(6)] == \
        [j + 3.0 * rounds for j in range(6)]


# -- (e) given and kept tasks of one body never share a launch ----------------

def test_given_and_kept_tasks_ready_together_leave_in_launches_of_their_own(
        make_ctx, monkeypatch):
    ctx = make_ctx()
    dev, x, seen = _module(ctx), _tiles(8), _Seen()
    launches, launch = [], dev.execute_group

    def watched(es, tasks, chore):
        n, held = launch(es, tasks, chore)
        if n:
            launches.append(({t.task_class.dtd_given for t in tasks[:n]},
                             chore.donates, held))
        return n, held

    monkeypatch.setattr(dev, "execute_group", watched)
    tp = _pool(ctx)
    gate = _Gate(tp)
    for j in range(0, 8, 2):            # every other tile has a reader
        tp.insert_task(seen, T(x, (0, j), IN), device=CPU)
    tasks = tp.insert_tasks(_add, [(gate.arg(), T(x, (0, j), INOUT))
                                   for j in range(8)], device=TPU, pure=True)
    # one body, one call, two classes: a row that differs from the
    # call's first leaves under the variant that gives what it may
    assert [t.task_class.dtd_given for t in tasks] == [(), ("f1",)] * 4
    gate.open()
    _done(tp)
    assert _counts(ctx) == (4, 4)
    assert sorted(launches, key=lambda x: x[2]) == [
        ({("f1",)}, ("f1",), 0), ({()}, None, 4 * NB * NB * 4)]
    assert dev.stats["batches"] == 2 and dev.stats["groups_in_place"] == 1
    assert [float(x.data_of((0, j))[0, 0]) for j in range(8)] == \
        [j + 1.0 for j in range(8)]
    assert sorted(v[0, 0] for v in seen.values) == [0.0, 2.0, 4.0, 6.0]


# -- (f) the two inserters of the benchmark's cells ---------------------------

def _everything_kept(monkeypatch):
    """A reader forced before every writer: each written tile counts one
    when its writer is inserted, so nothing is given and every task runs
    the code it ran before there was anything to give."""
    insert_one = dtd.Taskpool._insert_one

    def read_first(self, tc, args, *rest):
        for a in args:
            if isinstance(a, dtd.TileArg) and a.access & dtd.OUTPUT:
                self.tiles.tile_of(a.collection, a.key).readers += 1
        return insert_one(self, tc, args, *rest)

    monkeypatch.setattr(dtd.Taskpool, "_insert_one", read_first)


def _gemm(ctx, rng_seed):
    rng = np.random.default_rng(rng_seed)
    m, n, k = 32, 32, 16                # 16 chains of two, as the dry cell
    hosts = [rng.standard_normal(s).astype(np.float32)
             for s in ((m, k), (k, n), (m, n))]
    a, b, c = (TiledMatrix.from_array(h.copy(), NB, NB, name=name)
               for h, name in zip(hosts, "ABC"))
    for key in c.keys():                # resident, as in the cell
        c.write_tile(key, jnp.asarray(c.data_of(key)))
    tp = _pool(ctx, "gemm")
    insert_gemm_dtd(tp, a, b, c)
    _done(tp)
    return c, list(c.keys()), 32


def _potrf(ctx, rng_seed):
    rng = np.random.default_rng(rng_seed)
    n = 8 * NB                          # NT = 8: 120 tasks
    r = rng.standard_normal((n, n))
    a0 = (r @ r.T + n * np.eye(n)).astype(np.float32)
    A = TiledMatrix(n, n, NB, NB, name="A",
                    dist=SymTwoDimBlockCyclic(1, 1, uplo="lower"))
    for j in range(8):
        for i in range(j, 8):
            A.write_tile((i, j), jnp.asarray(
                a0[i * NB:(i + 1) * NB, j * NB:(j + 1) * NB]))
    tp = _pool(ctx, "potrf")
    insert_potrf_dtd(tp, A)
    _done(tp)
    return A, [(i, j) for j in range(8) for i in range(j, 8)], \
        8 + 28 + 28 + 56


@pytest.mark.parametrize("run", [_gemm, _potrf], ids=["gemm", "potrf"])
def test_the_cells_inserters_give_every_inout_tile_and_the_bits_stay(
        make_ctx, monkeypatch, run):
    # a stacked TRSM solves by another route than a lone one, and which
    # TRSMs meet in a launch is the workers' timing: every launch a
    # lone one, so that the two factors can be compared bit for bit
    if run is _potrf:
        monkeypatch.setattr(tpu_mod, "GROUP_BYTES", 0)
    ctx = make_ctx(nb_cores=4)
    ctx.devices.devices[0].weight = 0.01        # the chip's path
    dev = _module(ctx)
    got, keys, tasks = run(ctx, 7)
    given, kept = _counts(ctx)
    assert (given, kept) == (tasks, 0)          # the share reads 100
    stats = dev.dump_statistics()
    assert stats["tasks"] == tasks
    assert stats["lone_in_place"] + stats["groups_in_place"] == \
        tasks - stats["batched_tasks"] + stats["batches"]
    assert all(p.held == 0 for p in _programs(dev))
    tiles = {k: got.data_of(k) for k in keys}
    assert all(isinstance(t, jax.Array) and not t.is_deleted()
                         for t in tiles.values())

    with monkeypatch.context() as forced:
        _everything_kept(forced)
        want, _, _ = run(ctx, 7)
    assert _counts(ctx) == (tasks, tasks)
    assert dev.dump_statistics()["lone_in_place"] + \
        dev.dump_statistics()["groups_in_place"] == \
        stats["lone_in_place"] + stats["groups_in_place"]
    for key, tile in tiles.items():
        assert np.array_equal(np.asarray(tile),
                              np.asarray(want.data_of(key))), key
