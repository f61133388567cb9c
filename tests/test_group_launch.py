"""Group launch on the dynamic path: a worker that holds a ready
accelerator task whose chore has a group program takes the ready tasks of
its taskpool it can select, one bin per body (``Context._take_group``),
and the device module issues each bin as one XLA program
(``TPUDevice.execute_group``). DTD GEMMs with accelerator-typed pure
bodies and a small PTG POTRF (four classes ready together) on the CPU
platform, Python engine (the one a chip gets), one device module."""

import gc
import threading

import numpy as np
import pytest

import parsec_tpu as parsec
import parsec_tpu.device.tpu
from parsec_tpu import dtd
from parsec_tpu.dsl import ptg
from parsec_tpu.algorithms import build_potrf
from parsec_tpu.algorithms.gemm import _gemm_dtd_body
from parsec_tpu.core import context as context_mod
from parsec_tpu.core.task import GROUP_SIZES, GROUP_TAKE, DeviceType
from parsec_tpu.core.taskpool import CancelledError
from parsec_tpu.data.matrix import SymTwoDimBlockCyclic, TiledMatrix
from parsec_tpu.profiling.pins import PinsEvent
from parsec_tpu.utils import compile_cache, mca_param

# the `dry` sizes of the gemm_dtd_nb1024 cell: 32 tasks in 16 chains of 2
M, N, K, NB = 256, 256, 128, 64
BIG, SMALL = GROUP_SIZES[0], GROUP_SIZES[-1]
assert (BIG, SMALL) == (8, 4)       # the counts below are of these sizes
assert GROUP_TAKE == 2 * BIG


@pytest.fixture
def make_ctx():
    made = []
    knobs = {"runtime.native_dtd": 0, "device.tpu.max_devices": 1}

    def make(nb_cores=1, scheduler="lfq", **params):
        knobs.update(params)
        for knob, value in knobs.items():
            mca_param.set(knob, value)
        ctx = parsec.init(nb_cores=nb_cores, scheduler=scheduler)
        ctx.start()
        made.append(ctx)
        return ctx

    yield make
    for ctx in made:
        parsec.fini(ctx)
    for knob in knobs:
        mca_param.unset(knob)


def _module(ctx):
    (dev,) = ctx.devices.by_type(DeviceType.TPU)
    return dev


def _groups(ctx):
    """(group launches, tasks in them) so far."""
    stats = _module(ctx).stats
    return stats["batches"], stats["batched_tasks"]


def _wait(tp, timeout=120.0):
    """``tp.wait()`` that fails instead of hanging; what it raised."""
    raised = []

    def wait():
        try:
            tp.wait()
        except BaseException as exc:  # noqa: BLE001 — handed to the test
            raised.append(exc)

    waiter = threading.Thread(target=wait, daemon=True)
    waiter.start()
    waiter.join(timeout)
    assert not waiter.is_alive(), f"pool {tp.name} did not drain"
    assert tp._native is None
    return raised[0] if raised else None


def _matrices(rng, m=M, n=N, k=K, nb=NB):
    hosts = [rng.standard_normal(shape).astype(np.float32)
             for shape in ((m, k), (k, n), (m, n))]
    return hosts, [TiledMatrix.from_array(h.copy(), nb, nb, name=name)
                   for h, name in zip(hosts, "ABC")]


def _rows(a, b, c, m, ks=None):
    """The tasks of one row of C tiles, as ``insert_gemm_dtd`` makes them
    (of the k blocks ``ks`` alone, if given)."""
    one = dtd.ValueArg(1.0)
    return [(dtd.TileArg(a, (m, k), dtd.INPUT),
             dtd.TileArg(b, (k, n), dtd.INPUT),
             dtd.TileArg(c, (m, n), dtd.INOUT, affinity=True), one, one)
            for n in range(c.nt) for k in (ks or range(a.nt))]


def _gemm(ctx, a, b, c, device=DeviceType.TPU, pure=True, name="gemm"):
    tp = dtd.Taskpool(name)
    ctx.add_taskpool(tp)
    for m in range(c.mt):
        tp.insert_tasks(_gemm_dtd_body, _rows(a, b, c, m), device=device,
                        pure=pure)
    assert _wait(tp) is None
    return tp


# -- same arithmetic ----------------------------------------------------------

@pytest.mark.parametrize("scheduler", ["lfq", "gd", "wfq"])
def test_groups_and_lone_tasks_give_the_same_bits(make_ctx, rng, scheduler):
    ctx = make_ctx(scheduler=scheduler)
    (a_h, b_h, c_h), (a, b, c) = _matrices(rng)
    _gemm(ctx, a, b, c)
    groups, grouped = _groups(ctx)
    assert groups >= 1 and grouped >= SMALL
    # the single path, forced: a pool per task, in insertion order
    alone = TiledMatrix.from_array(c_h.copy(), NB, NB, name="C")
    for m in range(alone.mt):
        for row in _rows(a, b, alone, m):
            tp = dtd.Taskpool("one")
            ctx.add_taskpool(tp)
            tp.insert_tasks(_gemm_dtd_body, [row], device=DeviceType.TPU,
                            pure=True)
            assert _wait(tp) is None
    assert _groups(ctx) == (groups, grouped)
    assert np.array_equal(c.to_array(), alone.to_array())
    np.testing.assert_allclose(c.to_array(), c_h + a_h @ b_h, rtol=1e-4,
                               atol=1e-4)


class _Turn:
    """A module's turn that knows who holds it."""

    def __init__(self):
        self._lock, self.owner = threading.Lock(), None

    def acquire(self):
        self._lock.acquire()
        self.owner = threading.get_ident()

    def release(self):
        self.owner = None
        self._lock.release()

    def locked(self):
        return self._lock.locked()

    def mine(self):
        return self.owner == threading.get_ident()


def _turn_probe(ctx, monkeypatch):
    """Put a ``_Turn`` in the module's place and watch: ``launched`` says
    for every group launch whether its worker held the turn, ``released``
    for every member of a group whether its worker held the turn when the
    task completed, ``new`` the bytes of new outputs each group held."""
    dev = _module(ctx)
    dev.group_turn = turn = _Turn()
    seen = {"launched": [], "released": [], "new": []}
    grouped, launch = set(), dev.execute_group

    def in_turn(es, tasks, chore):
        n, new_bytes = launch(es, tasks, chore)
        if n:
            seen["launched"].append(turn.mine())
            seen["new"].append(new_bytes)
            grouped.update(t.uid for t in tasks[:n])
        return n, new_bytes

    def end(_es, task):
        if task.uid in grouped:
            seen["released"].append(turn.mine())

    monkeypatch.setattr(dev, "execute_group", in_turn)
    ctx.pins.register(PinsEvent.EXEC_END, end)
    return seen


def _look(c):
    return None


def _gemm_kept(ctx, a, b, c, name="kept"):
    """``_gemm`` with a reader of every C tile (a CPU body) inserted
    before each of its writers, a block of k at a time: no writer is the
    only holder of the version it takes in, so none is given its tile
    and every one returns a new tile, as every DTD body did before the
    front end counted a tile's readers."""
    tp = dtd.Taskpool(name)
    ctx.add_taskpool(tp)
    for m in range(c.mt):
        for k in range(a.nt):
            tp.insert_tasks(_look, [(dtd.TileArg(c, (m, n), dtd.INPUT),)
                                    for n in range(c.nt)],
                            device=DeviceType.CPU)
            tp.insert_tasks(_gemm_dtd_body, _rows(a, b, c, m, ks=[k]),
                            device=DeviceType.TPU, pure=True)
    assert _wait(tp) is None
    return tp


@pytest.mark.parametrize("tiles,n", [("given", 256), ("kept", 128)])
def test_1024_chains_of_four_refill_the_groups(make_ctx, rng, monkeypatch,
                                               tiles, n):
    """The cell's DAG (4096 tasks in 1024 chains of 4, four workers) at
    8x8 tiles: the successors a group releases go to the worker's own
    queue, and the next group forms from them. A chain's writers follow
    one another with no reader between, so the front end gives each its
    tile: every group holds nothing new and its members are released
    with the turn free, as a PTG POTRF's (the twin below). With a
    reader before every writer (256 chains) every body returns a new
    tile, every group holds new outputs and is one group in flight: its
    worker holds the turn from taking the tasks to the last member's
    release."""
    ctx = make_ctx(nb_cores=4)
    seen = _turn_probe(ctx, monkeypatch)
    (a_h, b_h, c_h), (a, b, c) = _matrices(rng, n, n, 32, 8)
    (_gemm if tiles == "given" else _gemm_kept)(ctx, a, b, c)
    groups, grouped = _groups(ctx)
    stats = _module(ctx).stats
    assert stats["tasks"] == (n // 8) ** 2 * 4
    assert grouped > 0 and grouped / groups >= 4
    assert len(seen["launched"]) == groups and all(seen["launched"])
    assert len(seen["released"]) == grouped
    if tiles == "given":
        assert not any(seen["released"]) and seen["new"] == [0] * groups
        assert stats["groups_in_place"] == groups
        assert stats["lone_in_place"] == stats["tasks"] - grouped
    else:
        assert all(seen["released"]) and all(n > 0 for n in seen["new"])
        assert stats["groups_in_place"] == stats["groups_pipelined"] == 0
        assert stats["lone_in_place"] == 0
    np.testing.assert_allclose(c.to_array(), c_h + a_h @ b_h, rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("nb_cores", [1, 4])
def test_an_in_place_groups_members_are_released_with_the_turn_free(
        make_ctx, rng, monkeypatch, nb_cores):
    """The twin on a PTG POTRF, whose bodies write where their tiles lie
    (``Chore.donates``): every group is launched inside its worker's
    turn and holds nothing new, so its members complete once the turn is
    given up, and the module counts what it did."""
    ctx = make_ctx(nb_cores=nb_cores)
    ctx.devices.devices[0].weight = 0.01    # the chip's path
    seen = _turn_probe(ctx, monkeypatch)
    m = rng.standard_normal((PN, PN))
    a0 = (m @ m.T + PN * np.eye(PN)).astype(np.float32)
    tiles = _potrf(ctx, a0)
    groups, grouped = _groups(ctx)
    stats = _module(ctx).dump_statistics()
    assert stats["tasks"] == sum(CLASSES.values()) and groups >= 1
    assert len(seen["launched"]) == groups and all(seen["launched"])
    assert len(seen["released"]) == grouped and not any(seen["released"])
    assert seen["new"] == [0] * groups
    # what the module counted: every group and every lone launch in
    # place, and a group is pipelined only behind another group
    assert stats["groups_in_place"] == groups
    assert stats["lone_in_place"] == stats["tasks"] - grouped
    assert 0 <= stats["groups_pipelined"] <= groups - 1
    assert ctx.statusz()["devices"][_module(ctx).index][
        "groups_in_place"] == groups
    want = np.linalg.cholesky(a0.astype(np.float64))
    for (i, j), t in tiles.items():
        ref = want[i * PNB:(i + 1) * PNB, j * PNB:(j + 1) * PNB]
        np.testing.assert_allclose(np.tril(t) if i == j else t, ref,
                                   rtol=0, atol=1e-4 * np.abs(want).max())


# -- a ready set of mixed classes ---------------------------------------------

PN, PNB = 256, 32                   # NT = 8: 8 + 28 + 28 + 56 = 120 tasks
PNT = PN // PNB
LOWER = [(i, j) for j in range(PNT) for i in range(j, PNT)]
CLASSES = {"POTRF": PNT, "TRSM": PNT * (PNT - 1) // 2,
           "SYRK": PNT * (PNT - 1) // 2,
           "GEMM": PNT * (PNT - 1) * (PNT - 2) // 6}


def _potrf_over(ctx, a0, in_place=True):
    """``build_potrf`` over the lower tiles of ``a0``, every body the
    plain one (POTRF's and TRSM's ``batch_hook`` solve by another route
    than the lone task, so their bits differ by design), with or without
    the bodies' ``donates``: the matrix, factored."""
    A = TiledMatrix(PN, PN, PNB, PNB, name="A",
                    dist=SymTwoDimBlockCyclic(1, 1, uplo="lower"))
    for i, j in LOWER:
        A.write_tile((i, j), a0[i * PNB:(i + 1) * PNB,
                                j * PNB:(j + 1) * PNB].copy())
    tp = build_potrf(A)
    for name in CLASSES:
        (chore,) = tp.task_class_by_name(name).incarnations
        assert chore.donates == (("T",) if name == "POTRF" else ("C",))
        chore.batch_hook = chore.batch_hook_shared = None
        if not in_place:
            chore.donates = None
    ctx.add_taskpool(tp)
    assert tp.wait_completed(120)
    return A


def _potrf(ctx, a0):
    """The factor's tiles, as ``_potrf_over`` leaves them."""
    A = _potrf_over(ctx, a0)
    return {k: np.asarray(A.data_of(k)) for k in LOWER}


@pytest.mark.parametrize("scheduler", ["lfq", "gd", "wfq"])
def test_a_mixed_ready_set_leaves_in_groups_by_class(
        make_ctx, rng, monkeypatch, scheduler):
    """Four classes ready together, none with a hand-batched form: a take
    holds across the change of class, every class with enough ready tasks
    leaves in groups, and the factor's bits are the lone path's."""
    ctx = make_ctx(scheduler=scheduler, **{"runtime.stage_timers": 1})
    # PTG bodies run on any module: keep the inline CPU module a last
    # resort, as the registry does beside a real accelerator
    ctx.devices.devices[0].weight = 0.01
    dev = _module(ctx)
    m = rng.standard_normal((PN, PN))
    a0 = (m @ m.T + PN * np.eye(PN)).astype(np.float32)
    seen = {PinsEvent.EXEC_BEGIN: [], PinsEvent.EXEC_END: []}
    hooks = {event: (lambda _es, task, uids=uids: uids.append(task.uid))
             for event, uids in seen.items()}
    for event, hook in hooks.items():
        ctx.pins.register(event, hook)
    tiles = _potrf(ctx, a0)
    for event, hook in hooks.items():
        ctx.pins.unregister(event, hook)
    for uids in seen.values():
        assert len(uids) == len(set(uids)) == sum(CLASSES.values())
    stats = dev.dump_statistics()
    launches = stats["launches_by_class"]
    assert stats["tasks_by_class"] == CLASSES
    assert launches["SYRK"] < CLASSES["SYRK"]
    assert launches["GEMM"] <= CLASSES["GEMM"] // 2
    assert sum(launches.values()) == \
        sum(CLASSES.values()) - stats["batched_tasks"] + stats["batches"]
    ends = _ends(ctx)
    assert ends["end_class"] == 0
    takes = ends["end_limit"] + ends["end_empty"]
    assert takes < ends["bins"] <= sum(launches.values())
    want = np.linalg.cholesky(a0.astype(np.float64))
    for (i, j), t in tiles.items():
        ref = want[i * PNB:(i + 1) * PNB, j * PNB:(j + 1) * PNB]
        np.testing.assert_allclose(np.tril(t) if i == j else t, ref,
                                   rtol=0, atol=1e-4 * np.abs(want).max())
    # a second step forms other groups and compiles nothing
    compiled = compile_cache.backend_compile_count()
    again = _potrf(ctx, a0)
    assert compile_cache.backend_compile_count() == compiled
    # groups off: the module launches every task alone, the same bits
    groups = _groups(ctx)
    monkeypatch.setattr(dev, "group_limit", lambda task, chore=None: 0)
    alone = _potrf(ctx, a0)
    assert _groups(ctx) == groups
    assert all(np.array_equal(tiles[k], alone[k]) and
               np.array_equal(again[k], alone[k]) for k in LOWER)
    assert all(d.load == 0.0 for d in ctx.devices.devices)


# -- forming the group --------------------------------------------------------

def _scale(x):
    return x * 2.0


def _shift(x):
    return x + 1.0


def _held_worker(ctx):
    """A pool whose one task keeps the (single) worker busy until the
    returned event is set: what is inserted meanwhile is all ready when
    the worker selects next."""
    go, tp = threading.Event(), dtd.Taskpool("gate")

    def gate():
        go.wait(30)

    ctx.add_taskpool(tp)
    tp.insert_task(gate, device=DeviceType.CPU)
    return go, tp


def _ends(ctx):
    """Why the takes ended and the bins they launched (stage timers on)."""
    return {why: sum(es.stats["group_" + why] for es in ctx.streams)
            for why in ("end_limit", "end_empty", "end_class", "bins")}


def _exec_order(ctx, middle):
    """Thirteen tiles by falling priority: six pure ``_scale`` of one
    pool, one ``_shift``, six ``_scale`` again. ``middle`` says what the
    ``_shift`` is: ``body`` another pure body of the same pool, ``impure``
    an impure one, ``pool`` a pure one of another pool; ``alone`` makes
    every task impure. Names in the order the tasks were announced
    (EXEC_BEGIN), and the tiles' final values."""
    x = TiledMatrix.from_array(np.full((13 * 8, 8), 3.0, np.float32), 8,
                               8, name="X")
    order = []

    def begin(_es, task):
        order.append(task.task_class.name)

    ctx.pins.register(PinsEvent.EXEC_BEGIN, begin)
    try:
        go, gate = _held_worker(ctx)
        tp = dtd.Taskpool("mixed")
        ctx.add_taskpool(tp)
        other = tp
        if middle == "pool":
            other = dtd.Taskpool("other")
            ctx.add_taskpool(other)
        for pool, fn, tiles, pure in (
                (tp, _scale, range(0, 6), middle != "alone"),
                (other, _shift, range(6, 7), middle in ("body", "pool")),
                (tp, _scale, range(7, 13), middle != "alone")):
            pool.insert_tasks(
                fn, [(dtd.TileArg(x, (i, 0), dtd.INOUT),) for i in tiles],
                priorities=[100 - i for i in tiles],
                device=DeviceType.TPU, pure=pure)
        go.set()
        for pool in {gate, tp, other}:
            assert _wait(pool) is None
    finally:
        ctx.pins.unregister(PinsEvent.EXEC_BEGIN, begin)
    return order[1:], x.to_array()[::8, 0]      # less the gate


@pytest.mark.parametrize("middle,ends_the_take", [
    ("body", False), ("impure", True), ("pool", True)])
def test_what_cannot_be_grouped_ends_the_take_and_runs_next(
        make_ctx, middle, ends_the_take):
    """Another body of the pool that has a group program is sorted into a
    bin of its own and the take goes on; a task that cannot be grouped
    (an impure body, another pool's task) ends it and runs next, in the
    scheduler's own order."""
    ctx = make_ctx(**{"runtime.stage_timers": 1})
    order, values = _exec_order(ctx, middle)
    groups, grouped = _groups(ctx)
    ends = _ends(ctx)
    # nothing has a group program: the scheduler's own order
    alone, values_alone = _exec_order(ctx, "alone")
    assert _groups(ctx) == (groups, grouped) and _ends(ctx) == ends
    assert alone == ["_scale"] * 6 + ["_shift"] + ["_scale"] * 6
    assert list(values) == list(values_alone) == [6.0] * 6 + [4.0] + [6.0] * 6
    if ends_the_take:
        # the twelve are split: a group of four and two alone on either
        # side of the one that went to the bypass slot
        assert order == alone
        assert (groups, grouped) == (2, 2 * SMALL)
        # the take it ended; another pool's task, taken first in its
        # turn, is ended by the next of the first pool
        assert ends["end_class"] == (2 if middle == "pool" else 1)
    else:
        # six, the other body, two more: the bin is full and leaves as
        # one launch before the lone task of the other bin
        assert order == ["_scale"] * BIG + ["_shift"] + ["_scale"] * SMALL
        assert (groups, grouped) == (2, BIG + SMALL)
        assert ends["end_class"] == 0 and ends["bins"] == 3
    assert ends["end_limit"] + ends["end_empty"] + ends["end_class"] >= 2


@pytest.mark.parametrize("scheduler", ["lfq", "gd"])
def test_a_cancelled_pools_tasks_inside_a_take_are_dropped(
        make_ctx, scheduler):
    """As in ``_worker_main``: a task of a cancelled pool that the
    scheduler still hands out is dropped by the take, which goes on."""
    ctx = make_ctx(scheduler=scheduler)
    x = TiledMatrix.from_array(np.full((15 * 8, 8), 3.0, np.float32), 8,
                               8, name="X")
    go, gate = _held_worker(ctx)
    tp, gone = dtd.Taskpool("kept"), dtd.Taskpool("gone")
    for pool in (tp, gone):
        ctx.add_taskpool(pool)
    for pool, tiles in ((tp, range(0, 6)), (gone, range(6, 9)),
                        (tp, range(9, 15))):
        pool.insert_tasks(
            _scale, [(dtd.TileArg(x, (i, 0), dtd.INOUT),) for i in tiles],
            priorities=[100 - i for i in tiles], device=DeviceType.TPU,
            pure=True)
    gone.cancel()
    go.set()
    assert _wait(gate) is None and _wait(tp) is None
    assert isinstance(gone.error, CancelledError) and \
        "cancelled" in str(_wait(gone))
    # the twelve that are left met in one take and the next: eight, four
    assert _groups(ctx) == (2, BIG + SMALL)
    assert _module(ctx).stats["tasks"] == 12
    assert list(x.to_array()[::8, 0]) == [6.0] * 6 + [3.0] * 3 + [6.0] * 6
    assert all(d.load == 0.0 for d in ctx.devices.devices)


def test_too_few_for_a_group_go_alone_once_the_turn_is_given_up(make_ctx):
    """A bin under the smallest size runs as tasks that were never taken,
    the turn given up; the body's first lone launch builds its programs."""
    ctx = make_ctx()
    turn = _module(ctx).group_turn
    x = TiledMatrix.from_array(np.ones((3 * 8, 8), np.float32), 8, 8,
                               name="X")
    held = []

    def begin(_es, task):
        if task.task_class.name == "_scale":
            held.append(turn.locked())      # the one worker's own turn

    ctx.pins.register(PinsEvent.EXEC_BEGIN, begin)
    tp = dtd.Taskpool("few")
    ctx.add_taskpool(tp)
    for _ in range(2):
        go, gate = _held_worker(ctx)
        tp.insert_tasks(_scale, [(dtd.TileArg(x, (i, 0), dtd.INOUT),)
                                 for i in range(3)],
                        device=DeviceType.TPU, pure=True)
        go.set()
        assert _wait(gate) is None
        tp.flush(x)             # the three ran; the pool stays open
    ctx.pins.unregister(PinsEvent.EXEC_BEGIN, begin)
    assert _wait(tp) is None
    assert held == [False] * 6
    assert _groups(ctx) == (0, 0) and (x.to_array() == 4.0).all()
    assert all(d.load == 0.0 for d in ctx.devices.devices)


def test_mixed_signatures_never_share_a_program(make_ctx):
    """One class, one body, tiles of two shapes: such members are taken
    together and launched apart."""
    ctx = make_ctx()
    small = TiledMatrix.from_array(np.ones((8 * 8, 8), np.float32), 8, 8,
                                   name="S")
    large = TiledMatrix.from_array(np.ones((8 * 16, 16), np.float32), 16,
                                   16, name="L")
    go, gate = _held_worker(ctx)
    tp = dtd.Taskpool("shapes")
    ctx.add_taskpool(tp)
    tp.insert_tasks(_scale, [(dtd.TileArg(mat, (i, 0), dtd.INOUT),)
                             for i in range(8) for mat in (small, large)],
                    device=DeviceType.TPU, pure=True)
    go.set()
    assert _wait(gate) is None and _wait(tp) is None
    assert _groups(ctx) == (0, 0) and _module(ctx).stats["tasks"] == 16
    assert (small.to_array() == 2.0).all() and (large.to_array() == 2.0).all()
    # apart, each shape fills its own groups
    for mat in (small, large):
        go, gate = _held_worker(ctx)
        tp = dtd.Taskpool("shape")
        ctx.add_taskpool(tp)
        tp.insert_tasks(_scale, [(dtd.TileArg(mat, (i, 0), dtd.INOUT),)
                                 for i in range(8)],
                        device=DeviceType.TPU, pure=True)
        go.set()
        assert _wait(gate) is None and _wait(tp) is None
    assert _groups(ctx) == (2, 16)
    assert (small.to_array() == 4.0).all() and (large.to_array() == 4.0).all()


@pytest.mark.parametrize("mib,want", [
    (12, (2, 16)),      # a GEMM of 1024-tiles: sixteen fit, launches of eight
    (32, (4, 16)),      # a SYRK of 2048-tiles: six fit, launches of four
    (48, (4, 16)),      # a GEMM of 2048-tiles: four fit, and no more
    (192, (0, 0))])     # a GEMM of 4096-tiles: every task alone
def test_a_group_is_held_to_its_bytes(make_ctx, monkeypatch, mib, want):
    """What a launch makes waits on the device for its members' release:
    a group's inputs may hold ``GROUP_BYTES`` at most. By proportion: the
    test's 256-byte tile stands for a task of ``mib`` MiB of inputs."""
    tile = 8 * 8 * 4
    limit = parsec_tpu.device.tpu.GROUP_BYTES * tile // (mib << 20)
    monkeypatch.setattr(parsec_tpu.device.tpu, "GROUP_BYTES", limit)
    ctx = make_ctx()
    x = TiledMatrix.from_array(np.ones((16 * 8, 8), np.float32), 8, 8,
                               name="X")
    go, gate = _held_worker(ctx)
    tp = dtd.Taskpool("bytes")
    ctx.add_taskpool(tp)
    tp.insert_tasks(_scale, [(dtd.TileArg(x, (i, 0), dtd.INOUT),)
                             for i in range(16)],
                    device=DeviceType.TPU, pure=True)
    go.set()
    assert _wait(gate) is None and _wait(tp) is None
    assert _groups(ctx) == want and _module(ctx).stats["tasks"] == 16
    assert all(d.load == 0.0 for d in ctx.devices.devices)
    assert (x.to_array() == 2.0).all()


@pytest.mark.parametrize("device,takes", [
    (DeviceType.CPU, 0), (DeviceType.ALL, 32)])
def test_a_module_without_group_programs_runs_them_one_by_one(
        make_ctx, rng, monkeypatch, device, takes):
    """A CPU body never enters the group path; a body for any module
    does, and the CPU module it lands on launches one task a time."""
    ctx = make_ctx()
    ctx.devices.devices[0].weight = 1e6     # whatever may, lands here
    entered = []
    group_progress = ctx._group_progress
    monkeypatch.setattr(ctx, "_group_progress", lambda es, task, chore: (
        entered.append(task), group_progress(es, task, chore)))
    (a_h, b_h, c_h), (a, b, c) = _matrices(rng)
    _gemm(ctx, a, b, c, device=device)
    assert len(entered) == takes
    assert _groups(ctx) == (0, 0)
    assert ctx.devices.devices[0].stats["tasks"] == 32
    assert all(d.load == 0.0 for d in ctx.devices.devices)
    np.testing.assert_allclose(c.to_array(), c_h + a_h @ b_h, rtol=1e-4,
                               atol=1e-4)


# -- failing, announcing, timing ----------------------------------------------

def _broken(x):
    raise ValueError("a body that cannot be traced")


def test_a_raising_group_aborts_the_pool_and_releases_every_load(make_ctx):
    ctx = make_ctx()
    x = TiledMatrix.from_array(np.ones((8 * 8, 8), np.float32), 8, 8,
                               name="X")
    go, gate = _held_worker(ctx)
    tp = dtd.Taskpool("broken")
    ctx.add_taskpool(tp)
    tp.insert_tasks(_broken, [(dtd.TileArg(x, (i, 0), dtd.INOUT),)
                              for i in range(8)],
                    device=DeviceType.TPU, pure=True)
    go.set()
    assert _wait(gate) is None
    raised = _wait(tp, timeout=60.0)            # no waiter hangs
    assert isinstance(tp.error, ValueError)
    assert raised is not None and "cannot be traced" in str(raised)
    assert all(d.load == 0.0 for d in ctx.devices.devices)
    # the context serves the next pool
    (_, _, c_h), (a, b, c) = _matrices(np.random.default_rng(1))
    _gemm(ctx, a, b, c)
    assert not np.array_equal(c.to_array(), c_h)


def test_every_task_is_announced_once_and_a_launch_has_one_span(
        make_ctx, rng, monkeypatch):
    made = []

    class Counting(context_mod.StageSpan):
        def __init__(self, name):
            made.append(name)
            super().__init__(name)

    for site in (context_mod, parsec_tpu.device.tpu):
        monkeypatch.setattr(site, "StageSpan", Counting)
    ctx = make_ctx(nb_cores=2, **{"runtime.stage_timers": 1})
    seen = {event: [] for event in (
        PinsEvent.PREPARE_INPUT_BEGIN, PinsEvent.PREPARE_INPUT_END,
        PinsEvent.EXEC_BEGIN, PinsEvent.EXEC_END)}
    hooks = {event: (lambda _es, task, uids=uids: uids.append(task.uid))
             for event, uids in seen.items()}
    for event, hook in hooks.items():
        ctx.pins.register(event, hook)
    _, (a, b, c) = _matrices(rng)
    _gemm(ctx, a, b, c)
    for event, hook in hooks.items():
        ctx.pins.unregister(event, hook)
    groups, grouped = _groups(ctx)
    assert grouped >= SMALL
    for uids in seen.values():
        assert len(uids) == len(set(uids)) == 32
    launches = 32 - grouped + groups
    assert made.count("parsec:exec") == launches
    assert made.count("parsec:dispatch") == 32
    assert made.count("parsec:release") == 32
    assert sum(es.stats["executed"] for es in ctx.streams) == 32


def _half(x):           # no other test's, so none has compiled its programs
    return x + 0.5


@pytest.mark.parametrize("first,body", [(16, _shift), (2, _half)])
def test_nothing_compiles_after_the_first_step(make_ctx, first, body):
    """Every size of the set, and the single path's program, are built
    the first time the module is handed tasks of a body, too few for a
    group as they may be: a later step that forms other sizes finds them
    all."""
    ctx = make_ctx()
    x = TiledMatrix.from_array(np.ones((16 * 8, 8), np.float32), 8, 8,
                               name="X")

    def step(tiles):
        go, gate = _held_worker(ctx)
        tp = dtd.Taskpool("step")
        ctx.add_taskpool(tp)
        tp.insert_tasks(body, [(dtd.TileArg(x, (i, 0), dtd.INOUT),)
                                for i in range(tiles)],
                        device=DeviceType.TPU, pure=True)
        go.set()
        assert _wait(gate) is None and _wait(tp) is None
        return _groups(ctx)

    groups, grouped = step(first)
    assert (groups, grouped) == ((2, 16) if first == 16 else (0, 0))
    compiled = compile_cache.backend_compile_count()
    assert step(7) == (groups + 1, grouped + 4)     # four, and three alone
    assert step(1) == (groups + 1, grouped + 4)
    assert step(16) == (groups + 3, grouped + 20)   # two launches of eight
    assert compile_cache.backend_compile_count() == compiled
    steps = np.array([first, 7, 1, 16])
    assert list(x.to_array()[::8, 0]) == \
        [1.0 + (body(0.0) * (steps > i)).sum() for i in range(16)]


# -- one route: one table, one staging rule -----------------------------------

def _third(x):          # no other test's: the cases below count its compiles
    return x / 3.0


class _CountingJax:
    """``jax`` as a module sees it, its ``device_put`` calls counted."""

    def __init__(self, jax):
        self._jax, self.puts = jax, 0

    def __getattr__(self, name):
        return getattr(self._jax, name)

    def device_put(self, *args, **kwargs):
        self.puts += 1
        return self._jax.device_put(*args, **kwargs)


def _placed_tile(where, dev):
    import jax
    import jax.numpy as jnp
    tile = np.full((8, 8), 3.0, np.float32)
    other = next(d for d in jax.devices() if d != dev)
    return {"host numpy": lambda: tile,
            "array here, uncommitted": lambda: jnp.asarray(tile),
            "committed here": lambda: jax.device_put(tile, dev),
            "committed to another device":
                lambda: jax.device_put(tile, other)}[where]()


@pytest.mark.parametrize("route,tasks", [("alone", 3), ("in a group", 8)])
@pytest.mark.parametrize("where,puts", [
    ("host numpy", 1), ("array here, uncommitted", 0),
    ("committed here", 0), ("committed to another device", 1)])
def test_one_staging_rule(make_ctx, where, puts, route, tasks):
    """Every route places a leaf by one rule: a ``jax.Array`` that is not
    committed to another chip passes as it is, no ``device_put`` called;
    a host value and a leaf committed elsewhere are put on the module's
    chip. So the result sits where the module is on both routes, and what
    the rule commits here is one argument pattern: one compile a body."""
    import jax
    ctx = make_ctx()
    dev = _module(ctx)
    assert jax.default_backend() == "cpu"
    x = TiledMatrix(tasks * 8, 8, 8, 8, name="X")

    def step(place):
        for i in range(tasks):
            x.write_tile((i, 0), _placed_tile(place, dev.jax_device))
        go, gate = _held_worker(ctx)
        tp = dtd.Taskpool("staged")
        ctx.add_taskpool(tp)
        tp.insert_tasks(_third, [(dtd.TileArg(x, (i, 0), dtd.INOUT),)
                                 for i in range(tasks)],
                        device=DeviceType.TPU, pure=True)
        go.set()
        assert _wait(gate) is None and _wait(tp) is None
        return [x.data_of((i, 0)) for i in range(tasks)]

    step("committed here")                  # the body's table is built
    groups = _groups(ctx)
    compiled = compile_cache.backend_compile_count()
    dev.jax = counting = _CountingJax(dev.jax)
    out = step(where)
    assert counting.puts == puts * tasks
    assert _groups(ctx) == (groups[0] + (tasks == 8),
                            groups[1] + tasks * (tasks == 8))
    one_pattern = where != "array here, uncommitted"
    if one_pattern:
        assert compile_cache.backend_compile_count() == compiled
    for tile in out:
        assert isinstance(tile, jax.Array)
        assert tile.devices() == {dev.jax_device}
        assert tile.committed == one_pattern
        assert (np.asarray(tile) == 1.0).all()


def _hooked(task, x):
    return x * 2.0


def _hooked_stacked(xs):
    _hooked_stacked.traced.append(xs.shape[0])
    return xs * 2.0


def test_a_batch_hook_body_goes_alone_from_the_table_and_stacks_at_its_first_group(
        make_ctx):
    """A body with a ``batch_hook``: its lone program is its plain hook,
    in the table from the first lone launch; the stacked programs, which
    no lone task runs, are built when its first group forms."""
    from parsec_tpu.core.task import Chore, Flow, FlowAccess, Task
    from parsec_tpu.core.taskpool import Taskpool
    ctx = make_ctx()
    dev = _module(ctx)
    tp = Taskpool("hooked")
    tc = tp.new_task_class("H", params=("i",),
                           flows=[Flow("x", FlowAccess.RW)])
    chore = Chore(DeviceType.TPU, _hooked, batch_hook=_hooked_stacked)
    tc.add_chore(chore)
    tp.context = ctx
    _hooked_stacked.traced = []

    def tasks(n):
        made = [Task(tp, tc, (i,)) for i in range(n)]
        for i, t in enumerate(made):
            t.data["x"] = np.full((8, 8), float(i), np.float32)
        return made

    def doubled(made):
        return all((np.asarray(t.output["x"]) == 2.0 * i).all()
                   for i, t in enumerate(made))

    compiled = compile_cache.backend_compile_count()
    (lone,) = tasks(1)
    dev.execute(None, lone, chore)
    assert doubled([lone]) and _hooked_stacked.traced == []
    assert compile_cache.backend_compile_count() == compiled + 1
    eight = tasks(BIG)
    # (how many, the bytes of new outputs the launch holds)
    assert dev.execute_group(None, eight, chore) == (BIG, BIG * 8 * 8 * 4)
    assert doubled(eight)
    assert sorted(_hooked_stacked.traced) == [SMALL, BIG]
    assert compile_cache.backend_compile_count() == compiled + 3
    # every later size finds its program
    for made in (tasks(1), tasks(SMALL), tasks(BIG)):
        if len(made) == 1:
            dev.execute(None, made[0], chore)
        else:
            assert dev.execute_group(None, made, chore) == (
                len(made), len(made) * 8 * 8 * 4)
        assert doubled(made)
    assert dev.execute_group(None, tasks(SMALL - 1), chore) == (0, 0)
    assert compile_cache.backend_compile_count() == compiled + 3
    assert _groups(ctx) == (3, 2 * BIG + SMALL)
    assert dev.stats["tasks"] == 2 + 2 * BIG + SMALL


def _impure(x):
    # a self-dispatching body gets its host values as they are
    assert isinstance(x, np.ndarray)
    return x + 1.0


@pytest.mark.parametrize("pure,body", [(True, _shift), (False, _impure)])
def test_a_warmed_pool_of_lone_tasks_constructs_no_chore(
        make_ctx, monkeypatch, pure, body):
    """Neither the table's route (a pure body) nor the pinned hook's (a
    body that dispatches itself) makes a ``Chore`` per task."""
    from parsec_tpu.core.task import Chore
    ctx = make_ctx()
    x = TiledMatrix.from_array(np.ones((3 * 8, 8), np.float32), 8, 8,
                               name="X")
    tp = dtd.Taskpool("lone")
    ctx.add_taskpool(tp)

    def step():
        go, gate = _held_worker(ctx)
        tp.insert_tasks(body, [(dtd.TileArg(x, (i, 0), dtd.INOUT),)
                               for i in range(3)],
                        device=DeviceType.TPU, pure=pure)
        go.set()
        assert _wait(gate) is None
        tp.flush(x)             # the three ran; the pool stays open

    step()
    made, init = [], Chore.__init__
    monkeypatch.setattr(Chore, "__init__", lambda self, *a, **kw: (
        made.append(a), init(self, *a, **kw))[1])
    step()
    assert _wait(tp) is None
    # the gate's class is new with its pool, and it alone
    assert [a[0] for a in made] == [DeviceType.CPU]
    assert _groups(ctx) == (0, 0) and _module(ctx).stats["tasks"] == 6
    assert (x.to_array() == 3.0).all()


def test_the_lone_launches_still_queued_hold_group_bytes_at_most(
        make_ctx, monkeypatch):
    """A launch of one is not waited for by the next, so its thread could
    run any distance ahead of a busy chip, every launch's outputs
    allocated as it is enqueued: the module keeps what the lone launches
    still queued have made within ``GROUP_BYTES`` (the thread that
    enqueues more waits for the oldest), and keeps none of it alive."""
    import weakref
    from parsec_tpu.core.task import Chore, Flow, FlowAccess, Task
    from parsec_tpu.core.taskpool import Taskpool
    tile = 8 * 8 * 4
    monkeypatch.setattr(parsec_tpu.device.tpu, "GROUP_BYTES", 3 * tile)
    ctx = make_ctx()
    dev = _module(ctx)
    tp = Taskpool("lone")
    tc = tp.new_task_class("H", params=("i",),
                           flows=[Flow("x", FlowAccess.RW)])
    chore = Chore(DeviceType.TPU, _hooked, batch_hook=_hooked_stacked)
    tc.add_chore(chore)
    tp.context = ctx
    seen = []
    for i in range(12):
        t = Task(tp, tc, (i,))
        t.data["x"] = np.full((8, 8), float(i), np.float32)
        dev.execute(None, t, chore)
        assert (np.asarray(t.output["x"]) == 2.0 * i).all()
        seen.append((dev._lone_bytes, len(dev._lone)))
    assert max(b for b, _ in seen) == 3 * tile
    assert [n for _, n in seen] == [1, 2] + [3] * 10
    # weakly: a tile nobody holds any more is done with, and is not kept
    assert all(isinstance(ref, weakref.ref) and ref() is None
               for ref, _n in list(dev._lone)[:-1])


# -- updates in place: what a launch holds decides the turn and the depth -----

def _tile_sized():
    import jax
    gc.collect()
    return sum(1 for a in jax.live_arrays() if a.shape == (PNB, PNB))


@pytest.mark.parametrize("scheduler", ["lfq", "gd", "wfq"])
def test_in_place_the_factor_is_the_same_and_each_tile_is_held_once(
        make_ctx, rng, scheduler):
    """``build_potrf``'s bodies name their RW flow as the last reading of
    its version: on four workers the factor is bit for bit what the same
    pool gives without the declarations, every task is announced once,
    and when the pool has ended the collection holds one live array a
    tile and nothing else of that shape is on the device."""
    ctx = make_ctx(nb_cores=4, scheduler=scheduler)
    ctx.devices.devices[0].weight = 0.01    # the chip's path
    m = rng.standard_normal((PN, PN))
    a0 = (m @ m.T + PN * np.eye(PN)).astype(np.float32)
    seen = {PinsEvent.EXEC_BEGIN: [], PinsEvent.EXEC_END: []}
    hooks = {event: (lambda _es, task, uids=uids: uids.append(task.uid))
             for event, uids in seen.items()}
    for event, hook in hooks.items():
        ctx.pins.register(event, hook)
    before = _tile_sized()
    A = _potrf_over(ctx, a0)
    for event, hook in hooks.items():
        ctx.pins.unregister(event, hook)
    for uids in seen.values():
        assert len(uids) == len(set(uids)) == sum(CLASSES.values())
    stats = _module(ctx).stats
    assert stats["groups_in_place"] == stats["batches"] >= 1
    assert stats["lone_in_place"] == stats["tasks"] - stats["batched_tasks"]
    held = {k: A.data_of(k) for k in LOWER}
    assert not any(t.is_deleted() for t in held.values())
    assert len({id(t) for t in held.values()}) == len(LOWER)
    del held
    assert _tile_sized() - before == len(LOWER)
    tiles = {k: np.asarray(A.data_of(k)) for k in LOWER}
    plain = _potrf_over(ctx, a0, in_place=False)
    assert all(np.array_equal(tiles[k], np.asarray(plain.data_of(k)))
               for k in LOWER)
    assert stats["groups_in_place"] < stats["batches"]
    assert all(d.load == 0.0 for d in ctx.devices.devices)


def _doubled(task, x):
    return x * 2.0


def _module_tasks(ctx, name, donates):
    """A class of one RW flow on the module's own interface: ``make(n)``
    gives n fresh tasks, each with a tile of its own."""
    from parsec_tpu.core.task import Chore, Flow, FlowAccess, Task
    from parsec_tpu.core.taskpool import Taskpool
    tp = Taskpool(name)
    tc = tp.new_task_class(name, params=("i",),
                           flows=[Flow("x", FlowAccess.RW)])
    chore = Chore(DeviceType.TPU, _doubled, donates=donates)
    tc.add_chore(chore)
    tp.context = ctx

    def make(n):
        tasks = [Task(tp, tc, (i,)) for i in range(n)]
        for i, t in enumerate(tasks):
            t.data["x"] = np.full((8, 8), float(i), np.float32)
        return tasks

    return tp, chore, make


def _waited_for(dev, monkeypatch):
    """What the module's launches wait for, in order."""
    waited, under = [], dev._under

    def watched(name, timed, fn, *args):
        if name == parsec_tpu.device.tpu.SPAN_EXEC_WAIT:
            waited.append(fn.__self__)
        return under(name, timed, fn, *args)

    monkeypatch.setattr(dev, "_under", watched)
    return waited


def test_the_third_in_place_group_waits_for_the_first_and_nothing_else(
        make_ctx, monkeypatch):
    """Two groups deep: an in-place group waits for the group before the
    last, so the chip has the next one queued while it works; a group
    that holds new outputs waits for the last group, whatever kind that
    was, and of such groups the module remembers only the last. (In
    place, a mark that is over when the module looks at it is not waited
    for, so what was waited for is at most what the rule names.)"""
    ctx = make_ctx()
    dev = _module(ctx)
    _tp, given, update = _module_tasks(ctx, "U", ("x",))
    _tp2, kept, fresh = _module_tasks(ctx, "F", None)
    tile = 8 * 8 * 4
    waited = _waited_for(dev, monkeypatch)
    marks = []
    for _ in range(3):
        assert dev.execute_group(None, update(SMALL), given) == (SMALL, 0)
        marks.append(dev._group_marks[-1])
    assert [m.shape for m in marks] == [(1,)] * 3      # the programs' own
    assert all(m is marks[0] for m in waited)
    assert dev._group_marks == marks[1:]
    # new outputs: the last group, and it alone is remembered
    made, before = fresh(SMALL), len(waited)
    assert dev.execute_group(None, made, kept) == (SMALL, SMALL * tile)
    assert waited[before:] == [marks[2]]
    assert dev._group_marks == [made[-1].output["x"]]
    # behind it an in-place group is queued at once, the next one waits
    # for it, and a group with new outputs for the one before itself
    before = len(waited)
    assert dev.execute_group(None, update(BIG), given) == (BIG, 0)
    assert len(waited) == before
    assert dev.execute_group(None, update(BIG), given) == (BIG, 0)
    assert all(m is made[-1].output["x"] for m in waited[before:])
    last, before = dev._group_marks[-1], len(waited)
    assert dev.execute_group(None, fresh(BIG), kept) == (BIG, BIG * tile)
    assert waited[before:] == [last]
    assert all((np.asarray(t.output["x"]) == 2.0 * i).all()
               for i, t in enumerate(made))


class _Mark:
    """A group's mark as the module asks it: over, or still queued."""

    def __init__(self, ready):
        self.ready, self.waits = ready, 0

    def is_deleted(self):
        return False

    def is_ready(self):
        return self.ready

    def block_until_ready(self):
        self.waits += 1
        self.ready = True


@pytest.mark.parametrize("in_place", [True, False],
                         ids=["in place", "new outputs"])
@pytest.mark.parametrize("over,waits", [
    # (the group before the last, the last) over?: waited for, if the
    # launch holds (nothing new, new outputs)
    ((False, False), ((1, 0), (0, 1))),
    ((True, False), ((0, 0), (0, 1))),
    ((True, True), ((0, 0), (0, 1)))],
    ids=["both queued", "the last queued", "the chip idle"])
def test_the_module_counts_how_often_the_rule_engages(
        make_ctx, in_place, over, waits):
    """Which of the last two groups a group waits for, by what it holds
    and what the chip has finished (in place, a wait that would return
    at once is not made; with new outputs today's rule stands, the wait
    for the last group a check); and the counters: ``lone_in_place``, ``groups_in_place``
    (launches that held nothing new), ``groups_pipelined`` (in-place
    groups called while the group before them was still on the chip's
    queue). In ``dump_statistics()`` and ``statusz()``, always on."""
    ctx = make_ctx()
    dev = _module(ctx)
    _tp, chore, make = _module_tasks(ctx, "C", ("x",) if in_place else None)
    (lone,) = make(1)
    dev.execute(None, lone, chore)
    older, last = (_Mark(ready) for ready in over)
    dev._group_marks = [older, last]
    n, new = dev.execute_group(None, make(SMALL), chore)
    assert n == SMALL and bool(new) != in_place
    assert (older.waits, last.waits) == waits[not in_place]
    assert dev._group_marks[:-1] == ([last] if in_place else [])
    stats = dev.dump_statistics()
    assert stats["lone_in_place"] == int(in_place)
    assert stats["groups_in_place"] == int(in_place)
    assert stats["groups_pipelined"] == int(in_place and not over[1])
    assert (stats["batches"], stats["tasks"]) == (1, 1 + SMALL)
    mine = ctx.statusz()["devices"][dev.index]
    assert all(mine[k] == stats[k] for k in (
        "lone_in_place", "groups_in_place", "groups_pipelined"))
    assert len(dev._lone) == (0 if in_place else 1)


def _updates(x, n, few=0):
    """A PTG pool over the tiles of ``x``: ``n`` independent updates
    written where they lie (class U), and ``few`` of a body that cannot
    be traced (class B), all ready when the pool starts."""
    tp = ptg.Taskpool("updates", X=x, N=n, FEW=few)

    def one_tile(name, space, at):
        return tp.task_class(
            name, params=("i",), space=space,
            affinity=lambda g, i: (g.X, (at(g, i), 0)),
            flows=[ptg.FlowSpec(
                "X", ptg.RW, tile=lambda g, i: (g.X, (at(g, i), 0)),
                ins=[ptg.In(data=lambda g, i: (g.X, (at(g, i), 0)))],
                outs=[ptg.Out(data=lambda g, i: (g.X, (at(g, i), 0)))])])

    U = one_tile("U", lambda g: ((i,) for i in range(g.N)),
                 lambda g, i: i)
    B = one_tile("B", lambda g: ((i,) for i in range(g.FEW)),
                 lambda g, i: g.N + i)

    @U.body(device=DeviceType.TPU, donates=("X",))
    def update(task, X):
        return X * 2.0

    @B.body(device=DeviceType.TPU, donates=("X",))
    def broken(task, X):
        raise ValueError("a body that cannot be traced")

    return tp


@pytest.mark.parametrize("what", ["a release", "a lone launch"])
def test_a_failure_after_the_turn_was_given_up_aborts_and_releases(
        make_ctx, what):
    """An in-place group's members are released with the turn free, and
    the bins too small for a group are launched after them: one of
    either that raises there leaves the pool aborted with its error,
    every load released, the turn free and the context serving."""
    ctx = make_ctx()
    dev = _module(ctx)
    few = 2 if what == "a lone launch" else 0
    x = TiledMatrix.from_array(np.ones(((BIG + few) * 8, 8), np.float32),
                               8, 8, name="X")
    tp = _updates(x, BIG, few)
    completed = []

    def complete(task):
        completed.append((task.locals, dev.group_turn.locked()))
        if what == "a release" and len(completed) == 3:
            raise ValueError("a release that raises")

    tp.task_class_by_name("U").on_complete = complete
    ctx.add_taskpool(tp)
    with pytest.raises(RuntimeError, match=(
            "release that raises" if what == "a release"
            else "cannot be traced")):
        tp.wait_completed(60.0)                 # no waiter hangs
    assert isinstance(tp.error, ValueError)
    # the eight left in ONE launch, which held nothing new; its members
    # were being released with the turn free when it happened
    assert _groups(ctx) == (1, BIG) and dev.stats["groups_in_place"] == 1
    assert len(completed) == (3 if what == "a release" else BIG)
    assert not any(locked for _locals, locked in completed)
    assert not dev.group_turn.locked()
    assert all(d.load == 0.0 for d in ctx.devices.devices)
    # the context serves the next pool
    y = TiledMatrix.from_array(np.ones((BIG * 8, 8), np.float32), 8, 8,
                               name="Y")
    again = _updates(y, BIG)
    ctx.add_taskpool(again)
    assert again.wait_completed(60.0) and (y.to_array() == 2.0).all()


def test_more_workers_than_cores_on_a_short_switch_interval(make_ctx, rng):
    """The releases of an in-place group run beside the next worker's
    take and call, and the module's marks pass from turn to turn: eight
    workers handed the interpreter every 10 µs factor the matrix three
    times over, every task once, the factor the lone path's bits, what
    the module counted whole."""
    import os
    import sys
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ctx = make_ctx(nb_cores=max(8, 2 * (os.cpu_count() or 1)))
        ctx.devices.devices[0].weight = 0.01    # the chip's path
        dev = _module(ctx)
        m = rng.standard_normal((PN, PN))
        a0 = (m @ m.T + PN * np.eye(PN)).astype(np.float32)
        runs = [_potrf(ctx, a0) for _ in range(3)]
    finally:
        sys.setswitchinterval(interval)
    stats = dev.dump_statistics()
    assert stats["tasks"] == 3 * sum(CLASSES.values())
    assert sum(es.stats["executed"] for es in ctx.streams) == stats["tasks"]
    assert stats["groups_in_place"] == stats["batches"]
    assert stats["lone_in_place"] == stats["tasks"] - stats["batched_tasks"]
    assert 0 <= stats["groups_pipelined"] < stats["batches"]
    assert not dev.group_turn.locked() and len(dev._group_marks) <= 2
    assert all(d.load == 0.0 for d in ctx.devices.devices)
    assert all(np.array_equal(runs[0][k], run[k])
               for run in runs[1:] for k in LOWER)
    want = np.linalg.cholesky(a0.astype(np.float64))
    for (i, j), t in runs[0].items():
        ref = want[i * PNB:(i + 1) * PNB, j * PNB:(j + 1) * PNB]
        np.testing.assert_allclose(np.tril(t) if i == j else t, ref,
                                   rtol=0, atol=1e-4 * np.abs(want).max())
