"""Group launch on the dynamic path: a worker that holds a ready
accelerator task whose chore offers a group program takes the ready tasks
of the same body it can select (``Context._take_group``) and the device
module issues them as one XLA program (``TPUDevice.execute_group``).
DTD GEMMs with accelerator-typed pure bodies on the CPU platform, Python
engine (the one a chip gets), one device module."""

import threading

import numpy as np
import pytest

import parsec_tpu as parsec
import parsec_tpu.device.tpu
from parsec_tpu import dtd
from parsec_tpu.algorithms.gemm import _gemm_dtd_body
from parsec_tpu.core import context as context_mod
from parsec_tpu.core.task import GROUP_SIZES, DeviceType
from parsec_tpu.data.matrix import TiledMatrix
from parsec_tpu.profiling.pins import PinsEvent
from parsec_tpu.utils import compile_cache, mca_param

# the `dry` sizes of the gemm_dtd_nb1024 cell: 32 tasks in 16 chains of 2
M, N, K, NB = 256, 256, 128, 64
BIG, SMALL = GROUP_SIZES[0], GROUP_SIZES[-1]
assert (BIG, SMALL) == (8, 4)       # the counts below are of these sizes


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


def _rows(a, b, c, m):
    """The tasks of one row of C tiles, as ``insert_gemm_dtd`` makes them."""
    one = dtd.ValueArg(1.0)
    return [(dtd.TileArg(a, (m, k), dtd.INPUT),
             dtd.TileArg(b, (k, n), dtd.INPUT),
             dtd.TileArg(c, (m, n), dtd.INOUT, affinity=True), one, one)
            for n in range(c.nt) for k in range(a.nt)]


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


def test_1024_chains_of_four_refill_the_groups(make_ctx, rng, monkeypatch):
    """The cell's DAG (4096 tasks in 1024 chains of 4, four workers) at
    8x8 tiles: the successors a group releases go to the worker's own
    queue, and the next group forms from them."""
    ctx = make_ctx(nb_cores=4)
    dev, out_of_turn = _module(ctx), []
    launch = dev.execute_group

    def in_turn(es, tasks, chore):
        # a module has one group in flight: the worker holds the turn
        # from taking the tasks to the last member's release
        out_of_turn.append(not dev.group_turn.locked())
        return launch(es, tasks, chore)

    monkeypatch.setattr(dev, "execute_group", in_turn)
    (a_h, b_h, c_h), (a, b, c) = _matrices(rng, 256, 256, 32, 8)
    _gemm(ctx, a, b, c)
    groups, grouped = _groups(ctx)
    assert _module(ctx).stats["tasks"] == 4096
    assert grouped > 0 and grouped / groups >= 4
    assert out_of_turn and not any(out_of_turn)
    np.testing.assert_allclose(c.to_array(), c_h + a_h @ b_h, rtol=1e-4,
                               atol=1e-4)


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


def _exec_order(ctx, pure):
    """Two bodies over 13 tiles, by falling priority: six of one, one of
    the other, six of the first again. Names in the order the tasks were
    announced (EXEC_BEGIN), and the tiles' final values."""
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
        for fn, tiles in ((_scale, range(0, 6)), (_shift, range(6, 7)),
                          (_scale, range(7, 13))):
            tp.insert_tasks(
                fn, [(dtd.TileArg(x, (i, 0), dtd.INOUT),) for i in tiles],
                priorities=[100 - i for i in tiles],
                device=DeviceType.TPU, pure=pure)
        go.set()
        assert _wait(gate) is None and _wait(tp) is None
    finally:
        ctx.pins.unregister(PinsEvent.EXEC_BEGIN, begin)
    return order[1:], x.to_array()[::8, 0]      # less the gate


def test_another_body_ends_the_group_and_runs_next(make_ctx):
    ctx = make_ctx()
    order, values = _exec_order(ctx, pure=True)
    groups, grouped = _groups(ctx)
    # impure bodies offer no group program: the scheduler's own order
    alone, values_alone = _exec_order(ctx, pure=False)
    assert _groups(ctx) == (groups, grouped)
    assert order == alone and sorted(order) == ["_scale"] * 12 + ["_shift"]
    assert list(values) == list(values_alone) == [6.0] * 6 + [4.0] + [6.0] * 6
    # the lone _shift split the twelve: no launch of eight, and a group
    # of four on either side of it where the scheduler kept them apart
    assert groups == grouped // SMALL and SMALL <= grouped <= 12


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


@pytest.mark.parametrize("members,want", [
    (8, (2, 16)),       # eight tiles fit: launches of eight
    (7, (4, 16)),       # the largest size that fits, again and again
    (3, (0, 0))])       # not even the smallest: every task alone
def test_a_group_is_held_to_its_bytes(make_ctx, monkeypatch, members, want):
    """What a launch makes waits on the device for its members' release:
    a group's inputs may hold ``GROUP_BYTES`` at most."""
    tile = 8 * 8 * 4
    monkeypatch.setattr(parsec_tpu.device.tpu, "GROUP_BYTES", members * tile)
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


def test_a_module_without_group_programs_runs_them_one_by_one(make_ctx, rng):
    ctx = make_ctx()
    (a_h, b_h, c_h), (a, b, c) = _matrices(rng)
    _gemm(ctx, a, b, c, device=DeviceType.CPU)
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


def test_nothing_compiles_after_the_first_step(make_ctx):
    """Every size of the set, and the single path's program, are built
    the first time a signature is seen: a later step that forms other
    sizes finds them all."""
    ctx = make_ctx()
    x = TiledMatrix.from_array(np.ones((16 * 8, 8), np.float32), 8, 8,
                               name="X")

    def step(tiles):
        go, gate = _held_worker(ctx)
        tp = dtd.Taskpool("step")
        ctx.add_taskpool(tp)
        tp.insert_tasks(_shift, [(dtd.TileArg(x, (i, 0), dtd.INOUT),)
                                 for i in range(tiles)],
                        device=DeviceType.TPU, pure=True)
        go.set()
        assert _wait(gate) is None and _wait(tp) is None
        return _groups(ctx)

    assert step(16) == (2, 16)              # two launches of eight
    compiled = compile_cache.backend_compile_count()
    assert step(7) == (3, 20)               # four, and three alone
    assert step(1) == (3, 20)
    assert step(16) == (5, 36)
    assert compile_cache.backend_compile_count() == compiled
    assert list(x.to_array()[::8, 0]) == [5.0] + [4.0] * 6 + [3.0] * 9
