"""DPLASMA ``dpotrf`` over four chip modules under one scheduler
(``testing_dpotrf -g 4``), on four of the suite's virtual CPU devices at
N=512, NB=64 (NT=8, 120 tasks): ``build_potrf`` through
``ctx.add_taskpool`` over ONE ``TiledMatrix`` whose lower tiles are
advised 2 x 2 over the modules (``advise_on_devices``) and lie there.
The rules of the multi-device path, each asserted on what the program
counts: a task runs on the module the tile it writes is advised to, so
the factor is the one-module factor bit for bit; a tile of another chip
is copied to a chip once per version, the least the graph allows, and the
counter counts every copy made; a copy goes with its version, with its
pool, and as the least recently read under ``REMOTE_BYTES``; with one
chip module nothing is looked up."""

import gc
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parsec_tpu as parsec
import parsec_tpu.device.tpu as tpu_mod
from parsec_tpu import dtd
from parsec_tpu.algorithms import build_potrf, insert_potrf_dtd
from parsec_tpu.core.task import DeviceType
from parsec_tpu.data.matrix import (SymTwoDimBlockCyclic, TiledMatrix,
                                    advise_on_devices)
from parsec_tpu.utils import mca_param

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import generate, ops_multidev  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402

MAN = Manifest(ROOT)
REF = MAN.reference("dpotrf_ptg_multidev_reference")
N, NB, GRID = 512, 64, (2, 2)
NT = N // NB
TILE = NB * NB * 4
LOWER = [(i, j) for j in range(NT) for i in range(j, NT)]
TASKS = NT + NT * (NT - 1) + NT * (NT - 1) * (NT - 2) // 6
assert TASKS == 120
LEAST = ops_multidev.potrf_min_remote_bytes(NT, NB, 4, GRID)
assert LEAST == 56 * TILE       # enumerated below, too

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs four (virtual) devices")


@pytest.fixture
def make_ctx(monkeypatch):
    made = []
    knobs = {"potrf.trsm_hook": "gemm", "runtime.native_dtd": 0}

    def make(chips, alone=True, **params):
        if alone:
            # every task a launch of one, as the cell's 4096-tiles are
            # (a grouped TRSM solves by the inverted triangle, a lone
            # one by substitution: grouping must agree to compare bits)
            monkeypatch.setattr(tpu_mod, "GROUP_BYTES", 0)
        knobs.update(params, **{"device.tpu.max_devices": chips})
        for knob, value in knobs.items():
            mca_param.set(knob, value)
        ctx = parsec.init(nb_cores=4)
        ctx.start()
        # as the registry does where a real accelerator is registered
        ctx.devices.devices[0].weight = 0.01
        made.append(ctx)
        return ctx

    yield make
    for ctx in made:
        parsec.fini(ctx)
    for knob in knobs:
        mca_param.unset(knob)


def _matrix(ctx, seed=7, step=1, advise=True):
    """The configuration's matrix at test size, advised over the
    context's chip modules and every tile on the chip it is advised to."""
    key = generate.step_key(seed, step)
    a0 = REF.dense_a0(key, N, NB)
    A = TiledMatrix(N, N, NB, NB, name="A",
                    dist=SymTwoDimBlockCyclic(1, 1, uplo="lower"))
    chips = ctx.devices.chips
    if advise:
        advise_on_devices(A, grid=GRID)
    for i, j in LOWER:
        home = chips[A.device_advice((i, j)) % len(chips)] if advise \
            else chips[0]
        A.write_tile((i, j), jax.device_put(jnp.asarray(
            a0[i * NB:(i + 1) * NB, j * NB:(j + 1) * NB], jnp.float32),
            home.jax_device))
    return A, key, a0


def _factor(ctx, A):
    tp = build_potrf(A)
    ctx.add_taskpool(tp)
    assert tp.wait_completed(300)
    return {k: A.data_of(k) for k in LOWER}


def _residual(key, tiles):
    """The multi-device reference's probe, every tile taken to one chip."""
    home = jax.devices()[0]
    with jax.default_matmul_precision("highest"):
        x = REF.probe_vectors(key, N)
        y, z, y2 = (jnp.zeros_like(x) for _ in range(3))
        for j in range(NT):
            y = REF.probe_input_row(j, key, x, y, n=N, nb=NB)
        for (i, j), t in tiles.items():
            z = REF.probe_factor_t(i, j, REF.on_probe_chip(t, home), x, z)
        for (i, j), t in tiles.items():
            y2 = REF.probe_factor(i, j, REF.on_probe_chip(t, home), z, y2)
        return REF.residual(y, y2)


def _remote(ctx):
    return {d.name: {k: d.stats[k] for k in (
        "remote_copies", "remote_bytes_in", "remote_hits")}
        for d in ctx.devices.chips}


def _summed(ctx, key):
    return sum(es.stats[key] for es in ctx.streams)


# -- (a), (b): placement follows the advice, and changes no bit ------------

def test_four_modules_give_the_one_module_factor_bit_for_bit(make_ctx):
    ctx4 = make_ctx(4)
    A4, key, _a0 = _matrix(ctx4)
    four = _factor(ctx4, A4)
    ctx1 = make_ctx(1)
    A1, _key, _a0 = _matrix(ctx1)
    one = _factor(ctx1, A1)
    for k in LOWER:
        np.testing.assert_array_equal(np.asarray(four[k]),
                                      np.asarray(one[k]), err_msg=str(k))
    limit = MAN.config("dpotrf_ptg_multidev")["correct"]["limit"]
    assert _residual(key, four) <= limit
    lone = [d.stats["batches"] for c in (ctx4, ctx1)
            for d in c.devices.chips]
    assert not any(lone), "every task of both runs was launched alone"


def test_every_task_ran_where_its_written_tile_is_advised(make_ctx):
    ctx = make_ctx(4)
    A, _key, _a0 = _matrix(ctx)
    tiles = _factor(ctx, A)
    chips = ctx.devices.chips
    assert [d.name for d in chips] == ["tpu0", "tpu1", "tpu2", "tpu3"]
    want = ops_multidev.potrf_tasks_by_chip(NT, GRID)
    assert want == {0: 30, 1: 20, 2: 30, 3: 40}
    assert {c: d.stats["tasks"] for c, d in enumerate(chips)} == want
    assert sum(d["tasks"] for d in ctx.devices.dump_statistics()) == TASKS
    assert _summed(ctx, "tasks_advised") == TASKS
    assert _summed(ctx, "tasks_on_advised") == TASKS
    for (i, j), t in tiles.items():
        home = chips[ops_multidev.chip_of(i, j, GRID)].jax_device
        assert t.devices() == {home}, (i, j)
    # in place on every chip, as on one: nothing new held by any launch
    assert all(d.stats["lone_in_place"] == d.stats["tasks"] for d in chips)


def test_grouped_launches_stay_on_their_tiles_module(make_ctx):
    """With groups (64-tiles go eight to a launch) a take leaves a task
    whose tile lies on another chip to that chip's module."""
    ctx = make_ctx(4, alone=False)
    ctx.set_stage_timers(True)          # group_end_* are counted
    A, key, _a0 = _matrix(ctx)
    tiles = _factor(ctx, A)
    want = ops_multidev.potrf_tasks_by_chip(NT, GRID)
    assert {c: d.stats["tasks"] for c, d in
            enumerate(ctx.devices.chips)} == want
    assert _summed(ctx, "tasks_on_advised") == TASKS
    assert _summed(ctx, "group_end_module") > 0
    assert _residual(key, tiles) <= 5.6e-6


def test_a_dtd_task_follows_its_first_written_argument(make_ctx):
    """``insert_potrf_dtd`` names no affinity: a task goes where the
    first tile it writes is advised."""
    ctx = make_ctx(4)
    A, key, _a0 = _matrix(ctx)
    tp = dtd.Taskpool("potrf_dtd")
    ctx.add_taskpool(tp)
    insert_potrf_dtd(tp, A)
    tp.wait(timeout=300)
    want = ops_multidev.potrf_tasks_by_chip(NT, GRID)
    assert {c: d.stats["tasks"] for c, d in
            enumerate(ctx.devices.chips)} == want
    tiles = {k: A.data_of(k) for k in LOWER}
    for (i, j), t in tiles.items():
        assert t.devices() == {ctx.devices.chips[
            ops_multidev.chip_of(i, j, GRID)].jax_device}, (i, j)
    assert _residual(key, tiles) <= 5.6e-6


def test_an_unadvised_collection_is_placed_by_load(make_ctx):
    ctx = make_ctx(4)
    A, key, _a0 = _matrix(ctx, advise=False)
    tiles = _factor(ctx, A)
    assert _summed(ctx, "tasks_advised") == 0
    assert sum(d.stats["tasks"] for d in ctx.devices.chips) == TASKS
    assert _residual(key, tiles) <= 5.6e-6


def test_a_host_tile_is_staged_on_the_chip_it_is_advised_to(make_ctx):
    """``stage_read`` among several chip modules: a host tile of an
    advised collection goes to its chip, committed; any other stays
    where JAX puts an uncommitted value."""
    ctx = make_ctx(4, **{"runtime.stage_reads": 1})
    chips = ctx.devices.chips
    X = advise_on_devices(TiledMatrix(2 * NB, 2 * NB, NB, NB, name="X"),
                          grid=GRID)
    Y = TiledMatrix(NB, NB, NB, NB, name="Y")
    for key in X.keys():
        staged = ctx.stage_read(X, key, np.ones((NB, NB), np.float32))
        home = chips[ops_multidev.chip_of(*key, GRID)].jax_device
        assert staged.committed and staged.devices() == {home}
        assert X.data_of(key) is staged
    staged = ctx.stage_read(Y, (0, 0), np.ones((NB, NB), np.float32))
    assert not staged.committed


# -- (c): a remote tile is copied to a chip once ---------------------------

def test_the_least_count_is_the_enumerated_one():
    """The benchmark's count against the graph written out by hand."""
    def chip(m, n):
        return (m % 2) * 2 + n % 2
    copies = 0
    for k in range(NT):
        readers = {chip(m, k) for m in range(k + 1, NT)}    # TRSM(m, k)
        copies += len(readers - {chip(k, k)})
        for m in range(k + 1, NT):
            readers = {chip(m, m)}                          # SYRK(m, k)
            for n in range(k + 1, m):
                readers.add(chip(m, n))                     # GEMM(m, n, k)
            for i in range(m + 1, NT):
                readers.add(chip(i, m))                     # GEMM(i, m, k)
            copies += len(readers - {chip(m, k)})
    assert copies * TILE == LEAST
    into = ops_multidev.potrf_min_remote_bytes_into(NT, NB, 4, GRID)
    assert sum(into.values()) == LEAST
    # the cell's traffic as its files state it
    assert ops_multidev.potrf_min_remote_bytes(
        24, 4096, 4, GRID) == 552 * 4096 * 4096 * 4
    assert ops_multidev.potrf_tasks_by_chip(24, GRID) == {
        0: 650, 1: 572, 2: 650, 3: 728}


def test_a_remote_tile_is_copied_to_a_chip_once(make_ctx):
    ctx = make_ctx(4)
    A, _key, _a0 = _matrix(ctx)
    _factor(ctx, A)
    remote = _remote(ctx)
    into = ops_multidev.potrf_min_remote_bytes_into(NT, NB, 4, GRID)
    assert {c: remote[f"tpu{c}"]["remote_bytes_in"] for c in into} == into
    assert sum(r["remote_bytes_in"] for r in remote.values()) == LEAST
    assert sum(r["remote_copies"] for r in remote.values()) == 56
    # every other read of another chip's tile was served by a copy
    reads = sum(len(chips) for chips in ops_multidev.potrf_remote_readers(
        NT, GRID).values())
    assert reads == 56
    hits = sum(r["remote_hits"] for r in remote.values())
    assert hits > 0
    assert all(d.stats["remote_copy_s"] > 0 for d in ctx.devices.chips)


def test_the_counter_counts_every_copy_made(make_ctx, monkeypatch):
    """The cache defeated by hand: every copy is let go as soon as it is
    made and made again, and the counter reads exactly double."""
    ctx = make_ctx(4)
    A, key, _a0 = _matrix(ctx)
    stock = tpu_mod.TPUDevice._copy_here
    one_at_a_time = threading.Lock()

    def twice(self, leaf, pool, used=None):
        with one_at_a_time:
            copy = self._copies.get(id(leaf))
            if copy is None or copy.source() is not leaf:
                stock(self, leaf, pool)
                with self._copies_lock:
                    self._let_go(id(leaf))
            return stock(self, leaf, pool, used)

    monkeypatch.setattr(tpu_mod.TPUDevice, "_copy_here", twice)
    tiles = _factor(ctx, A)
    remote = _remote(ctx)
    assert sum(r["remote_bytes_in"] for r in remote.values()) == 2 * LEAST
    assert sum(r["remote_copies"] for r in remote.values()) == 2 * 56
    assert _residual(key, tiles) <= 5.6e-6


def test_readers_of_one_tile_on_one_chip_wait_for_one_copy(
        make_ctx, monkeypatch):
    """Eight threads read one tile of another chip at once: one copy is
    made, outside the module's lock (a reader of ANOTHER tile is served
    meanwhile), and the others wait for it."""
    ctx = make_ctx(4)
    src, dst = ctx.devices.chips[0], ctx.devices.chips[1]
    tile = jax.device_put(jnp.ones((NB, NB), jnp.float32), src.jax_device)
    other = jax.device_put(jnp.zeros((NB, NB), jnp.float32), src.jax_device)
    pool = build_potrf(_matrix(ctx)[0])
    pool.context = ctx
    slow, put = threading.Event(), jax.device_put

    def slow_put(leaf, device):
        if leaf is tile:
            slow.wait(10)
        return put(leaf, device)

    monkeypatch.setattr(dst.jax, "device_put", slow_put)
    got = []
    readers = [threading.Thread(
        target=lambda: got.append(dst._copy_here(tile, pool)))
        for _ in range(8)]
    for t in readers:
        t.start()
    time.sleep(0.2)
    assert not got                      # all eight stand at the one copy
    assert dst._copy_here(other, pool).devices() == {dst.jax_device}
    slow.set()
    for t in readers:
        t.join(10)
    monkeypatch.undo()
    assert len(got) == 8 and all(g is got[0] for g in got)
    assert got[0].devices() == {dst.jax_device}
    assert dst.stats["remote_copies"] == 2      # tile, other
    assert dst.stats["remote_hits"] == 7
    assert dst.copies_held() == (2, 2 * TILE)
    dst.drop_copies(pool)
    assert dst.copies_held() == (0, 0)


def test_under_a_small_bound_the_least_recently_read_copy_goes(
        make_ctx, monkeypatch):
    """Three tiles of copies a chip: more copies are made, never more
    held but what launches are reading at that moment, and the factor is
    the same bit for bit."""
    ctx = make_ctx(4)
    A, _key, _a0 = _matrix(ctx)
    whole = {k: np.asarray(t) for k, t in _factor(ctx, A).items()}
    least = sum(r["remote_bytes_in"] for r in _remote(ctx).values())
    monkeypatch.setattr(tpu_mod, "REMOTE_BYTES", 3 * TILE)
    held = []
    stock = tpu_mod.TPUDevice._room_for

    def watched(self, nbytes, timed):
        stock(self, nbytes, timed)
        within = self._copy_bytes + nbytes <= tpu_mod.REMOTE_BYTES
        held.append(within or (not self._retired and all(
            c.pins for c in self._copies.values())))

    monkeypatch.setattr(tpu_mod.TPUDevice, "_room_for", watched)
    A2, _key, _a0 = _matrix(ctx)
    bounded = _factor(ctx, A2)
    again = sum(r["remote_bytes_in"] for r in _remote(ctx).values()) - least
    assert again > least == LEAST
    assert held and all(held)
    assert max(d.copies_held()[1] for d in ctx.devices.chips) == 0
    for k in LOWER:
        np.testing.assert_array_equal(np.asarray(bounded[k]), whole[k])


# -- (d): a copy goes with its version and with its pool -------------------

def test_a_finished_pool_leaves_no_copy_on_any_module(make_ctx):
    ctx = make_ctx(4)
    A, _key, _a0 = _matrix(ctx)
    seen = []
    stock = tpu_mod.TPUDevice.drop_copies

    def watched(self, pool):
        seen.append(len(self._copies))
        stock(self, pool)

    tpu_mod.TPUDevice.drop_copies = watched
    try:
        _factor(ctx, A)
    finally:
        tpu_mod.TPUDevice.drop_copies = stock
    assert sum(seen) == 56              # all were there when it ended
    assert [d.copies_held() for d in ctx.devices.chips] == [(0, 0)] * 4


def test_a_superseded_versions_copy_is_gone(make_ctx):
    """A reader on another chip than the tile's has a copy made; a new
    version written to the collection, and the old one dropped, takes
    the copy away while the pool is still open."""
    ctx = make_ctx(4)
    chips = ctx.devices.chips
    X = TiledMatrix(2 * NB, NB, NB, NB, name="X")
    advise_on_devices(X, grid=(2, 1))       # (0, 0) -> tpu0, (1, 0) -> tpu1
    x0 = jax.device_put(jnp.ones((NB, NB), jnp.float32), chips[0].jax_device)
    X.write_tile((0, 0), x0)
    X.write_tile((1, 0), jax.device_put(jnp.zeros((NB, NB), jnp.float32),
                                        chips[1].jax_device))
    tp = dtd.Taskpool("reader")
    ctx.add_taskpool(tp)

    def add(src, dst):
        return src + dst

    def read_once():
        tp.insert_task(add, dtd.TileArg(X, (0, 0), dtd.INPUT),
                       dtd.TileArg(X, (1, 0), dtd.INOUT),
                       device=DeviceType.TPU, pure=True)
        tp.flush()

    read_once()
    read_once()
    assert chips[1].stats["remote_copies"] == 1
    assert chips[1].stats["remote_hits"] == 1
    assert chips[1].copies_held() == (1, TILE)
    # the version is superseded: a new array in the collection, the old
    # one dropped by its last holder
    X.write_tile((0, 0), jax.device_put(
        jnp.full((NB, NB), 2.0, jnp.float32), chips[0].jax_device))
    del x0
    gc.collect()
    assert chips[1].copies_held() == (0, 0)
    read_once()
    assert chips[1].stats["remote_copies"] == 2
    np.testing.assert_array_equal(np.asarray(X.data_of((1, 0))),
                                  np.full((NB, NB), 4.0, np.float32))
    assert X.data_of((1, 0)).devices() == {chips[1].jax_device}
    tp.wait(timeout=60)
    assert chips[1].copies_held() == (0, 0)


# -- (e): one chip module, no look-up --------------------------------------

def test_with_one_module_no_advice_is_looked_up(make_ctx):
    ctx = make_ctx(1)
    A, key, _a0 = _matrix(ctx)          # advised all the same
    tiles = _factor(ctx, A)
    assert ctx.devices.advice_lookups == 0
    assert _summed(ctx, "tasks_advised") == 0
    assert _remote(ctx) == {"tpu0": {
        "remote_copies": 0, "remote_bytes_in": 0, "remote_hits": 0}}
    assert _residual(key, tiles) <= 5.6e-6
    # and with four, the rule is asked
    ctx4 = make_ctx(4)
    A4, _key, _a0 = _matrix(ctx4)
    _factor(ctx4, A4)
    assert ctx4.devices.advice_lookups >= TASKS


def test_statusz_shows_the_remote_counters(make_ctx):
    ctx = make_ctx(4)
    A, _key, _a0 = _matrix(ctx)
    _factor(ctx, A)
    devices = {d["name"]: d for d in ctx.statusz()["devices"]}
    for name in ("tpu0", "tpu1", "tpu2", "tpu3"):
        for key in ("remote_copies", "remote_bytes_in", "remote_hits",
                    "remote_copy_s"):
            assert key in devices[name], (name, key)
    assert sum(devices[f"tpu{c}"]["remote_bytes_in"]
               for c in range(4)) == LEAST
