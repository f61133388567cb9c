"""What a taskpool still refers to once it has terminated (PR 34).

A finished pool refers to none of its user's data, and nothing in the
runtime keeps the pool beyond what can still address it: with the
garbage collector off, the collection a user drops after ``wait()`` and
the pool itself are freed by reference count. The cell that showed it is
the DTD GEMM's: a product's C stayed on the chip through the whole of
the next product.
"""

import gc
import threading
import time
import weakref

import numpy as np
import pytest

import parsec_tpu as parsec
from parsec_tpu import _native
from parsec_tpu.algorithms import build_potrf, insert_gemm_dtd
from parsec_tpu.comm.local import LocalCommEngine
from parsec_tpu.core import context as ctx_mod
from parsec_tpu.data import TiledMatrix
from parsec_tpu.dsl import dtd
from parsec_tpu.utils import mca_param

from conftest import spd_matrix

NB = 16


@pytest.fixture
def no_collector():
    """The collector off: what dies in the test dies by reference count."""
    gc.collect()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()


@pytest.fixture
def engine_ctx():
    made = []

    def make(engine):
        mca_param.set("runtime.native_dtd", engine)
        ctx = parsec.init(nb_cores=4)
        ctx.start()
        made.append(ctx)
        return ctx

    yield make
    for ctx in made:
        parsec.fini(ctx)
    mca_param.unset("runtime.native_dtd")


def _dead(*refs, timeout=5.0):
    """Every weak reference dead; a worker may hold the last task it ran
    for the moment it takes to come back to its loop."""
    deadline = time.monotonic() + timeout
    while any(r() is not None for r in refs):
        if time.monotonic() > deadline:
            return False
        time.sleep(0.005)
    return True


def _tiled(rng, m, n, name):
    return TiledMatrix.from_array(
        rng.standard_normal((m, n)).astype(np.float32), NB, NB, name=name)


def _gemm(ctx, rng, A, B, name="gemm", flush_all=False, wait=True):
    """One product into a fresh C by a fresh pool: the pool, C, weak
    references to the pool, to C and to one of C's result tiles, and
    what C has to read."""
    C = _tiled(rng, A.m, B.n, "C")
    want = C.to_array() + A.to_array() @ B.to_array()
    tp = dtd.Taskpool(name)
    ctx.add_taskpool(tp)
    insert_gemm_dtd(tp, A, B, C)
    if flush_all:
        tp.flush_all(C)
    if not wait:
        return tp, C, (), want
    assert tp.wait(timeout=60.0)
    return tp, C, (weakref.ref(tp), weakref.ref(C),
                   weakref.ref(C.data_of((0, 0)))), want


def _dtd_case(ctx, rng, flush_all=False, second=False, native=False):
    A, B = _tiled(rng, 4 * NB, 2 * NB, "A"), _tiled(rng, 2 * NB, 4 * NB, "B")
    tp, C, refs, want = _gemm(ctx, rng, A, B, flush_all=flush_all)
    assert (tp._native is not None) == native
    # the bank holds nothing once the pool has ended, whoever holds the
    # pool; what a flush did not take out, the end did
    assert tp.tiles.all() == []
    tracked = C.mt * C.nt + A.mt * A.nt + B.mt * B.nt
    assert tp.tiles.retired + tp.tiles.dropped == tracked
    assert tp.tiles.retired == (C.mt * C.nt if flush_all else 0)
    assert ctx.find_taskpool("gemm", active_only=False) is tp
    tp2 = None
    if second:
        # the next product's pool, under the same name, still open
        del tp
        tp2, C2, _, _ = _gemm(ctx, rng, A, B, wait=False)
        assert ctx.find_taskpool("gemm", active_only=False) is tp2
    else:
        # the caller's drop of the pool alone frees the pool; C stays its
        # caller's, whole
        del tp
        assert _dead(refs[0])
        assert refs[1]() is C
        assert ctx.find_taskpool("gemm", active_only=False) is None
    got = C.to_array()
    del C
    assert _dead(*refs)
    if tp2 is not None:
        assert tp2.wait(timeout=60.0)
    return got, want


def _ptg_case(ctx, rng):
    A = TiledMatrix.from_array(spd_matrix(rng, 4 * NB), NB, NB, name="A")
    tp = build_potrf(A)
    ctx.add_taskpool(tp)
    assert ctx.wait(timeout=60.0)
    refs = (weakref.ref(tp), weakref.ref(A), weakref.ref(A.data_of((1, 0))))
    tc = tp.task_class_by_name("GEMM")
    assert tc.tp is tp and ctx.find_taskpool(tp.name, active_only=False) is tp
    del tp
    assert _dead(refs[0])
    # a class kept without its pool says so instead of failing somewhere
    with pytest.raises(ReferenceError, match="hold the taskpool"):
        tc.tp
    del tc
    L = np.tril(A.to_array())
    del A
    assert _dead(*refs)
    return L


@pytest.mark.parametrize("case", [
    "dtd", "dtd_flush_all", "dtd_same_name_next_pool_open", "dtd_native",
    "ptg_potrf"])
def test_a_finished_pool_and_the_matrix_its_user_drops_are_freed_by_count(
        engine_ctx, no_collector, rng, case):
    native = case == "dtd_native"
    if native and not _native.available():
        pytest.skip("native core unavailable")
    ctx = engine_ctx(1 if native else 0)
    before = ctx.statusz()["taskpools"]
    if case == "ptg_potrf":
        L = _ptg_case(ctx, rng)
        assert np.isfinite(L).all() and (np.diag(L) > 0).all()
        pools = 1
    else:
        got, want = _dtd_case(
            ctx, rng, flush_all=case == "dtd_flush_all",
            second=case == "dtd_same_name_next_pool_open", native=native)
        pools = 2 if case == "dtd_same_name_next_pool_open" else 1
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
    # the Context counts what ended and refers to none of it (the thread
    # that ended the last pool may be on its way out of it still)
    deadline = time.monotonic() + 5.0
    while ctx.statusz()["taskpools"]["referenced"] and \
            time.monotonic() < deadline:
        time.sleep(0.005)
    after = ctx.statusz()["taskpools"]
    assert after["added"] - before["added"] == pools
    assert after["terminated"] - before["terminated"] == pools
    assert after["referenced"] == 0


def test_the_tiles_let_go_at_the_end_are_counted_under_the_stage_timers(
        engine_ctx, rng):
    ctx = engine_ctx(0)
    A, B = _tiled(rng, 2 * NB, NB, "A"), _tiled(rng, NB, 2 * NB, "B")
    tp, C, _, _ = _gemm(ctx, rng, A, B)
    assert tp.tiles.dropped == 8 and ctx.dtd_counters == {}
    ctx.set_stage_timers(True)
    for flush_all in (False, True):
        _gemm(ctx, rng, A, B, flush_all=flush_all)
    ctx.set_stage_timers(False)
    total = dict(ctx.dtd_counters)
    assert total["dtd_tiles_dropped_at_end"] == 8 + 4
    assert total["dtd_tiles_flushed"] == 4
    assert total["dtd_tiles_tracked_peak"] == 8
    assert total["dtd_insert_calls"] == 2 * 4 and total["dtd_insert_s"] > 0


class _Vec:
    """Scalar-tile collection distributed round-robin by index
    (``tests/test_dtd_dist.py``'s)."""

    def __init__(self, n, nb_ranks, my_rank, dc_id):
        self.nb_ranks, self.my_rank, self.dc_id = nb_ranks, my_rank, dc_id
        self.v = {i: np.float32(0.0) for i in range(n)}

    def rank_of(self, key):
        return key[0] % self.nb_ranks

    def data_of(self, key):
        return self.v[key[0]]

    def write_tile(self, key, value):
        self.v[key[0]] = value


def test_across_ranks_the_bank_stays_until_the_flush_has_sent_it_home(
        no_collector):
    """Two ranks pass one datum back and forth; its last version ends on
    rank 1, away from its owner. The pools end holding their banks (the
    late write-back finds its collection there, through the pool found
    by name), the collective flush sends the version home, and then the
    pools hold nothing and die with their callers' references."""
    nb_ranks, n_steps = 2, 8
    engines = LocalCommEngine.make_fabric(nb_ranks)
    ctxs = [ctx_mod.init(nb_cores=2, comm=engines[r])
            for r in range(nb_ranks)]
    results, errors = [None] * nb_ranks, []
    both_ended = threading.Barrier(nb_ranks)

    def scenario(rank, ctx):
        P = _Vec(n_steps, nb_ranks, rank, dc_id=21)
        A = _Vec(1, nb_ranks, rank, dc_id=22)
        tp = dtd.Taskpool("xchain")
        ctx.add_taskpool(tp)
        for k in range(n_steps):
            tp.insert_task(lambda p, x: x + 1,
                           dtd.TileArg(P, (k,), dtd.INPUT, affinity=True),
                           dtd.TileArg(A, (0,), dtd.INOUT))
        tp.wait()
        held = len(tp.tiles.all())
        found = ctx.find_taskpool("xchain", active_only=False) is tp
        stale = float(A.v[0])
        both_ended.wait(timeout=30.0)
        tp.flush(A)
        left = [t.collection for t in tp.tiles.all()]
        refs = (weakref.ref(tp), weakref.ref(A))
        final = float(A.v[0])
        del tp, A
        return held, found, stale, final, left == [P] * n_steps, \
            _dead(*refs)

    def worker(r):
        try:
            results[r] = scenario(r, ctxs[r])
        except BaseException as exc:  # noqa: BLE001
            import traceback
            errors.append((r, exc, traceback.format_exc()))

    threads = [threading.Thread(target=worker, args=(r,))
               for r in range(nb_ranks)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
    gone = [c.find_taskpool("xchain", active_only=False) for c in ctxs]
    for c in ctxs:
        c.fini()
    assert not errors, errors[0][2]
    for rank, (held, found, stale, final, left_p, dead) in enumerate(results):
        assert held == 1 + n_steps and found, rank
        assert left_p and dead, rank
    # the owner's copy was stale until the flush's message reached the
    # pool that had ended there
    assert results[0][2] < n_steps and results[0][3] == float(n_steps)
    assert gone == [None, None]
