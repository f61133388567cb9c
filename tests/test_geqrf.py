"""Tiled QR tests (BASELINE 'PTG dgeqrf' config): the four compact-WY
kernels against their equations, checker validation, the taskpool on the
host runtime and on the compiled executors against ``numpy.linalg.qr``
through the benchmark's plain reference (``apply_q`` from V and T)."""

import os
import sys

import numpy as np
import pytest

import parsec_tpu as parsec
from parsec_tpu.algorithms.geqrf import (build_geqrf, geqrf_flops,
                                         geqrf_t_collection)
from parsec_tpu.data import TiledMatrix
from parsec_tpu.dsl import ptg
from parsec_tpu.ops import tile_kernels
from parsec_tpu.ops.tile_kernels import (geqrt_tile, tsmqr_tile, tsqrt_tile,
                                         unmqr_tile)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture(scope="module")
def ref():
    """The benchmark's plain reference of the factored form."""
    from benchmark.manifest import Manifest
    return Manifest(ROOT).reference("dgeqrf_ptg_host_reference")


@pytest.fixture
def make_ctx():
    """Contexts with one chip module (tests/test_group_launch.py's)."""
    from parsec_tpu.utils import mca_param
    made = []
    mca_param.set("device.tpu.max_devices", 1)

    def make(nb_cores=1):
        ctx = parsec.init(nb_cores=nb_cores)
        ctx.start()
        made.append(ctx)
        return ctx

    yield make
    for ctx in made:
        parsec.fini(ctx)
    mca_param.unset("device.tpu.max_devices")


@pytest.fixture
def small_base(monkeypatch):
    """Panels of the test's 64-tiles split as the chip's 2048-tiles do."""
    monkeypatch.setattr(tile_kernels, "_QR_BASE", 16)


def _seeded(shape, seed=7):
    return np.random.default_rng(seed).uniform(
        -0.5, 0.5, shape).astype(np.float32)


def _blocks(V, T, ib):
    """The block reflectors ``(V_j, T_j)`` of explicit V (m x nb) and T
    (ib x nb), as float64."""
    return [(np.asarray(V[:, o:o + ib], np.float64),
             np.asarray(T[:, o:o + ib], np.float64))
            for o in range(0, V.shape[1], ib)]


def _check_reflectors(blocks):
    """T upper triangular, T^-1 + T^-T = V^T V (as T + T^T = T^T V^T V T:
    the last reflector of a square block has nothing under its diagonal
    and LAPACK gives it tau = 0); returns Q = Q_1 ... Q_p, which has to
    be orthogonal."""
    m = blocks[0][0].shape[0]
    Q = np.eye(m)
    for Vj, Tj in blocks:
        assert np.array_equal(np.tril(Tj, -1), np.zeros_like(Tj))
        np.testing.assert_allclose(Tj + Tj.T, Tj.T @ (Vj.T @ Vj) @ Tj,
                                   atol=2e-5)
        Q = Q @ (np.eye(m) - Vj @ Tj @ Vj.T)
    np.testing.assert_allclose(Q.T @ Q, np.eye(m), atol=1e-5)
    return Q


def _geqrt_v(packed, ib):
    """GEQRT's V made explicit: unit lower, block j's rows from j*ib."""
    V = np.tril(np.asarray(packed, np.float64), -1) + np.eye(len(packed))
    for o in range(0, len(packed), ib):
        V[:o, o:o + ib] = 0.0
    return V


@pytest.mark.parametrize("ib", [64, 32, 16])
def test_geqrt_unmqr_tiles_against_the_equations(small_base, ib):
    nb = 64
    A = _seeded((nb, nb))
    packed, T = geqrt_tile(A, ib)
    assert packed.shape == (nb, nb) and T.shape == (ib, nb)
    Q = _check_reflectors(_blocks(_geqrt_v(packed, ib), T, ib))
    np.testing.assert_allclose(Q @ np.triu(packed), A, atol=1e-5)
    C = _seeded((nb, nb), 8)
    np.testing.assert_allclose(unmqr_tile(packed, T, C), Q.T @ C, atol=1e-5)
    # what the diagonal tile holds on and above its diagonal is R, not V
    junk = np.asarray(packed) + np.triu(np.ones((nb, nb), np.float32))
    assert np.array_equal(unmqr_tile(junk, T, C), unmqr_tile(packed, T, C))


@pytest.mark.parametrize("ib", [64, 32, 16])
def test_tsqrt_tsmqr_tiles_against_the_equations(small_base, ib):
    nb = 64
    A1, _ = geqrt_tile(_seeded((nb, nb)), ib)
    A2 = _seeded((nb, nb), 9)
    out1, V2, T = tsqrt_tile(A1, A2, ib)
    assert V2.shape == (nb, nb) and T.shape == (ib, nb)
    # GEQRT's V under the diagonal stays; the upper triangle is R'
    assert np.array_equal(np.tril(out1, -1), np.tril(A1, -1))
    Q = _check_reflectors(_blocks(np.vstack([np.eye(nb), V2]), T, ib))
    stacked = np.vstack([np.triu(A1), A2])
    np.testing.assert_allclose(
        Q.T @ stacked, np.vstack([np.triu(out1), np.zeros((nb, nb))]),
        atol=1e-5)
    C1, C2 = _seeded((nb, nb), 10), _seeded((nb, nb), 11)
    o1, o2 = tsmqr_tile(V2, T, C1, C2)
    np.testing.assert_allclose(np.vstack([o1, o2]),
                               Q.T @ np.vstack([C1, C2]), atol=1e-5)


@pytest.mark.parametrize("cond", [1e3, 1e6])
def test_geqrt_tile_of_an_ill_conditioned_tile(cond):
    """A diagonal tile with kappa far over eps^-1/2 factors to rounding
    level: its nearly square block goes through Householder QR, the tall
    ones through shifted Cholesky-QR with the signs of the
    reconstruction chosen pivot by pivot."""
    nb = 64
    rng = np.random.default_rng(3)
    U, _ = np.linalg.qr(rng.standard_normal((nb, nb)))
    W, _ = np.linalg.qr(rng.standard_normal((nb, nb)))
    A = ((U * np.logspace(0, -np.log10(cond), nb)) @ W.T).astype(np.float32)
    for tile, ib in ((A, nb), (-A, nb), (A[:, ::-1], 16), (A, 16)):
        packed, T = geqrt_tile(tile, ib)
        Q = _check_reflectors(_blocks(_geqrt_v(packed, ib), T, ib))
        np.testing.assert_allclose(Q @ np.triu(packed), tile, atol=2e-6)


@pytest.mark.parametrize("tile", [np.zeros((32, 32), np.float32),
                                  np.eye(32, dtype=np.float32),
                                  np.triu(np.ones((32, 32), np.float32))])
def test_geqrt_tile_of_a_tile_with_nothing_to_eliminate(tile):
    """Zeros, the identity, an upper triangle: every tau is 0, nothing is
    divided by it, and Q R is the tile."""
    packed, T = geqrt_tile(tile, 32)
    assert np.isfinite(packed).all() and np.isfinite(T).all()
    Q = _check_reflectors(_blocks(_geqrt_v(packed, 32), T, 32))
    np.testing.assert_allclose(Q @ np.triu(packed), tile, atol=1e-6)


def test_geqrf_checker_square():
    A = TiledMatrix(4 * 16, 4 * 16, 16, 16, name="A")
    ptg.check_taskpool(build_geqrf(A))


def test_geqrf_checker_tall():
    A = TiledMatrix(6 * 16, 3 * 16, 16, 16, name="A")
    ptg.check_taskpool(build_geqrf(A, ib=8))


def test_geqrf_rejects_wide():
    A = TiledMatrix(2 * 16, 4 * 16, 16, 16, name="A")
    with pytest.raises(ValueError):
        build_geqrf(A)


def test_geqrf_rejects_an_ib_that_does_not_divide_nb():
    A = TiledMatrix(64, 64, 16, 16, name="A")
    with pytest.raises(ValueError):
        build_geqrf(A, ib=12)
    with pytest.raises(ValueError):
        build_geqrf(A, TiledMatrix(4 * 8, 3 * 16, 8, 16, name="T"))


def _check_factored_form(ref, A, T, A_host, ib):
    """R in the upper triangle of A, V under it and T beside it in the
    layout the plain reference's ``apply_q`` reads: A0 = Q R, Q
    orthogonal; against ``numpy.linalg.qr``: R up to row signs, Q's
    first columns up to the same signs. Nothing in a scratch collection."""
    m, n = A_host.shape
    nb, mt, nt = A.nb, A.mt, A.nt
    assert (T.mb, T.nb, T.mt, T.nt) == (ib, nb, mt, nt)
    assert not getattr(A, "scratch", False) and \
        not getattr(T, "scratch", False)
    F = A.to_array()
    R = np.triu(F)[:n].astype(np.float64)
    Q = ref.dense_q(lambda i, j: np.asarray(A.data_of((i, j))),
                    lambda i, j: np.asarray(T.data_of((i, j))), mt, nt, nb)
    np.testing.assert_allclose(Q.T @ Q, np.eye(m), atol=2e-5)
    np.testing.assert_allclose(Q[:, :n] @ R, A_host, atol=2e-5)
    q_np, r_np = np.linalg.qr(A_host.astype(np.float64))
    signs = np.sign(np.diagonal(r_np)) * np.sign(np.diagonal(R))
    np.testing.assert_allclose(R, signs[:, None] * r_np, atol=1e-4)
    np.testing.assert_allclose(Q[:, :n], q_np * signs[None, :], atol=1e-4)
    # V really is under R: the tiles under the diagonal are not zero
    for i in range(mt):
        for j in range(min(i, nt)):
            assert np.abs(F[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]).max() \
                > 1e-3
    # and T holds a tile for each of them and for the diagonal; what an
    # executor made of the others beside them stays zero
    written = {(i, k) for k in range(nt) for i in range(k, mt)}
    assert written <= set(T._tiles)
    for key, tile in T._tiles.items():
        assert (np.abs(np.asarray(tile)).max() > 0) == (key in written)


@pytest.mark.parametrize("shape,ib", [((96, 96), 32), ((96, 96), 16),
                                      ((128, 64), 32), ((128, 64), 8)])
def test_geqrf_host_runtime(ctx, ref, shape, ib):
    """The whole taskpool on the host runtime, square and tall, one
    block a tile and several, against ``numpy.linalg.qr``."""
    nb = 32
    A_host = _seeded(shape)
    A = TiledMatrix.from_array(A_host.copy(), nb, nb, name="A")
    tp = build_geqrf(A, ib=ib)
    ctx.add_taskpool(tp)
    assert ctx.wait(timeout=120)
    _check_factored_form(ref, A, tp.g.T, A_host, ib)


def test_geqrf_takes_the_callers_t_collection(ctx, ref):
    nb, ib = 32, 16
    A_host = _seeded((64, 64))
    A = TiledMatrix.from_array(A_host.copy(), nb, nb, name="A")
    T = geqrf_t_collection(A, ib)
    tp = build_geqrf(A, T)
    assert tp.g.T is T
    ctx.add_taskpool(tp)
    assert ctx.wait(timeout=120)
    _check_factored_form(ref, A, T, A_host, ib)


@pytest.mark.parametrize("mode", ["tile_dict", "stacked"])
def test_geqrf_compiled(rng, ref, mode):
    """The dgeqrf DAG through the compiled executor: the factored form
    of the host runtime (V in A, T in its collection)."""
    import jax
    from parsec_tpu.compiled.wavefront import (WavefrontExecutor,
                                               plan_taskpool)
    m = n = 96
    nb, ib = 32, 16
    A_host = rng.standard_normal((m, n)).astype(np.float32)
    A = TiledMatrix.from_array(A_host.copy(), nb, nb, name="A")
    tp = build_geqrf(A, ib=ib)
    ex = WavefrontExecutor(plan_taskpool(tp))
    if mode == "tile_dict":
        out = jax.jit(ex.run_tile_dict)(ex.make_tiles())
        ex.write_back_tiles(out)
    else:
        ex.run()
    _check_factored_form(ref, A, tp.g.T, A_host, ib)


def test_geqrf_run_sharded(rng, ref):
    """Two collections of unequal tile shape through the SPMD mesh path:
    geqrf over the 8-device virtual mesh."""
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices (virtual CPU mesh)")
    from parsec_tpu.compiled.spmd import make_mesh, run_sharded
    from parsec_tpu.compiled.wavefront import (WavefrontExecutor,
                                               plan_taskpool)
    m = n = 128
    nb, ib = 32, 32
    A_host = rng.standard_normal((m, n)).astype(np.float32)
    A = TiledMatrix.from_array(A_host.copy(), nb, nb, name="A")
    tp = build_geqrf(A, ib=ib)
    ex = WavefrontExecutor(plan_taskpool(tp))
    run_sharded(ex, mesh=make_mesh(8, axis="tiles"))
    _check_factored_form(ref, A, tp.g.T, A_host, ib)


def test_a_row_of_tsmqrs_shares_a_launch_and_gives_what_they_give_alone(
        make_ctx):
    """Through the chip's module: rows of TSMQRs (V2 and T shared, two
    written tiles a member) and of UNMQRs leave in launches of several,
    every class counted, and A and T hold what the same pool leaves with
    every task launched alone."""
    import jax.numpy as jnp
    nb, nt, ib = 16, 8, 8
    A_host = _seeded((nb * nt, nb * nt))

    def run(alone):
        ctx = make_ctx(nb_cores=2)
        dev = next(d for d in ctx.devices.devices
                   if d.name.startswith("tpu"))
        for d in ctx.devices.devices:
            if d.name == "cpu":
                d.weight = 0.01         # the chip's path
        if alone:
            dev.group_limit = lambda task, chore=None: 0
        ctx.set_stage_timers(True)
        A = TiledMatrix.from_array(A_host.copy(), nb, nb, name="A")
        for key in A.keys():
            A.write_tile(key, jnp.asarray(A.data_of(key)))
        tp = build_geqrf(A, ib=ib)
        ctx.add_taskpool(tp)
        assert ctx.wait(timeout=300)
        stats = dev.dump_statistics()
        return A.to_array(), tp.g.T.to_array(), stats

    a1, t1, alone = run(True)
    a2, t2, grouped = run(False)
    # one wide product for a row rounds otherwise than its members' own
    np.testing.assert_allclose(a2, a1, atol=2e-5)
    np.testing.assert_allclose(t2, t1, atol=2e-5)
    want = {"GEQRT": 8, "UNMQR": 28, "TSQRT": 28, "TSMQR": 140}
    assert alone["tasks_by_class"] == want == alone["launches_by_class"]
    assert grouped["tasks_by_class"] == want
    assert grouped["launches_by_class"]["TSMQR"] < 140
    assert grouped["launches_by_class"]["GEQRT"] == 8
    assert grouped["launches_by_class"]["TSQRT"] == 28
    assert grouped["batched_tasks"] >= 4 * grouped["batches"] > 0


@pytest.mark.parametrize("alone", [True, False], ids=["alone", "grouped"])
def test_the_updates_run_where_their_tiles_lie(make_ctx, alone):
    """``Chore.donates``: an UNMQR's C and a TSMQR's C1 and A2 are the
    last reading of their version, so the chip's module gives the input's
    buffer to the program (the caller's tile is gone once its update is
    launched, one to a launch or several); the diagonal and the panel
    tiles, which GEQRT and TSQRT take beside a row's readers, are not
    given; a group is waited for through its program's own mark, never
    through a tile a later launch was given; and the factored form is
    what tiles nobody can give (host arrays, put on the chip a launch)
    come to."""
    import jax.numpy as jnp
    nb, nt, ib = 16, 6, 8
    A_host = _seeded((nb * nt, nb * nt))

    def run(on_chip):
        ctx = make_ctx(nb_cores=2)
        dev = next(d for d in ctx.devices.devices
                   if d.name.startswith("tpu"))
        for d in ctx.devices.devices:
            if d.name == "cpu":
                d.weight = 0.01         # the chip's path
        if alone:
            dev.group_limit = lambda task, chore=None: 0
        A = TiledMatrix.from_array(A_host.copy(), nb, nb, name="A")
        first = {}
        if on_chip:
            for key in A.keys():
                first[key] = jnp.asarray(A.data_of(key))
                A.write_tile(key, first[key])
        tp = build_geqrf(A, ib=ib)
        ctx.add_taskpool(tp)
        assert ctx.wait(timeout=300)
        return A.to_array(), tp.g.T.to_array(), first, dev

    a0, t0, _, _ = run(False)
    a1, t1, first, dev = run(True)
    np.testing.assert_allclose(a1, a0, atol=2e-5)
    np.testing.assert_allclose(t1, t0, atol=2e-5)
    for (i, j), tile in first.items():
        assert tile.is_deleted() == (j > 0), (i, j)
    if alone:
        # an update alone holds nothing new and is not counted among the
        # launches still queued: the chains' GEQRTs and TSQRTs are
        # (tiles this small never fill the queue's bound)
        assert len(dev._lone) == 6 + 15
    else:
        mark = dev._group_marks[-1]
        assert mark.shape == (1,) and not mark.is_deleted()


def test_the_group_rule_counts_a_shared_operand_once(make_ctx):
    """One rule, one constant: a launch's inputs, an operand its stacked
    form shares counted once, within ``GROUP_BYTES``. By proportion, a
    256-byte tile for 16 MiB: four TSMQRs (V2, T shared; C1, A2 their
    own) are admitted where 4 x 50 MiB were not, eight are not; a TRSM's
    shared L admits eight; a GEMM's 48 MiB still four."""
    import parsec_tpu.device.tpu as tpu
    from parsec_tpu.core.task import Chore, DeviceType
    ctx = make_ctx(nb_cores=1)
    dev = next(d for d in ctx.devices.devices if d.name.startswith("tpu"))
    tile = np.zeros((8, 8), np.float32)
    t_tile = np.zeros((1, 8), np.float32)        # ib = nb/8: 2 MiB
    old = tpu.GROUP_BYTES
    tpu.GROUP_BYTES = old * tile.nbytes // (16 << 20)
    try:
        plain = Chore(DeviceType.TPU, lambda *a: None)
        tsmqr = Chore(DeviceType.TPU, lambda *a: None,
                      batch_hook=lambda *a: None,
                      batch_hook_shared=("V", "T"))
        trsm = Chore(DeviceType.TPU, lambda *a: None,
                     batch_hook=lambda *a: None, batch_hook_shared=("L",))
        row = [("V", tile), ("T", t_tile), ("C1", tile), ("A2", tile)]
        assert dev._sizes(row, tsmqr) == [4]
        assert dev._sizes(row, plain) == []
        assert dev._sizes([("L", tile), ("C", tile)], trsm) == [8, 4]
        assert dev._sizes([("L", tile), ("C", tile)], plain) == [4]
        assert dev._sizes([("A", tile), ("B", tile), ("C", tile)],
                          plain) == [4]
    finally:
        tpu.GROUP_BYTES = old


def test_geqrf_flops_positive():
    assert geqrf_flops(512, 512) > 0
    assert geqrf_flops(1024, 512) > geqrf_flops(512, 512)


# ---- blocked-Householder (panel-fused) variant -------------------------

def _check_qr_result(R, A_host, nb):
    m, n = A_host.shape
    for bi in range(m // nb):
        for bj in range(n // nb):
            blk = R[bi * nb:(bi + 1) * nb, bj * nb:(bj + 1) * nb]
            if bi > bj:
                np.testing.assert_allclose(blk, 0.0, atol=1e-4)
    np.testing.assert_allclose(R.T @ R, A_host.T @ A_host,
                               rtol=2e-3, atol=2e-2)


def test_panel_qr_tile_identity(rng):
    """The CholeskyQR2 + reconstruction kernel: H orthogonal,
    H·E1 = Q_r, Hᵀ·P = [R; 0]."""
    import jax.numpy as jnp
    from parsec_tpu.ops.tile_kernels import panel_qr_tile
    mk, nb = 96, 32
    P = rng.standard_normal((mk, nb)).astype(np.float32)
    Vt, Xinv, R = panel_qr_tile(jnp.asarray(P.T))
    Vt_n, Xinv_n, R_n = (np.asarray(x) for x in (Vt, Xinv, R))
    H = np.eye(mk, dtype=np.float32) - Vt_n.T @ Xinv_n.T @ Vt_n
    np.testing.assert_allclose(H.T @ H, np.eye(mk), atol=1e-4)
    HtP = H.T @ P
    np.testing.assert_allclose(HtP[:nb], R_n, atol=1e-3)
    np.testing.assert_allclose(HtP[nb:], 0.0, atol=1e-3)
    np.testing.assert_allclose(np.tril(R_n, -1), 0.0, atol=1e-5)
    # the public trailing-update kernel must agree with H's action
    from parsec_tpu.ops.tile_kernels import panel_qr_apply
    C = rng.standard_normal((mk, 48)).astype(np.float32)
    got = np.asarray(panel_qr_apply(Vt, Xinv, jnp.asarray(C.T)))
    np.testing.assert_allclose(got, (H.T @ C).T, atol=1e-3)


def test_geqrf_hh_checker_square():
    from parsec_tpu.algorithms.geqrf import build_geqrf_hh
    A = TiledMatrix(4 * 16, 4 * 16, 16, 16, name="A")
    ptg.check_taskpool(build_geqrf_hh(A))


def test_geqrf_hh_checker_tall():
    from parsec_tpu.algorithms.geqrf import build_geqrf_hh
    A = TiledMatrix(6 * 16, 3 * 16, 16, 16, name="A")
    ptg.check_taskpool(build_geqrf_hh(A))


def test_geqrf_hh_rejects_nonsquare_tiles():
    from parsec_tpu.algorithms.geqrf import build_geqrf_hh
    A = TiledMatrix(64, 64, 32, 16, name="A")
    with pytest.raises(ValueError):
        build_geqrf_hh(A)


@pytest.mark.parametrize("shape", [(96, 96), (128, 64)])
def test_geqrf_hh_host_runtime(ctx, rng, shape):
    from parsec_tpu.algorithms.geqrf import build_geqrf_hh
    m, n = shape
    nb = 32
    A_host = rng.standard_normal((m, n)).astype(np.float32)
    A = TiledMatrix.from_array(A_host.copy(), nb, nb, name="A")
    ctx.add_taskpool(build_geqrf_hh(A))
    assert ctx.wait(timeout=120)
    _check_qr_result(A.to_array(), A_host, nb)


@pytest.mark.parametrize("shape", [(128, 128), (160, 96)])
def test_geqrf_hh_panel_fused(rng, shape):
    """The fused path (PanelExecutor over the Aᵀ store) matches the QR
    identity end-to-end."""
    import jax
    from parsec_tpu.algorithms.geqrf import build_geqrf_hh
    from parsec_tpu.compiled.panels import PanelExecutor
    from parsec_tpu.compiled.wavefront import plan_taskpool
    m, n = shape
    nb = 32
    A_host = rng.standard_normal((m, n)).astype(np.float32)
    A = TiledMatrix.from_array(A_host.copy(), nb, nb, name="A")
    ex = PanelExecutor(plan_taskpool(build_geqrf_hh(A)))
    out = jax.jit(ex.run_state)(ex.make_state())
    ex.write_back(out)
    _check_qr_result(A.to_array(), A_host, nb)


def test_geqrf_hh_refused_by_tile_executor():
    """Value flows + direct collection reads: the per-tile compiled
    executors must refuse loudly."""
    from parsec_tpu.algorithms.geqrf import build_geqrf_hh
    from parsec_tpu.compiled.wavefront import (WavefrontExecutor,
                                               plan_taskpool)
    A = TiledMatrix(4 * 16, 4 * 16, 16, 16, name="A")
    plan = plan_taskpool(build_geqrf_hh(A))
    assert plan.has_value_flows
    with pytest.raises(ValueError):
        WavefrontExecutor(plan)


def test_a_serial_class_is_never_compiled_for_a_group(make_ctx):
    """Four TSQRTs of four columns ready together (it happens once in a
    few steps at sixteen tiles a side): each has its own diagonal tile
    where the stacked form declares one, so the chip module sends them
    alone and builds no stacked program: not for the four, not for what
    is left of the bin. A compile inside a later step is a stall of
    seconds."""
    import jax.numpy as jnp
    from parsec_tpu.core.task import Task
    from parsec_tpu.utils import compile_cache
    ctx = make_ctx()
    dev = next(d for d in ctx.devices.devices if d.name.startswith("tpu"))
    A = TiledMatrix(8 * 16, 8 * 16, 16, 16, name="A")
    tp = build_geqrf(A, ib=8)
    tp.context = ctx
    tc = tp.task_class_by_name("TSQRT")
    (chore,) = tc.incarnations
    assert chore.batch_hook is not None and \
        tuple(chore.batch_hook_shared) == ("R",)
    tasks = [Task(tp, tc, (k + 1, k)) for k in range(4)]
    for i, t in enumerate(tasks):
        t.data["R"] = jnp.triu(jnp.asarray(_seeded((16, 16), 20 + i)))
        t.data["A"] = jnp.asarray(_seeded((16, 16), 30 + i))
    compiled = compile_cache.backend_compile_count()
    for left in (tasks, tasks[1:], tasks[3:]):
        assert dev.execute_group(None, list(left), chore) == (0, 0)
    assert compile_cache.backend_compile_count() == compiled
    assert not any(slot[2] for record in dev._table.values()
                   for slot in record if isinstance(slot, tuple))
    # the same four sharing their diagonal tile would be a group
    for t in tasks[1:]:
        t.data["R"] = tasks[0].data["R"]
    # four of three new tiles each: R and A 16 x 16, T 8 x 16
    assert dev.execute_group(None, list(tasks), chore) == (
        4, 4 * (2 * 16 * 16 + 8 * 16) * 4)
    assert all(set(t.output) == {"R", "A", "T"} for t in tasks)
