"""Panel-fused executor (compiled/panels.py): wavefront plans lowered to
dense-array panel ops. Correctness vs LAPACK and vs the tile-dict
executor, write-set preservation, and rejection diagnostics."""

import numpy as np
import pytest

import parsec_tpu.compiled.panels as panels
from parsec_tpu.algorithms.potrf import build_potrf
from parsec_tpu.compiled.panels import PanelExecutor, PanelGeometry
from parsec_tpu.compiled.wavefront import WavefrontExecutor, plan_taskpool
from parsec_tpu.data.matrix import TiledMatrix
from parsec_tpu.utils import mca_param


def _spd(n, seed=0):
    rng = np.random.default_rng(seed)
    M = rng.standard_normal((n, n))
    return (M @ M.T + n * np.eye(n)).astype(np.float32)


@pytest.mark.parametrize("n,nb", [(256, 64), (256, 128), (192, 64),
                                  (128, 128)])
def test_panel_potrf_matches_lapack(n, nb):
    A_host = _spd(n)
    A = TiledMatrix.from_array(A_host.copy(), nb, nb, name="A")
    ex = PanelExecutor(plan_taskpool(build_potrf(A)))
    ex.run()
    L = np.tril(A.to_array())
    err = np.linalg.norm(L @ L.T - A_host) / np.linalg.norm(A_host)
    assert err < 1e-4, err


def test_panel_matches_tile_dict_executor():
    """Same plan, both substrates → same lower triangle (same kernels,
    same wave order)."""
    A_host = _spd(256)
    A1 = TiledMatrix.from_array(A_host.copy(), 64, 64, name="A")
    PanelExecutor(plan_taskpool(build_potrf(A1))).run()
    A2 = TiledMatrix.from_array(A_host.copy(), 64, 64, name="A")
    WavefrontExecutor(plan_taskpool(build_potrf(A2))).run()
    assert np.allclose(np.tril(A1.to_array()), np.tril(A2.to_array()),
                       atol=2e-2), "substrates diverged"


def test_panel_preserves_upper_tiles():
    """The DAG never writes strictly-upper tiles; neither may the fused
    path (write-set equivalence with the tiled executors)."""
    A_host = _spd(256)
    A = TiledMatrix.from_array(A_host.copy(), 64, 64, name="A")
    ex = PanelExecutor(plan_taskpool(build_potrf(A)))
    ex.run()
    out = A.to_array()
    nt = 256 // 64
    for i in range(nt):
        for j in range(i + 1, nt):
            assert np.array_equal(out[i * 64:(i + 1) * 64,
                                      j * 64:(j + 1) * 64],
                                  A_host[i * 64:(i + 1) * 64,
                                         j * 64:(j + 1) * 64]), (i, j)


def test_panel_requires_wave_fuser():
    """Taskpools without a wave_fuser are rejected with a clear error."""
    A = TiledMatrix.from_array(_spd(128), 64, 64, name="A")
    tp = build_potrf(A)
    del tp.wave_fuser
    with pytest.raises(ValueError, match="wave_fuser"):
        PanelExecutor(plan_taskpool(tp))


def test_panel_geometry_slices():
    g = PanelGeometry(name="A", mb=32, nb=32, mt=4, nt=4)
    assert g.rows(2) == slice(64, 96)


# ---------------------------------------------------------------- left-looking

def test_left_potrf_host_runtime_matches_lapack():
    """build_potrf_left through the HOST runtime (CTL-gather ordering +
    direct collection reads in UPDATE bodies)."""
    import parsec_tpu as parsec
    from parsec_tpu.algorithms.potrf import build_potrf_left

    A_host = _spd(256)
    A = TiledMatrix.from_array(A_host.copy(), 64, 64, name="A")
    ctx = parsec.init(nb_cores=4)
    ctx.start()
    ctx.add_taskpool(build_potrf_left(A))
    assert ctx.wait(timeout=60)
    parsec.fini(ctx)
    L = np.tril(A.to_array())
    err = np.linalg.norm(L @ L.T - A_host) / np.linalg.norm(A_host)
    assert err < 1e-4, err


@pytest.fixture
def run_tiles(request, monkeypatch):
    """Tiles to a column run of a row panel (None: the constant as it
    ships, one run a step at these sizes), set where the lowering reads
    it: the module constant, not a knob."""
    if request.param is not None:
        nb = request.node.callspec.params["nb"]
        monkeypatch.setattr(panels, "PANEL_CHUNK_BYTES",
                            request.param * nb * nb * 4)
    return request.param


@pytest.fixture
def hook(request):
    mca_param.set("potrf.trsm_hook", request.param)
    yield request.param
    mca_param.unset("potrf.trsm_hook")


def _left_factor(A_host, nb):
    from parsec_tpu.algorithms.potrf import build_potrf_left

    A = TiledMatrix.from_array(A_host.copy(), nb, nb, name="A")
    ex = PanelExecutor(plan_taskpool(build_potrf_left(A)))
    ex.run()
    return ex, np.tril(A.to_array())


@pytest.mark.parametrize("hook", ["solve", "gemm"], indirect=True)
@pytest.mark.parametrize("run_tiles", [None, 2, 1], indirect=True)
@pytest.mark.parametrize("n,nb", [(256, 64), (192, 64), (256, 128)])
def test_left_potrf_panel_executor(n, nb, run_tiles, hook):
    """The one-chip program's factor against LAPACK, its updates in one,
    two and three column runs a step (n=256, nb=64: step 1 has three
    tiles), both TRSM hooks."""
    A_host = _spd(n)
    ex, L = _left_factor(A_host, nb)
    err = np.linalg.norm(L @ L.T - A_host) / np.linalg.norm(A_host)
    assert err < 1e-4, err
    ref = np.linalg.cholesky(A_host.astype(np.float64))
    np.testing.assert_allclose(L, ref, rtol=1e-4, atol=1e-4)
    nt = n // nb
    per_step = [-(-(nt - k) // (run_tiles or nt)) for k in range(1, nt)]
    assert ex.lowering_report()["update_runs"] == sum(per_step)
    if (n, nb) == (256, 64):
        assert max(per_step) == {None: 1, 2: 2, 1: 3}[run_tiles]


def _left_fused_subtraction(A, nb, hook):
    """The formulation before PR 40, in plain f32 numpy over Aᵀ: a step's
    whole row panel less its product in one piece, then the diagonal
    tile's factor and the solve from that piece."""
    from scipy.linalg import solve_triangular
    D = A.T.astype(np.float32).copy()
    for r0 in range(0, len(D), nb):
        r1 = r0 + nb
        rowk = D[r0:r1, r0:] - D[:r0, r0:r1].T @ D[:r0, r0:]
        d = rowk[:, :nb]
        L = np.linalg.cholesky(0.5 * (d + d.T))
        if hook == "gemm":
            solved = solve_triangular(
                L, np.eye(nb, dtype=np.float32), lower=True) @ rowk[:, nb:]
        else:
            solved = solve_triangular(L, rowk[:, nb:], lower=True)
        D[r0:r1, r0:] = np.concatenate([L.T, solved], axis=1)
    return np.triu(D).T


@pytest.mark.parametrize("hook", ["gemm", "solve"], indirect=True)
@pytest.mark.parametrize("run_tiles", [None, 1], indirect=True)
@pytest.mark.parametrize("n,nb", [(256, 64)])
def test_left_runs_equal_the_fused_subtraction(n, nb, run_tiles, hook):
    """Where the subtraction happens moves one f32 rounding per element
    and nothing else: the factor of the products-alone-in-runs lowering
    equals the whole-row-panel formulation's to f32 rounding."""
    A_host = _spd(n, seed=40)
    _ex, L = _left_factor(A_host, nb)
    want = _left_fused_subtraction(A_host, nb, hook)
    assert np.abs(L - want).max() <= 2e-5 * np.abs(want).max()


@pytest.mark.parametrize("n,nb,chunk_bytes,runs,ops", [
    # the flagship as it ships: 39 steps, 15 of them (k < 16) in two runs
    (40960, 1024, None, 54, 22892175687680),
    (512, 64, 3 * 64 * 64 * 4, 3 + 2 + 2 + 2 + 1 + 1 + 1, None),
    (512, 64, 64 * 64 * 4, sum(range(1, 8)), None)])
def test_left_lowering_report_is_the_run_rule(monkeypatch, n, nb,
                                              chunk_bytes, runs, ops):
    """``update_runs`` / ``update_ops``: the lowering's static account of
    its UPDATE waves (no run needed), equal to what the run rule gives —
    the fewest equal runs of whole tiles within ``PANEL_CHUNK_BYTES`` —
    and to the algorithm's count of the updates' operations."""
    from parsec_tpu.algorithms.potrf import build_potrf_left

    if chunk_bytes:
        monkeypatch.setattr(panels, "PANEL_CHUNK_BYTES", chunk_bytes)
    ex = PanelExecutor(plan_taskpool(build_potrf_left(
        TiledMatrix(n, n, nb, nb, name="A"))))
    nt = n // nb
    rep = ex.lowering_report()
    assert rep["update_runs"] == runs
    assert rep["update_ops"] == sum(
        2 * (k * nb) * nb * (nt - k) * nb for k in range(1, nt))
    if ops:
        assert rep["update_ops"] == ops
    # the size of a run is part of the stored program's key
    monkeypatch.setattr(panels, "PANEL_CHUNK_BYTES", 7 * nb * nb * 4)
    other = PanelExecutor(plan_taskpool(build_potrf_left(
        TiledMatrix(n, n, nb, nb, name="A"))))
    assert other.monolith_cache_key() != ex.monolith_cache_key()


def test_left_update_wave_writes_nothing(monkeypatch):
    """An UPDATE wave multiplies and carries: it returns the state array
    it was given (the step's one write a run is the TRSM wave's) and a
    product a column run, under the scope the trace reads."""
    import jax
    import jax.numpy as jnp
    from parsec_tpu.algorithms.potrf import build_potrf_left

    n, nb = 256, 64
    monkeypatch.setattr(panels, "PANEL_CHUNK_BYTES", 2 * nb * nb * 4)
    ex = PanelExecutor(plan_taskpool(build_potrf_left(
        TiledMatrix(n, n, nb, nb, name="A"))))
    kinds = [w[0].tc.name for w in ex.plan.waves]
    update = ex._wave_fns[kinds.index("UPDATE")]      # step 1: tiles 1..3
    D = jnp.asarray(_spd(n))
    out = update({"A": D})
    assert out["A"] is D
    assert [p.shape for p in out["_products"]] == [(nb, nb), (nb, 2 * nb)]
    assert update.account == {"update_runs": 2,
                              "update_ops": 2 * nb * nb * 3 * nb}
    text = jax.jit(ex.run_state).lower(ex.state_shapes()).as_text(
        debug_info=True)
    assert "parsec:panel_update" in text


def test_left_matches_right_fused():
    """Left- and right-looking fused paths agree on the factor."""
    from parsec_tpu.algorithms.potrf import build_potrf_left

    A_host = _spd(256)
    A1 = TiledMatrix.from_array(A_host.copy(), 64, 64, name="A")
    PanelExecutor(plan_taskpool(build_potrf_left(A1))).run()
    A2 = TiledMatrix.from_array(A_host.copy(), 64, 64, name="A")
    PanelExecutor(plan_taskpool(build_potrf(A2))).run()
    assert np.allclose(np.tril(A1.to_array()), np.tril(A2.to_array()),
                       atol=2e-2)


def test_left_wave_structure():
    """ASAP leveling of the left DAG: exactly 3 waves per step k
    ([UPDATE], [POTRF], [TRSM]) — the schedule the fuser assumes."""
    from parsec_tpu.algorithms.potrf import build_potrf_left

    A = TiledMatrix.from_array(_spd(256), 64, 64, name="A")
    plan = plan_taskpool(build_potrf_left(A))
    assert plan.n_waves == 3 * 4 - 2       # 3 per step, last has no TRSM
    kinds = [sorted(g.tc.name for g in w) for w in plan.waves]
    assert kinds[0] == ["POTRF"] and kinds[1] == ["TRSM"]
    for k in range(1, 4):
        base = 2 + 3 * (k - 1)
        assert kinds[base] == ["UPDATE"]
        assert kinds[base + 1] == ["POTRF"]
        if k < 3:
            assert kinds[base + 2] == ["TRSM"]


def test_left_rejected_by_wavefront_executor():
    from parsec_tpu.algorithms.potrf import build_potrf_left

    A = TiledMatrix.from_array(_spd(128), 64, 64, name="A")
    with pytest.raises(ValueError, match="PanelExecutor"):
        WavefrontExecutor(plan_taskpool(build_potrf_left(A)))


# ------------------------------------------------------------- segmented

def test_segmented_tile_dict_matches_whole_dag():
    """run_tile_dict_segmented: same results as the whole-DAG jit, with
    a bounded segment cache (compile scales with distinct (class,
    bucket) shapes, not waves/tasks)."""
    A_host = _spd(512)
    A1 = TiledMatrix.from_array(A_host.copy(), 64, 64, name="A")
    ex1 = WavefrontExecutor(plan_taskpool(build_potrf(A1)))
    t1 = ex1.make_tiles()
    out1 = ex1.run_tile_dict(dict(t1))

    A2 = TiledMatrix.from_array(A_host.copy(), 64, 64, name="A")
    ex2 = WavefrontExecutor(plan_taskpool(build_potrf(A2)))
    out2 = ex2.run_tile_dict_segmented(ex2.make_tiles())

    for k in out1:
        assert np.allclose(np.asarray(out1[k]), np.asarray(out2[k]),
                           atol=1e-3), k
    # the segment cache must stay below the wave-group count (shape
    # reuse across waves — the point of the mode) and is bounded by
    # classes x power-of-two buckets, not by DAG size
    n_groups = sum(len(w) for w in ex2.plan.waves)
    assert len(ex2._segments) < n_groups, (len(ex2._segments), n_groups)
    assert len(ex2._segments) <= 4 * 6


def test_segmented_reuses_segments_across_sizes():
    """Same tile shape at a bigger NT adds few/no new segments."""
    A1 = TiledMatrix.from_array(_spd(256), 64, 64, name="A")
    ex = WavefrontExecutor(plan_taskpool(build_potrf(A1)))
    ex.run_tile_dict_segmented(ex.make_tiles())
    n_small = len(ex._segments)

    A2 = TiledMatrix.from_array(_spd(512), 64, 64, name="A")
    ex2 = WavefrontExecutor(plan_taskpool(build_potrf(A2)))
    ex2._segments = ex._segments          # shared cache (same shapes)
    ex2.run_tile_dict_segmented(ex2.make_tiles())
    added = len(ex2._segments) - n_small
    assert added <= 8, added              # only new bucket sizes appear


# ---------------------------------------------------------- multi-collection

def test_panel_gemm_multi_collection():
    """GEMM through the panel executor: three transposed stores, one
    rank-nb dense update per k wave — the multi-collection case of the
    wave_fuser contract."""
    from parsec_tpu.algorithms.gemm import build_gemm_ptg

    rng = np.random.default_rng(3)
    A_h = rng.standard_normal((192, 256)).astype(np.float32)
    B_h = rng.standard_normal((256, 128)).astype(np.float32)
    C_h = rng.standard_normal((192, 128)).astype(np.float32)
    A = TiledMatrix.from_array(A_h.copy(), 64, 64, name="A")
    B = TiledMatrix.from_array(B_h.copy(), 64, 64, name="B")
    C = TiledMatrix.from_array(C_h.copy(), 64, 64, name="C")
    ex = PanelExecutor(plan_taskpool(build_gemm_ptg(A, B, C)))
    assert isinstance(ex.geom, dict) and set(ex.geom) == {"A", "B", "C"}
    ex.run()
    assert np.allclose(C.to_array(), A_h @ B_h + C_h, atol=1e-3)
    # read-only stores never written back
    assert np.array_equal(A.to_array(), A_h)


def test_panel_gemm_rectangular_nonuniform_tiles():
    from parsec_tpu.algorithms.gemm import build_gemm_ptg

    rng = np.random.default_rng(4)
    A_h = rng.standard_normal((128, 96)).astype(np.float32)
    B_h = rng.standard_normal((96, 64)).astype(np.float32)
    C_h = np.zeros((128, 64), np.float32)
    A = TiledMatrix.from_array(A_h.copy(), 64, 32, name="A")
    B = TiledMatrix.from_array(B_h.copy(), 32, 64, name="B")
    C = TiledMatrix.from_array(C_h.copy(), 64, 64, name="C")
    ex = PanelExecutor(plan_taskpool(build_gemm_ptg(A, B, C)))
    ex.run()
    assert np.allclose(C.to_array(), A_h @ B_h, atol=1e-3)


def test_panel_gemm_matches_tile_dict():
    from parsec_tpu.algorithms.gemm import build_gemm_ptg

    rng = np.random.default_rng(5)
    A_h = rng.standard_normal((128, 128)).astype(np.float32)
    B_h = rng.standard_normal((128, 128)).astype(np.float32)
    C_h = rng.standard_normal((128, 128)).astype(np.float32)

    C1 = TiledMatrix.from_array(C_h.copy(), 64, 64, name="C")
    PanelExecutor(plan_taskpool(build_gemm_ptg(
        TiledMatrix.from_array(A_h.copy(), 64, 64, name="A"),
        TiledMatrix.from_array(B_h.copy(), 64, 64, name="B"),
        C1))).run()

    C2 = TiledMatrix.from_array(C_h.copy(), 64, 64, name="C")
    WavefrontExecutor(plan_taskpool(build_gemm_ptg(
        TiledMatrix.from_array(A_h.copy(), 64, 64, name="A"),
        TiledMatrix.from_array(B_h.copy(), 64, 64, name="B"),
        C2))).run()
    assert np.allclose(C1.to_array(), C2.to_array(), atol=1e-4)


@pytest.mark.parametrize("kb", [1, 2, 0])
@pytest.mark.parametrize("beta", [1.0, 0.5])
def test_panel_gemm_k_blocking_exact(kb, beta):
    """k-blocked fusion (gemm.k_block) must reproduce the per-wave
    chain bit-for-bit semantics, including β applied per chain step."""
    from parsec_tpu.algorithms.gemm import build_gemm_ptg
    from parsec_tpu.utils import mca_param

    rng = np.random.default_rng(7)
    A_h = rng.standard_normal((128, 192)).astype(np.float32)
    B_h = rng.standard_normal((192, 128)).astype(np.float32)
    C_h = rng.standard_normal((128, 128)).astype(np.float32)
    A = TiledMatrix.from_array(A_h.copy(), 64, 64, name="A")
    B = TiledMatrix.from_array(B_h.copy(), 64, 64, name="B")
    C = TiledMatrix.from_array(C_h.copy(), 64, 64, name="C")
    mca_param.set("gemm.k_block", kb)
    try:
        ex = PanelExecutor(plan_taskpool(
            build_gemm_ptg(A, B, C, alpha=2.0, beta=beta)))
        ex.run()
    finally:
        mca_param.unset("gemm.k_block")
    KT = 3
    ref = C_h.copy()
    for k in range(KT):
        ref = 2.0 * A_h[:, k * 64:(k + 1) * 64] @ \
            B_h[k * 64:(k + 1) * 64] + beta * ref
    assert np.allclose(C.to_array(), ref, atol=1e-3)


# ------------------------------------------------------- segmented panels

@pytest.mark.parametrize("n,nb", [(256, 64), (320, 64), (192, 64),
                                  (128, 128)])
def test_segmented_left_potrf_matches_lapack(n, nb):
    """run_state_segmented on exact-bucket grids (NT ≤ 16 never pads):
    LAPACK-grade results incl. non-power-of-two tile grids."""
    from parsec_tpu.algorithms.potrf import build_potrf_left

    A_host = _spd(n)
    A = TiledMatrix.from_array(A_host.copy(), nb, nb, name="A")
    ex = PanelExecutor(plan_taskpool(build_potrf_left(A)))
    assert ex.supports_segments
    ex.run(segmented=True)
    L = np.tril(A.to_array())
    err = np.linalg.norm(L @ L.T - A_host) / np.linalg.norm(A_host)
    assert err < 1e-4, err


def test_segmented_bucket_padding_exact():
    """NT = 20 (n=640, nb=32): interior tile counts 17 and 19 round up
    to lattice points 18 and 20, so the UPDATE / TRSM panels genuinely
    PAD — this is the only tier-1 case that executes the zero-mask +
    clamped-window + roll paths of _build_extract/_build_write
    (grids of ≤ 16 tiles are exact-bucket and so is the cap point).
    A masking or roll off-by-one would corrupt the factor or scribble
    outside the true window; check both against LAPACK and the
    untouched upper triangle."""
    from parsec_tpu.algorithms.potrf import build_potrf_left
    from parsec_tpu.compiled.panels import bucket_tiles

    n, nb = 640, 32
    assert bucket_tiles(17, n // nb) == 18       # pads inside the grid
    assert bucket_tiles(19, n // nb) == 20
    A_host = _spd(n)
    A = TiledMatrix.from_array(A_host.copy(), nb, nb, name="A")
    ex = PanelExecutor(plan_taskpool(build_potrf_left(A)))
    # at least one descriptor must carry a padded (bucketed > true)
    # extent, or this test is not exercising what it claims
    padded = [rd for step in ex.segments() for rd in step.reads
              if rd.src == "state" and (rd.rows_b > rd.rows or
                                        rd.cols_b > rd.cols)]
    assert padded, "no padded windows at NT=17 — lattice changed?"
    ex.run(segmented=True)
    out = A.to_array()
    L = np.tril(out)
    err = np.linalg.norm(L @ L.T - A_host) / np.linalg.norm(A_host)
    assert err < 1e-4, err
    nt = n // nb
    for i in range(nt):                 # masked writes stay in-window
        for j in range(i + 1, nt):
            assert np.array_equal(out[i * nb:(i + 1) * nb,
                                      j * nb:(j + 1) * nb],
                                  A_host[i * nb:(i + 1) * nb,
                                         j * nb:(j + 1) * nb]), (i, j)


@pytest.mark.parametrize("hook", ["solve", "gemm"])
def test_segmented_matches_monolith(hook):
    """Same plan through the whole-DAG fused program and the segmented
    path: same factor (same kernels, same wave order) under BOTH
    trsm hooks."""
    from parsec_tpu.algorithms.potrf import build_potrf_left
    from parsec_tpu.utils import mca_param

    A_host = _spd(256)
    mca_param.set("potrf.trsm_hook", hook)
    try:
        A1 = TiledMatrix.from_array(A_host.copy(), 64, 64, name="A")
        PanelExecutor(plan_taskpool(build_potrf_left(A1))).run()
        A2 = TiledMatrix.from_array(A_host.copy(), 64, 64, name="A")
        PanelExecutor(plan_taskpool(build_potrf_left(A2))).run(
            segmented=True)
    finally:
        mca_param.unset("potrf.trsm_hook")
    assert np.allclose(np.tril(A1.to_array()), np.tril(A2.to_array()),
                       atol=2e-4), "segmented diverged from monolith"


def test_segmented_preserves_upper_tiles():
    """Masked window writes must honor the DAG write-set exactly like
    the monolith: strictly-upper tiles stay untouched even though the
    bucketed panels overlap them before masking."""
    from parsec_tpu.algorithms.potrf import build_potrf_left

    A_host = _spd(320)
    A = TiledMatrix.from_array(A_host.copy(), 64, 64, name="A")
    PanelExecutor(plan_taskpool(build_potrf_left(A))).run(segmented=True)
    out = A.to_array()
    nt = 320 // 64
    for i in range(nt):
        for j in range(i + 1, nt):
            assert np.array_equal(out[i * 64:(i + 1) * 64,
                                      j * 64:(j + 1) * 64],
                                  A_host[i * 64:(i + 1) * 64,
                                         j * 64:(j + 1) * 64]), (i, j)


@pytest.mark.parametrize("kb,beta", [(0, 1.0), (2, 0.5)])
def test_segmented_gemm_k_blocking_exact(kb, beta):
    """GEMM through the segmented panel path (multi-collection, const
    inputs, bucketed contraction extent): per-chain-step β semantics
    reproduced exactly."""
    from parsec_tpu.algorithms.gemm import build_gemm_ptg
    from parsec_tpu.utils import mca_param

    rng = np.random.default_rng(7)
    A_h = rng.standard_normal((128, 192)).astype(np.float32)
    B_h = rng.standard_normal((192, 128)).astype(np.float32)
    C_h = rng.standard_normal((128, 128)).astype(np.float32)
    A = TiledMatrix.from_array(A_h.copy(), 64, 64, name="A")
    B = TiledMatrix.from_array(B_h.copy(), 64, 64, name="B")
    C = TiledMatrix.from_array(C_h.copy(), 64, 64, name="C")
    mca_param.set("gemm.k_block", kb)
    try:
        ex = PanelExecutor(plan_taskpool(
            build_gemm_ptg(A, B, C, alpha=2.0, beta=beta)))
        ex.run(segmented=True)
    finally:
        mca_param.unset("gemm.k_block")
    ref = C_h.copy()
    for k in range(3):
        ref = 2.0 * A_h[:, k * 64:(k + 1) * 64] @ \
            B_h[k * 64:(k + 1) * 64] + beta * ref
    assert np.allclose(C.to_array(), ref, atol=1e-3)


def test_segmented_requires_segment_fuser():
    """Taskpools without a panel_segment_fuser are rejected loudly (the
    right-looking POTRF registers only the monolith wave_fuser)."""
    A = TiledMatrix.from_array(_spd(128), 64, 64, name="A")
    ex = PanelExecutor(plan_taskpool(build_potrf(A)))
    with pytest.raises(ValueError, match="panel_segment_fuser"):
        ex.run(segmented=True)


def test_prepare_segments_counts_programs():
    """prepare_segments resolves every program of the walk without
    touching data — after it, a run dispatches from cache only."""
    from parsec_tpu.algorithms.potrf import build_potrf_left
    from parsec_tpu.utils import compile_cache as cc

    A = TiledMatrix.from_array(_spd(384, seed=21), 128, 128, name="A")
    ex = PanelExecutor(plan_taskpool(build_potrf_left(A)))
    ex.prepare_segments()
    state = ex.make_state()      # host→device staging is not serving
    c0 = cc.backend_compile_count()
    out = ex.run_state_segmented(state)
    assert cc.backend_compile_count() == c0
    ex.write_back(out)


@pytest.mark.parametrize("builder", ["left", "right"])
def test_panel_potrf_trsm_solve_mode(builder):
    """potrf.trsm_hook=solve: the fusers use exact triangular solves
    (no inversion) and must match numpy chol closely."""
    from parsec_tpu.algorithms.potrf import build_potrf, build_potrf_left
    from parsec_tpu.utils import mca_param

    rng = np.random.default_rng(9)
    n, nb = 128, 32
    M = rng.standard_normal((n, n)).astype(np.float64)
    A_in = (M @ M.T + n * np.eye(n)).astype(np.float32)
    A = TiledMatrix.from_array(A_in.copy(), nb, nb, name="A")
    mca_param.set("potrf.trsm_hook", "solve")
    try:
        build = build_potrf_left if builder == "left" else build_potrf
        ex = PanelExecutor(plan_taskpool(build(A)))
        ex.run()
    finally:
        mca_param.unset("potrf.trsm_hook")
    L = np.tril(A.to_array().astype(np.float64))
    ref = np.linalg.cholesky(A_in.astype(np.float64))
    np.testing.assert_allclose(L, ref, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# the panel executor's own check (algorithms/potrf.py panel_spd_state,
# panel_potrf_residual): what chip_smoke.py's flagship phases decide on
# ---------------------------------------------------------------------------

def _probed_factor(n, nb):
    """The factor of ``panel_spd_state``'s matrix through the one-chip
    panel program, the residual the probe reads off it, and the matrix
    itself as the docstring defines it: the lower triangle from the
    stored upper triangle of D, diagonal blocks averaged."""
    import jax
    from parsec_tpu.algorithms.potrf import (build_potrf_left,
                                             panel_potrf_residual,
                                             panel_spd_state)
    key = jax.random.PRNGKey(3)
    D = np.asarray(panel_spd_state(key, n, nb)["A"], np.float64)
    block = np.arange(n) // nb
    above = block[:, None] < block[None, :]     # D's strictly upper blocks
    on = block[:, None] == block[None, :]
    A0 = np.where(above, D, 0.0)
    A0 = A0 + A0.T + np.where(on, 0.5 * (D + D.T), 0.0)
    ex = PanelExecutor(plan_taskpool(build_potrf_left(
        TiledMatrix(n, n, nb, nb, name="A"))))
    Lt = ex.jitted(panel_spd_state(key, n, nb))["A"]

    def probe(factor):
        with jax.default_matmul_precision("highest"):
            return float(panel_potrf_residual(factor, key, n, nb))
    return Lt, probe, A0


@pytest.mark.parametrize("hook", ["solve", "gemm"], indirect=True)
def test_the_panel_residual_probe_agrees_with_a_dense_residual(hook):
    n, nb = 256, 64
    Lt, probe, A0 = _probed_factor(n, nb)
    L = np.triu(np.asarray(Lt)).T.astype(np.float64)
    dense = np.linalg.norm(L @ L.T - A0) / np.linalg.norm(A0)
    assert dense < 1e-5 and probe(Lt) < 1e-5, (dense, probe(Lt))


def test_the_panel_residual_probe_sees_a_wrong_factor():
    """A check that cannot fail decides nothing: one tile of the factor
    off by a hundredth, and the factor of another matrix, both read
    far above the 1e-4 that chip_smoke.py admits."""
    import jax
    from parsec_tpu.algorithms.potrf import panel_spd_state
    n, nb = 256, 64
    Lt, probe, _ = _probed_factor(n, nb)
    assert probe(Lt) < 1e-5
    assert probe(Lt.at[nb:2 * nb, 2 * nb:3 * nb].multiply(1.01)) > 1e-4
    other = panel_spd_state(jax.random.PRNGKey(4), n, nb)["A"]
    assert probe(Lt + 0.01 * np.triu(np.asarray(other))) > 1e-3
