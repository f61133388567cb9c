"""Tile LU with partial pivoting over whole panels (DPLASMA ``dgetrf_1d``,
``build_getrf_1d``): the kernels and the whole taskpool against LAPACK
(``scipy.linalg.lu_factor``) on seeded matrices — IPIV entry for entry,
L\\U to float32 rounding, every multiplier at most 1 over whole columns —
through the host scheduler on the CPU device and through ``TPUDevice`` on
the CPU backend (the chip's path: under the device-to-host transfer
guard, every launch in place, the ranged-flow counters what the graph
says)."""

import numpy as np
import pytest
import scipy.linalg

import parsec_tpu as parsec
from parsec_tpu.algorithms import build_getrf_1d
from parsec_tpu.algorithms.getrf import getrf_1d_ipiv_collection
from parsec_tpu.data import TiledMatrix
from parsec_tpu.dsl import ptg
from parsec_tpu.ops.tile_kernels import (_swap_moves, gemm_full_tile,
                                         getrf_panel_tiles, laswp_tiles,
                                         swptrsm_tiles)

NB, IB = 64, 16


@pytest.fixture(autouse=True)
def highest():
    """The comparisons are of float32 arithmetic, not of bf16 passes."""
    import jax
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture
def make_ctx():
    """Contexts with one chip module; ``on``: the module the bodies run
    on, ``cpu`` (the inline CPU device) or ``tpu`` (``TPUDevice`` on the
    CPU backend: the chip's path)."""
    from parsec_tpu.utils import mca_param
    made = []
    mca_param.set("device.tpu.max_devices", 1)

    def make(on="tpu", nb_cores=2):
        ctx = parsec.init(nb_cores=nb_cores)
        ctx.start()
        made.append(ctx)
        for d in ctx.devices.devices:
            if d.name.startswith("tpu" if on == "cpu" else "cpu"):
                d.weight = 1e-6
        return ctx

    yield make
    for ctx in made:
        parsec.fini(ctx)
    mca_param.unset("device.tpu.max_devices")


def _seeded(shape, seed=7):
    return np.random.default_rng(seed).uniform(
        -0.5, 0.5, shape).astype(np.float32)


def _chip(ctx):
    return next(d for d in ctx.devices.devices if d.name.startswith("tpu"))


def _programs(dev, tc):
    return [p for chore in tc.incarnations
            for slot, programs in dev._table.get(id(chore), {}).items()
            if slot != "pinned" for p in programs.values()]


def _factor(ctx, a0, nb, ib, guard=False):
    """``a0`` factored through ``ctx``: (pool, A, IPIV); every tile a
    ``jax.Array`` before the pool starts, as the cell's are."""
    import jax
    import jax.numpy as jnp
    nt = a0.shape[0] // nb
    A = TiledMatrix(nt * nb, nt * nb, nb, nb, name="A")
    P = getrf_1d_ipiv_collection(A)
    for i in range(nt):
        P.write_tile((i, 0), jnp.zeros((1, nb), jnp.int32))
        for j in range(nt):
            A.write_tile((i, j), jnp.asarray(
                a0[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]))
    tp = build_getrf_1d(A, P, ib=ib)
    was = jax.config.jax_transfer_guard_device_to_host
    if guard:
        jax.config.update("jax_transfer_guard_device_to_host", "disallow")
    try:
        ctx.add_taskpool(tp)
        assert tp.wait_completed(600)
    finally:
        jax.config.update("jax_transfer_guard_device_to_host", was)
    return tp, A, P


def _stored(A, P, nt, nb):
    """(L\\U dense, LAPACK's global 0-based ipiv) of the stored form."""
    lu = np.block([[np.asarray(A.data_of((i, j))) for j in range(nt)]
                   for i in range(nt)])
    piv = np.concatenate([np.asarray(P.data_of((k, 0)))[0] + k * nb
                          for k in range(nt)])
    return lu, piv


def _lists(nt):
    """The sum of the list lengths of GETRF, SWPTRSM and SWPBACK."""
    return sum(nt - k for k in range(nt)) + \
        sum((nt - k - 1) * (nt - k) for k in range(nt)) + \
        sum(k * (nt - k) for k in range(nt))


# -- the kernels -------------------------------------------------------------

@pytest.mark.parametrize("kind", ["anywhere", "in_the_head", "repeated"])
def test_the_interchanges_as_row_moves_against_the_loop(kind):
    import jax.numpy as jnp
    rng = np.random.default_rng(5)
    for trial in range(60):
        n = int(rng.choice([1, 2, 4, 8, 16]))
        rows = n * int(rng.integers(1, 5))
        piv = np.array([
            rng.integers(j, rows) if kind == "anywhere" else
            rng.integers(j, n) if kind == "in_the_head" else
            rng.choice([j, rows - 1, min(j + 1, rows - 1)])
            for j in range(n)], np.int32)
        want = np.arange(rows)
        for j in range(n):
            want[[j, piv[j]]] = want[[piv[j], j]]
        top, low, frm = map(np.asarray, _swap_moves(jnp.asarray(piv), n))
        got = np.arange(rows)
        for j in range(n):
            if low[j] >= 0:
                got[low[j]] = frm[j]
        got[:n] = top
        assert (got == want).all(), (piv, want, got)
        assert (frm < n).all()      # what goes down comes from the head


@pytest.mark.parametrize("r,nb,ib", [(1, 16, 4), (3, 16, 4), (4, 32, 8),
                                     (2, NB, IB), (1, 128, 128),
                                     (2, 128, 128)])
def test_the_panel_kernel_is_lapacks_getrf_of_the_stack(r, nb, ib):
    """IPIV entry for entry, L\\U to rounding; ib = 128 goes through the
    VMEM panel (interpreted here), the others through XLA's LU."""
    import jax
    import jax.numpy as jnp
    a = _seeded((r * nb, nb), 10 * r + nb)
    tiles = [jnp.asarray(a[t * nb:(t + 1) * nb]) for t in range(r)]
    out, ipiv = jax.jit(lambda ts: getrf_panel_tiles(ts, ib))(tiles)
    lu, piv = scipy.linalg.lu_factor(a.astype(np.float64))
    assert ipiv.shape == (1, nb) and ipiv.dtype == jnp.int32
    assert (np.asarray(ipiv)[0] == piv).all()
    got = np.concatenate([np.asarray(t) for t in out])
    np.testing.assert_allclose(got, lu, atol=5e-6 * r * nb)
    assert np.abs(np.tril(got, -1)).max() <= 1.0
    # dlaswp by the same indices, and SWPTRSM's solve after it
    c = _seeded((r * nb, nb), 99)
    ct = [jnp.asarray(c[t * nb:(t + 1) * nb]) for t in range(r)]
    want = c.copy()
    for j in range(nb):
        want[[j, piv[j]]] = want[[piv[j], j]]
    swapped = np.concatenate([np.asarray(t) for t in
                              jax.jit(laswp_tiles)(ct, ipiv)])
    assert (swapped == want).all()
    solved = swptrsm_tiles(out[0], ipiv, ct)
    low = np.tril(lu[:nb], -1) + np.eye(nb)
    np.testing.assert_allclose(
        np.asarray(solved[0]), np.linalg.solve(low, want[:nb]), atol=5e-6 * nb)
    for t in range(1, r):
        assert (np.asarray(solved[t]) == want[t * nb:(t + 1) * nb]).all()


def test_the_trailing_product_is_full_float32_whatever_the_knob():
    from parsec_tpu.utils import mca_param
    a, b, c = (_seeded((NB, NB), s) for s in (1, 2, 3))
    mca_param.set("ops.matmul_precision", "default")
    try:
        got = np.asarray(gemm_full_tile(a, b, c))
    finally:
        mca_param.unset("ops.matmul_precision")
    np.testing.assert_allclose(
        got, c - a.astype(np.float64) @ b.astype(np.float64), atol=1e-5)
    import jax
    text = jax.jit(gemm_full_tile).lower(a, b, c).as_text()
    assert "HIGHEST" in text.upper()


# -- the graph ---------------------------------------------------------------

@pytest.mark.parametrize("nt", [1, 2, 5])
def test_both_sides_of_every_dependency_agree(nt):
    A = TiledMatrix(nt * 16, nt * 16, 16, 16)
    tp = build_getrf_1d(A, ib=4)
    ptg.check_taskpool(tp)
    counts = {tc.name: len(list(tc.enumerate_space()))
              for tc in tp.task_classes}
    pairs = nt * (nt - 1) // 2
    assert counts == {"GETRF": nt, "SWPTRSM": pairs, "SWPBACK": pairs,
                      "GEMM": sum((nt - k - 1) ** 2 for k in range(nt))}
    assert ptg.taskpool_has_ranged_flows(tp)
    assert not ptg.taskpool_writes_regions(tp)
    from parsec_tpu.compiled.wavefront import plan_taskpool
    with pytest.raises(ValueError, match="ranged data flow"):
        plan_taskpool(tp)
    # a task writes the FIRST tile of its list
    getrf = tp.get_task_class("GETRF")
    from parsec_tpu.core.task import Task
    assert getrf.written_tile(Task(tp, getrf, (nt - 1,))) == \
        (A, (nt - 1, nt - 1))
    with pytest.raises(ValueError, match="ib has to divide"):
        build_getrf_1d(A, ib=5)


@pytest.mark.parametrize("on", ["cpu", "tpu"])
@pytest.mark.parametrize("nt,nb,ib", [(1, 16, 4), (2, 16, 8), (5, 16, 4),
                                      (3, 16, 16), (1, NB, IB), (2, NB, IB)])
def test_the_taskpool_against_lapack(make_ctx, nt, nb, ib, on):
    n = nt * nb
    a0 = _seeded((n, n), 100 * nt + ib)
    ctx = make_ctx(on)
    _tp, A, P = _factor(ctx, a0, nb, ib, guard=(on == "tpu"))
    ran = {s["name"]: s["tasks"] for s in ctx.devices.dump_statistics()}
    assert ran[_chip(ctx).name] == (sum(ran.values()) if on == "tpu" else 0)
    got, piv = _stored(A, P, nt, nb)
    lu, want_piv = scipy.linalg.lu_factor(a0.astype(np.float64))
    assert (piv == want_piv).all()          # entry for entry
    np.testing.assert_allclose(got, lu, atol=1e-4 * max(1, nt))
    # every multiplier at most 1, over whole columns
    assert np.abs(np.tril(got, -1)).max() <= 1.0
    # P A0 = L U as dgetrs reads it
    pa = a0.copy()
    for j, p in enumerate(piv):
        pa[[j, p]] = pa[[p, j]]
    low = np.tril(got, -1).astype(np.float64) + np.eye(n)
    np.testing.assert_allclose(low @ np.triu(got), pa, atol=1e-5 * n)


def test_pivots_from_the_tiles_below_the_diagonal(make_ctx):
    """A tiny diagonal tile: every pivot of the first panel has to come
    from a tile under it, which a search over the first tile alone (or
    pairwise pivoting's multipliers) would not give."""
    nt, nb, ib = 3, 16, 4
    a0 = _seeded((nt * nb, nt * nb), 11)
    a0[:nb, :nb] *= 1e-6
    ctx = make_ctx("tpu")
    _tp, A, P = _factor(ctx, a0, nb, ib)
    got, piv = _stored(A, P, nt, nb)
    assert (piv[:nb] >= nb).all()
    _lu, want = scipy.linalg.lu_factor(a0.astype(np.float64))
    assert (piv == want).all()
    assert np.abs(np.tril(got, -1)).max() <= 1.0


def test_the_cells_rehearsal_size_on_the_chips_path(make_ctx):
    """N = 512 in 64-tiles, IB = 16, under the transfer guard: LAPACK's
    pivots entry for entry; every launch held nothing new; the counters
    read what the graph says."""
    nt = 8
    a0 = _seeded((nt * NB, nt * NB), 21)
    ctx = make_ctx("tpu", nb_cores=4)
    dev = _chip(ctx)
    tp, A, P = _factor(ctx, a0, NB, IB, guard=True)
    got, piv = _stored(A, P, nt, NB)
    lu, want = scipy.linalg.lu_factor(a0.astype(np.float64))
    assert (piv == want).all()
    np.testing.assert_allclose(got, lu, atol=2e-3)
    assert np.abs(np.tril(got, -1)).max() <= 1.0
    stats = dev.dump_statistics()
    tasks = sum(len(list(tc.enumerate_space())) for tc in tp.task_classes)
    assert stats["tasks"] == tasks == 8 + 28 + 140 + 28
    # in the storage of A and IPIV: every program the module built holds
    # nothing new, so every launch was in place
    for tc in tp.task_classes:
        progs = _programs(dev, tc)
        assert progs and {p.held for p in progs} == {0}, tc.name
    launches = tasks - stats["batched_tasks"] + stats["batches"]
    assert stats["lone_in_place"] + stats["groups_in_place"] == launches
    # one program a list length for the panel, none for a group of it
    assert len(_programs(dev, tp.get_task_class("GETRF"))) == nt
    # the ranged-flow counters: the sum of the list lengths
    assert stats["ranged_tiles_staged"] == _lists(nt) == 36 + 168 + 84
    assert stats["ranged_launches"] <= 8 + 28 + 28
    # every element a scatter activates: GETRF's tiles to the SWPTRSMs,
    # the GEMMs and SWPBACK; SWPTRSM's to the GEMMs; SWPBACK's onward
    want_scatters = sum(
        (nt - k - 1) + (nt - k - 1) ** 2 + (nt - k - 1) for k in range(nt)) \
        + sum(2 * (nt - k - 1) for k in range(nt)
              for _n in range(k + 1, nt)) \
        + sum(nt - k - 1 for k in range(nt) for _n in range(k))
    assert sum(es.stats["ranged_scatters"] for es in ctx.streams) == \
        want_scatters
    import jax
    assert all(isinstance(A.data_of(key), jax.Array) for key in A.keys())
    assert all(P.data_of((k, 0)).dtype == np.int32 for k in range(nt))
    assert any("ranged_tiles_staged" in d
               for d in ctx.statusz()["devices"])
