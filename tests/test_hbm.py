"""HBM residency manager (device/hbm.py): zone accounting, Belady
eviction from plan schedules, LRU fallback, and over-budget POTRF
completing via spill (reference semantics:
device_cuda_module.c:864-1179 reserve/evict, utils/zone_malloc.c)."""

import numpy as np
import pytest

import jax.numpy as jnp

from parsec_tpu.algorithms.potrf import build_potrf
from parsec_tpu.compiled.wavefront import WavefrontExecutor, plan_taskpool
from parsec_tpu.data.matrix import TiledMatrix
from parsec_tpu.device.hbm import HBMManager


def _spd(n, seed=0):
    rng = np.random.default_rng(seed)
    M = rng.standard_normal((n, n))
    return (M @ M.T + n * np.eye(n)).astype(np.float32)


def test_ensure_stages_and_accounts():
    m = HBMManager(1 << 20, unit=256)
    v = m.ensure("a", np.ones((64, 64), np.float32))
    assert isinstance(v, type(jnp.zeros(1)))
    assert m.resident_bytes() >= 64 * 64 * 4
    assert m.stats["stage_in"] == 1


def test_eviction_prefers_farthest_next_use():
    tile = np.ones((64, 64), np.float32)      # 16 KiB
    m = HBMManager(3 * 16 * 1024, unit=1024)  # room for 3 tiles
    m.ensure("soon", tile, next_use=1)
    m.ensure("later", tile.copy(), next_use=50)
    m.ensure("mid", tile.copy(), next_use=10)
    # 4th tile forces one eviction: "later" must be the victim (Belady)
    m.ensure("new", tile.copy(), next_use=2)
    assert isinstance(m.value("later"), np.ndarray), "wrong victim"
    for k in ("soon", "mid", "new"):
        assert not isinstance(m.value(k), np.ndarray), k
    assert m.stats["spills"] == 1


def test_lru_fallback_without_schedule():
    tile = np.ones((64, 64), np.float32)
    m = HBMManager(2 * 16 * 1024, unit=1024)
    m.ensure("old", tile)
    m.ensure("newer", tile.copy())
    m.ensure("old", None)                     # touch: old is now recent
    m.ensure("third", tile.copy())            # evicts "newer" (LRU)
    assert isinstance(m.value("newer"), np.ndarray)
    assert not isinstance(m.value("old"), np.ndarray)


def test_protect_prevents_working_set_eviction():
    tile = np.ones((64, 64), np.float32)
    m = HBMManager(2 * 16 * 1024, unit=1024)
    m.ensure("a", tile, protect=("a", "b"))
    m.ensure("b", tile.copy(), protect=("a", "b"))
    with pytest.raises(MemoryError):
        m.ensure("c", tile.copy(), protect=("a", "b", "c"))


def test_spill_callback_writes_back():
    got = {}
    tile = np.ones((8, 8), np.float32)
    m = HBMManager(256 + 64, unit=64)         # room for ONE tile
    m.ensure("x", tile, spill=lambda k, host: got.update({k: host}))
    m.ensure("y", tile.copy())
    assert "x" in got and got["x"].shape == (8, 8)


def test_over_budget_potrf_completes_with_spill():
    """POTRF whose tile set exceeds the budget: the segmented executor
    + manager complete it by spilling (reference: a GPU problem larger
    than device memory runs via LRU eviction), and the factor is
    correct."""
    n, nb = 512, 64                     # 36 lower tiles x 16 KiB
    A_host = _spd(n)
    A = TiledMatrix.from_array(A_host.copy(), nb, nb, name="A")
    ex = WavefrontExecutor(plan_taskpool(build_potrf(A)))
    # 12 tiles: far below the 36-tile lower triangle AND below the
    # largest wave-group working set — oversized groups are split into
    # budget-sized sub-batches, so this must still complete
    budget = 12 * nb * nb * 4
    mgr = HBMManager(budget, unit=1024)
    tiles = ex.make_tiles(host=True)
    out = ex.run_tile_dict_segmented(tiles, manager=mgr)
    ex.write_back_tiles({k: np.asarray(v) for k, v in out.items()})
    L = np.tril(A.to_array())
    err = np.linalg.norm(L @ L.T - A_host) / np.linalg.norm(A_host)
    assert err < 1e-4, err
    assert mgr.stats["spills"] > 0, "budget never exercised"
    assert mgr.stats["peak_bytes"] <= budget
    assert mgr.stats["stage_in"] > len(tiles), "no re-staging happened"


def test_eviction_policy_sweep_budget_ratios():
    """VERDICT r4 #8: the eviction policy across budget/matrix ratios
    (1/2, 1/4, 1/8) — one data point is a demo, a sweep is evidence.
    Asserts per ratio: the factor stays correct, peak stays within
    budget, and spill counts grow MONOTONICALLY as the budget shrinks;
    across the sweep, the plan-informed (Belady) policy — not the LRU
    fallback — must be doing the work (the segmented executor feeds
    next-use schedules). Reference bar: LRU + data_avail_epoch eviction
    (device_cuda_module.c:864-1179) — Belady-from-plan is the stronger
    policy the plan substrate makes possible."""
    n, nb = 512, 64
    A_host = _spd(n)
    tile_bytes = nb * nb * 4
    matrix_tiles = 36                  # lower triangle of an 8x8 grid
    spills_by_ratio = []
    belady_total = lru_total = 0
    for denom in (2, 4, 8):
        budget_tiles = max(matrix_tiles // denom, 5)
        A = TiledMatrix.from_array(A_host.copy(), nb, nb, name="A")
        ex = WavefrontExecutor(plan_taskpool(build_potrf(A)))
        mgr = HBMManager(budget_tiles * tile_bytes, unit=1024)
        out = ex.run_tile_dict_segmented(ex.make_tiles(host=True),
                                         manager=mgr)
        ex.write_back_tiles({k: np.asarray(v) for k, v in out.items()})
        L = np.tril(A.to_array())
        err = np.linalg.norm(L @ L.T - A_host) / np.linalg.norm(A_host)
        assert err < 1e-4, (denom, err)
        assert mgr.stats["peak_bytes"] <= budget_tiles * tile_bytes, \
            (denom, mgr.stats)
        spills_by_ratio.append(mgr.stats["spills"])
        belady_total += mgr.stats["evict_belady"]
        lru_total += mgr.stats["evict_lru"]
    # tighter budgets must spill at least as much
    assert spills_by_ratio == sorted(spills_by_ratio), spills_by_ratio
    assert spills_by_ratio[-1] > spills_by_ratio[0], spills_by_ratio
    # the segmented executor supplies next-use schedules: Belady must
    # carry the sweep (LRU is the no-schedule fallback only)
    assert belady_total > 0, (belady_total, lru_total)
    assert belady_total >= lru_total, (belady_total, lru_total)


@pytest.mark.parametrize("hook", ["solve", "gemm"])
@pytest.mark.parametrize("budget_tiles", [18, 5])
def test_the_budgeted_factor_is_bitwise_the_unbudgeted_one(hook,
                                                            budget_tiles):
    """Out of core changes where a tile is, never what is computed: under
    a budget of half and of an eighth of the 36-tile triangle, with the
    exact and the inverted-triangle TRSM, every tile of the factor is
    bit for bit the one the unbudgeted run gives, the manager never
    holds more than the budget, and it did spill."""
    from parsec_tpu.utils import mca_param
    n, nb = 512, 64
    A_host = _spd(n)
    mca_param.set("potrf.trsm_hook", hook)
    try:
        A1 = TiledMatrix.from_array(A_host.copy(), nb, nb, name="A")
        ex1 = WavefrontExecutor(plan_taskpool(build_potrf(A1)))
        out1 = ex1.run_tile_dict_segmented(ex1.make_tiles())

        A2 = TiledMatrix.from_array(A_host.copy(), nb, nb, name="A")
        ex2 = WavefrontExecutor(plan_taskpool(build_potrf(A2)))
        budget = budget_tiles * nb * nb * 4
        mgr = HBMManager(budget, unit=1024)
        out2 = ex2.run_tile_dict_segmented(ex2.make_tiles(host=True),
                                           manager=mgr)
    finally:
        mca_param.unset("potrf.trsm_hook")
    assert out1.keys() == out2.keys()
    for k in out1:
        assert np.array_equal(np.asarray(out1[k]), np.asarray(out2[k])), k
    assert mgr.stats["spills"] > 0
    assert mgr.stats["peak_bytes"] <= budget


def test_host_runtime_collection_spill():
    """Host-runtime POTRF with a device budget: task-written device
    tiles spill back into their collection as host numpy when the
    budget fills, and the factor stays correct."""
    import parsec_tpu as parsec
    from parsec_tpu.utils import mca_param

    n, nb = 1024, 64        # 136 written lower tiles = 2.2 MiB
    mca_param.set("device.hbm_budget_mb", 1)   # 1 MiB = 64 tiles
    # one device module → one zone: with 8 virtual devices the batching
    # manager spreads tiles over 8 per-device zones and the budget is
    # never exercised
    mca_param.set("device.tpu.max_devices", 1)
    try:
        A_host = _spd(n)
        A = TiledMatrix.from_array(A_host.copy(), nb, nb, name="A")
        ctx = parsec.init(nb_cores=2)
        assert ctx.hbm is not None
        ctx.start()
        ctx.add_taskpool(build_potrf(A))
        assert ctx.wait(timeout=120)
        spills = ctx.hbm.stats["spills"]
        peak = ctx.hbm.stats["peak_bytes"]
        parsec.fini(ctx)
        L = np.tril(A.to_array())
        err = np.linalg.norm(L @ L.T - A_host) / np.linalg.norm(A_host)
        assert err < 1e-4, err
        assert spills > 0, "budget never exercised"
        assert peak <= 1 << 20
    finally:
        mca_param.set("device.hbm_budget_mb", 0)
        mca_param.unset("device.tpu.max_devices")


def test_sweep_drops_dead_collection_entries():
    """Entries of garbage-collected collections are dropped by sweep
    (no unbounded growth across taskpools in a long-lived context)."""
    import gc
    import weakref
    from parsec_tpu.core.context import _hbm_entry_dead

    m = HBMManager(1 << 20, unit=1024)

    class DC:
        def write_tile(self, key, value):
            pass

    dc = DC()
    dc_ref = weakref.ref(dc)

    def _spill(_k, host, dc_ref=dc_ref, key=(0,)):
        target = dc_ref()
        if target is not None:
            target.write_tile(key, host)

    m.ensure("t", np.ones((16, 16), np.float32), spill=_spill)
    assert m.sweep(_hbm_entry_dead) == 0
    del dc
    gc.collect()
    assert m.sweep(_hbm_entry_dead) == 1
    assert m.resident_bytes() == 0


def test_segmented_spill_rebinds_tiles_dict():
    """When the manager spills a tile, the executor's tile dict must
    drop its device reference too (otherwise no HBM is really freed)."""
    n, nb = 512, 64
    A_host = _spd(n)
    A = TiledMatrix.from_array(A_host.copy(), nb, nb, name="A")
    ex = WavefrontExecutor(plan_taskpool(build_potrf(A)))
    mgr = HBMManager(12 * nb * nb * 4, unit=1024)
    out = ex.run_tile_dict_segmented(ex.make_tiles(host=True),
                                     manager=mgr)
    assert mgr.stats["spills"] > 0
    n_host = sum(1 for v in out.values() if isinstance(v, np.ndarray))
    n_dev = len(out) - n_host
    # resident device tiles must be bounded by the budget
    assert n_dev * nb * nb * 4 <= mgr.zone.capacity, (n_dev, n_host)


def test_put_over_budget_drops_entry():
    """A value larger than the whole budget: put raises AND the entry
    is removed — no stale superseded version stays pinned."""
    m = HBMManager(1 << 14, unit=1024)       # 16 KiB budget
    small = np.ones((16, 16), np.float32)
    m.ensure("k", small)
    big = np.ones((128, 128), np.float32)    # 64 KiB > budget
    import jax.numpy as jnp
    with pytest.raises(MemoryError):
        m.put("k", jnp.asarray(big))
    with pytest.raises(KeyError):
        m.value("k")
    assert m.resident_bytes() == 0


def test_pinned_put_survives_pressure_until_unpin():
    """A pinned entry must never be the eviction victim (the
    track->write->unpin window of the runtime completion paths);
    after unpin it is evictable again."""
    tile = jnp.ones((64, 64), jnp.float32)
    m = HBMManager(2 * 16 * 1024, unit=1024)   # room for two tiles
    spilled = {}
    m.put("pinned", tile, pin=True,
          spill=lambda k, host: spilled.update({k: host}))
    m.put("other", tile + 1)
    # pressure: the pinned entry must be passed over -> "other" spills
    m.ensure("third", np.ones((64, 64), np.float32))
    assert "pinned" not in spilled
    assert not isinstance(m.value("pinned"), np.ndarray)
    m.unpin("pinned")
    m.ensure("fourth", np.ones((64, 64), np.float32))
    assert "pinned" in spilled
    m.unpin("unknown-key")                     # no-op, no raise


def test_native_exec_hbm_tracking():
    """The native executor's write-back path enforces the budget like
    the host runtime: over-budget DAG completes with spills and the
    collection holds correct (possibly host) values."""
    from parsec_tpu.core.native_exec import NativeDAGExecutor
    from parsec_tpu import _native
    if _native.load() is None:
        pytest.skip("native core unavailable")
    rng = np.random.default_rng(5)
    n, nb = 128, 32
    M = rng.standard_normal((n, n)).astype(np.float32)
    A_in = M @ M.T + n * np.eye(n, dtype=np.float32)
    A = TiledMatrix.from_array(A_in.copy(), nb, nb, name="A")
    mgr = HBMManager(6 * nb * nb * 4, unit=1024)
    ex = NativeDAGExecutor(build_potrf(A), nworkers=2, hbm=mgr)
    ex.run()
    L = np.tril(A.to_array())
    np.testing.assert_allclose(L @ L.T, A_in, rtol=2e-4, atol=2e-3)
    assert mgr.stats["spills"] > 0


def test_a_host_value_is_staged_on_the_chip_that_asks():
    """A manager stages host values on its own chip (``home``: a
    Context's first chip module's, not JAX's first device), or on the
    chip of the module that asks, and accounts them in that chip's
    zone."""
    import jax
    devs = jax.devices()
    if len(devs) < 3:
        pytest.skip("needs three (virtual) devices")
    tile = np.ones((16, 16), np.float32)
    m = HBMManager(1 << 20, device=devs[1])
    a = m.ensure("a", tile)
    assert a.devices() == {devs[1]}
    assert m.zone is m.zone_of(devs[1])
    assert m.zone.bytes_used() >= tile.nbytes
    b = m.ensure("b", tile.copy(), device=devs[2])
    assert b.devices() == {devs[2]}
    assert m.zone_of(devs[2]).bytes_used() >= tile.nbytes
    assert m.zone_of(devs[0]).bytes_used() == 0
    # without a chip of its own, as before: wherever JAX puts it
    plain = HBMManager(1 << 20)
    c = plain.ensure("c", tile.copy())
    assert plain.zone_of(next(iter(c.devices()))).bytes_used() >= tile.nbytes
