"""Compile-once serving: persistent executor cache, bucketed panels,
preferential-pjit front end (utils/compile_cache.py + the segmented
executors). All compile assertions use compilation COUNTERS
(jax.monitoring backend-compile events through
compile_cache.backend_compile_count) — never wall clock."""

import contextlib
import os

import numpy as np
import pytest

import parsec_tpu.algorithms.potrf  # noqa: F401 — registers the
#   potrf trace knobs + panel kernels the fingerprint tests exercise
from parsec_tpu.utils import compile_cache as cc
from parsec_tpu.utils import mca_param


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _spd(n, seed=0):
    rng = np.random.default_rng(seed)
    M = rng.standard_normal((n, n))
    return (M @ M.T + n * np.eye(n)).astype(np.float32)


def _left_executor(n, nb, seed=0):
    from parsec_tpu.algorithms.potrf import build_potrf_left
    from parsec_tpu.compiled.panels import PanelExecutor
    from parsec_tpu.compiled.wavefront import plan_taskpool
    from parsec_tpu.data.matrix import TiledMatrix
    A = TiledMatrix.from_array(_spd(n, seed), nb, nb, name="A")
    return A, PanelExecutor(plan_taskpool(build_potrf_left(A)))


@contextlib.contextmanager
def _tmp_store(path):
    """Enable the persistent store at ``path``, restoring the process
    jax-cache config and store state afterwards (both are process
    globals the other tests must not inherit)."""
    import jax
    prev = jax.config.jax_compilation_cache_dir
    try:
        cc.enable_compile_cache(str(path))
        yield
    finally:
        cc.disable_compile_cache()
        jax.config.update("jax_compilation_cache_dir", prev)


# ---------------------------------------------------------------------------
# bucket lattice
# ---------------------------------------------------------------------------

def test_bucket_lattice_shape():
    from parsec_tpu.compiled.panels import bucket_tiles
    # exact to 16; 2^(log2-3)-multiples above; never exceeds the cap
    for t in range(1, 17):
        assert bucket_tiles(t, 100) == t
    assert bucket_tiles(17, 100) == 18
    assert bucket_tiles(33, 100) == 36
    assert bucket_tiles(41, 100) == 44
    assert bucket_tiles(67, 100) == 72
    for t in range(1, 120):
        b = bucket_tiles(t, 64)
        assert t <= b or b == 64
        assert b <= 64
        if t <= 64:
            assert (b - t) / t <= 0.125 + 1e-9
    # lattice points are absolute: a smaller grid's buckets are a
    # subset of a larger grid's (the cross-N reuse property), except
    # the cap point itself
    big = {bucket_tiles(t, 40) for t in range(1, 41)}
    small = {bucket_tiles(t, 32) for t in range(1, 33)}
    assert small - big <= {32}


# ---------------------------------------------------------------------------
# fingerprints + key invalidation (satellite: dtype / NB / trsm_hook /
# version-salt must miss; same-bucket must hit)
# ---------------------------------------------------------------------------

def _make_body(a):
    def body(x):
        return x * a
    return body


def test_function_fingerprint_stable_and_sensitive():
    s1, d1 = cc.function_fingerprint(_make_body(2.0))
    s2, d2 = cc.function_fingerprint(_make_body(2.0))
    s3, d3 = cc.function_fingerprint(_make_body(3.0))
    assert s1 and s2 and s3
    assert d1 == d2              # same code + closure literals
    assert d1 != d3              # closure value differs

    class Opaque:
        pass

    def closes_over_object(o=Opaque()):
        def body(x):
            return x
        body.__defaults__ = None
        return lambda x: (x, o)

    stable, _ = cc.function_fingerprint(closes_over_object())
    assert not stable            # unhashable closure cell → unstable


def test_lowering_fingerprint_invalidation():
    base = cc.lowering_fingerprint("k", (64, 64), "float32")
    assert base == cc.lowering_fingerprint("k", (64, 64), "float32")
    # dtype flip
    assert base != cc.lowering_fingerprint("k", (64, 64), "float64")
    # NB / bucket-shape flip
    assert base != cc.lowering_fingerprint("k", (128, 128), "float32")
    # body-hook knob flip (registered trace knob)
    mca_param.set("potrf.trsm_hook", "gemm")
    try:
        assert base != cc.lowering_fingerprint("k", (64, 64), "float32")
    finally:
        mca_param.unset("potrf.trsm_hook")
    # version-salt flip
    mca_param.set("jit.cache_salt", "r99")
    try:
        assert base != cc.lowering_fingerprint("k", (64, 64), "float32")
    finally:
        mca_param.unset("jit.cache_salt")
    assert base == cc.lowering_fingerprint("k", (64, 64), "float32")


def test_cached_jit_store_roundtrip(tmp_path):
    """Persistent layer: compile once, then a simulated fresh process
    (in-process store cleared) must deserialize — ZERO XLA compiles."""
    import jax
    import jax.numpy as jnp
    with _tmp_store(tmp_path / "cache"):
        sds = jax.ShapeDtypeStruct((16, 16), np.float32)
        key = ("roundtrip-test", (16, 16), "float32")
        s0 = cc.cache_stats()
        fn = cc.cached_jit(lambda x: x * 2 + 1, key=key,
                           example_args=(sds,))
        assert float(fn(jnp.ones((16, 16))).sum()) == 16 * 16 * 3
        s1 = cc.cache_stats()
        assert s1["store_misses"] == s0["store_misses"] + 1
        # same key, same process: the SAME callable, no store traffic
        assert cc.cached_jit(lambda x: x * 2 + 1, key=key,
                             example_args=(sds,)) is fn
        # "new process"
        cc.reset_in_process_cache()
        c0 = cc.backend_compile_count()
        fn2 = cc.cached_jit(lambda x: x * 2 + 1, key=key,
                            example_args=(sds,))
        assert float(fn2(jnp.ones((16, 16))).sum()) == 16 * 16 * 3
        assert cc.backend_compile_count() == c0
        s2 = cc.cache_stats()
        assert s2["store_hits"] == s1["store_hits"] + 1


def test_store_knob_invalidation_end_to_end(tmp_path):
    """Flipping potrf.trsm_hook between store runs must MISS (the
    segmented programs trace different kernels); flipping back must HIT
    again — counter-asserted, no wall clock."""
    with _tmp_store(tmp_path / "cache"):
        _, ex = _left_executor(256, 64)
        ex.run(segmented=True)
        s0 = cc.cache_stats()
        cc.reset_in_process_cache()
        mca_param.set("potrf.trsm_hook", "gemm")
        try:
            _, ex2 = _left_executor(256, 64, seed=1)
            ex2.run(segmented=True)
            s1 = cc.cache_stats()
            # kernel programs re-lowered (inverse-multiply variants):
            # misses grew; the knob-independent window programs may hit
            assert s1["store_misses"] > s0["store_misses"]
        finally:
            mca_param.unset("potrf.trsm_hook")
        cc.reset_in_process_cache()
        c0 = cc.backend_compile_count()
        _, ex3 = _left_executor(256, 64, seed=2)
        ex3.run(segmented=True)
        assert cc.backend_compile_count() == c0     # back to full hits


def _factor_error(A, a_host):
    L = np.tril(A.to_array())
    return np.linalg.norm(L @ L.T - a_host) / np.linalg.norm(a_host)


def _serve_panel_segmented(seed):
    """One serving process of the flagship's shape: resolve every
    program of the segmented walk, then run. Returns the programs the
    walk holds and the factor's residual."""
    A, ex = _left_executor(192, 64, seed)
    programs = ex.prepare_segments()
    ex.write_back(ex.run_state_segmented(ex.make_state()))
    return programs, _factor_error(A, _spd(192, seed))


def _serve_wavefront_segmented(seed):
    from parsec_tpu.algorithms.potrf import build_potrf
    from parsec_tpu.compiled.wavefront import (WavefrontExecutor,
                                               plan_taskpool)
    from parsec_tpu.data.matrix import TiledMatrix
    A = TiledMatrix.from_array(_spd(256, seed), 64, 64, name="A")
    ex = WavefrontExecutor(plan_taskpool(build_potrf(A)))
    ex.write_back_tiles(ex.run_tile_dict_segmented(ex.make_tiles()))
    return len(ex._segments), _factor_error(A, _spd(256, seed))


def _serve_panel_monolith(seed):
    A, ex = _left_executor(192, 64, seed)
    ex.write_back(ex.jitted(ex.make_state()))
    return 1, _factor_error(A, _spd(192, seed))


@pytest.mark.parametrize("serve", [_serve_panel_segmented,
                                   _serve_wavefront_segmented,
                                   _serve_panel_monolith],
                         ids=["panel_segmented", "wavefront_segmented",
                              "panel_monolith"])
def test_a_second_process_compiles_nothing_and_loads_every_program(
        tmp_path, serve):
    """The compile-once claim across processes, for each compiled path
    and under the flagship's kernels (``potrf.trsm_hook=gemm``): the
    first process at a size compiles and stores every program it
    resolves; a second process (the in-process store cleared, the
    directory kept) resolves the same number, every one from the store,
    with no XLA compile, and its factor is right."""
    mca_param.set("potrf.trsm_hook", "gemm")
    try:
        with _tmp_store(tmp_path / "cache"):
            cc.reset_in_process_cache()      # cold whatever ran before
            s0 = cc.cache_stats()
            programs, _ = serve(0)
            s1 = cc.cache_stats()
            assert programs > 0
            assert s1["store_misses"] - s0["store_misses"] == programs
            assert s1["store_hits"] == s0["store_hits"]

            cc.reset_in_process_cache()      # "the second process"
            compiled = cc.backend_compile_count()
            again, err = serve(1)
            s2 = cc.cache_stats()
            assert cc.backend_compile_count() == compiled
            assert again == programs
            assert s2["store_hits"] - s1["store_hits"] == programs
            assert s2["store_misses"] == s1["store_misses"]
            assert err < 1e-4, err
    finally:
        mca_param.unset("potrf.trsm_hook")


def test_jit_cache_dir_knob_auto_enables(tmp_path, monkeypatch):
    """jit.cache_dir MCA knob auto-enables the store (no manual
    enable_compile_cache call); '' disables; PARSEC_COMPILE_CACHE=0 is
    the kill switch that overrides the knob."""
    import jax
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("PARSEC_COMPILE_CACHE", raising=False)
    d = str(tmp_path / "knobcache")
    try:
        cc.disable_compile_cache()
        mca_param.set("jit.cache_dir", d)
        store = cc.executor_store()
        assert store is not None and store.root.startswith(d)
        # kill switch wins over the knob
        cc.disable_compile_cache()
        monkeypatch.setenv("PARSEC_COMPILE_CACHE", "0")
        assert cc.executor_store() is None
        monkeypatch.delenv("PARSEC_COMPILE_CACHE")
        # '' = off
        cc.disable_compile_cache()
        mca_param.set("jit.cache_dir", "")
        assert cc.executor_store() is None
    finally:
        mca_param.unset("jit.cache_dir")
        cc.disable_compile_cache()
        jax.config.update("jax_compilation_cache_dir", prev)


def test_cache_placed_from_outside_is_never_moved(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: the XLA cache stays where JAX put
    it (no code path rewrites jax_compilation_cache_dir) and the store
    is <dir>/executors — whatever an explicit path, the
    PARSEC_COMPILE_CACHE path or the jit.cache_dir knob say."""
    import jax
    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    prev = jax.config.jax_compilation_cache_dir
    want = os.path.join(placed, "executors")
    try:
        cc.disable_compile_cache()
        assert cc.enable_compile_cache(str(tmp_path / "explicit")) == placed
        assert cc.executor_store().root == want
        cc.disable_compile_cache()
        monkeypatch.setenv("PARSEC_COMPILE_CACHE", str(tmp_path / "env"))
        assert cc.executor_store().root == want
        monkeypatch.delenv("PARSEC_COMPILE_CACHE")
        cc.disable_compile_cache()
        mca_param.set("jit.cache_dir", str(tmp_path / "knob"))
        assert cc.executor_store().root == want
        cc.disable_compile_cache()
        mca_param.set("jit.cache_dir", "")       # the env var is the opt-in
        assert cc.executor_store().root == want
        assert jax.config.jax_compilation_cache_dir == prev
        assert sorted(os.listdir(tmp_path)) == ["placed"]
        # the kill switch still wins
        cc.disable_compile_cache()
        monkeypatch.setenv("PARSEC_COMPILE_CACHE", "0")
        assert cc.executor_store() is None
    finally:
        mca_param.unset("jit.cache_dir")
        cc.disable_compile_cache()


def test_auto_is_the_fixed_checkout_cache(monkeypatch):
    """Nothing placed from outside: ``auto`` is <checkout>/.xla_cache —
    a fixed path, because the path is part of the XLA cache key."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("PARSEC_COMPILE_CACHE", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    mca_param.set("jit.cache_dir", "auto")
    try:
        assert cc._resolve_dir() == os.path.join(root, ".xla_cache")
    finally:
        mca_param.unset("jit.cache_dir")


def test_store_roundtrip_on_many_device_host(tmp_path):
    """Regression for the store on the 8-virtual-device platform: a
    program compiled for ONE device (not the default one), and a mesh
    program on 4 of the 8, reload as callables that RUN — the store
    used to load every program over all devices of the backend, which
    then rejected its own arguments."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import (NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)
    from parsec_tpu.compiled.spmd import compile_with_plan, make_mesh
    dev = jax.devices()[5]
    mesh = make_mesh(4, axis="rows")
    sh = NamedSharding(mesh, P("rows"))
    x = np.arange(64, dtype=np.float32).reshape(8, 8)

    def one():
        return cc.cached_jit(
            _times_two_plus_one, key=("manydev-1", dev.id),
            example_args=(jax.ShapeDtypeStruct(
                (8, 8), jnp.float32, sharding=SingleDeviceSharding(dev)),))

    def meshed():
        return compile_with_plan(
            _times_two_plus_one, mesh=mesh, in_shardings=(sh,),
            out_shardings=sh, key=("manydev-mesh",),
            example_args=(jax.ShapeDtypeStruct((8, 8), jnp.float32),))

    with _tmp_store(tmp_path / "cache"):
        for build, where in ((one, dev), (meshed, sh)):
            build()
            cc.reset_in_process_cache()
            s0 = cc.cache_stats()
            out = build()(jax.device_put(x, where))
            assert cc.cache_stats()["store_hits"] == s0["store_hits"] + 1
            np.testing.assert_array_equal(np.asarray(out), x * 2 + 1)
        assert out.sharding.is_equivalent_to(sh, 2)
    assert cc.cache_stats()["store_errors"] == 0


def _times_two_plus_one(x):
    return x * 2 + 1


def test_unloadable_store_entry_raises(tmp_path):
    """An entry that exists but cannot be loaded is an error, not a
    quiet recompile: that quiet miss is what hid the broken store."""
    import jax
    with _tmp_store(tmp_path / "cache"):
        key = ("corrupt-entry-test",)
        sds = jax.ShapeDtypeStruct((4,), np.float32)
        cc.cached_jit(_times_two_plus_one, key=key, example_args=(sds,))
        store = cc.executor_store()
        (entry,) = os.listdir(store.root)
        with open(os.path.join(store.root, entry), "wb") as fh:
            fh.write(b"not a pickle")
        cc.reset_in_process_cache()
        with pytest.raises(RuntimeError, match="cannot be loaded"):
            cc.cached_jit(_times_two_plus_one, key=key, example_args=(sds,))


def test_store_rejects_a_program_reloaded_onto_other_devices(tmp_path,
                                                             monkeypatch):
    """libtpu reloads a one-device program compiled for a non-default
    chip onto its first chip. The CPU backend does not, so play that
    backend: the store must notice at load, not at the first call."""
    import jax
    from jax.experimental import serialize_executable as se
    sds = jax.ShapeDtypeStruct((4,), np.float32)
    on_dev0 = jax.jit(_times_two_plus_one).lower(sds).compile()
    with _tmp_store(tmp_path / "cache"):
        key = ("reloaded-elsewhere", 3)
        cc.cached_jit(_times_two_plus_one, key=key, example_args=(
            jax.ShapeDtypeStruct((4,), np.float32,
                                 sharding=jax.sharding.SingleDeviceSharding(
                                     jax.devices()[3])),))
        cc.reset_in_process_cache()
        monkeypatch.setattr(se, "deserialize_and_load",
                            lambda *a, **k: on_dev0)
        with pytest.raises(RuntimeError, match=r"compiled for devices "
                           r"\[3\] but the backend reloaded it onto \[0\]"):
            cc.cached_jit(_times_two_plus_one, key=key, example_args=(sds,))


# ---------------------------------------------------------------------------
# compile-once across executors / problem sizes (the acceptance row:
# second run of any NEW N at a served (NB, dtype) pays zero compiles)
# ---------------------------------------------------------------------------

def test_panel_segmented_new_n_second_run_zero_compiles():
    # nb=32 sizes are unique to this test, so the shared in-process
    # caches are honestly cold here regardless of suite ordering
    _, ex1 = _left_executor(256, 32)
    ex1.run(segmented=True)

    # rebuilt executor, same config: everything shared — zero
    c0 = cc.backend_compile_count()
    _, ex1b = _left_executor(256, 32, seed=1)
    ex1b.run(segmented=True)
    assert cc.backend_compile_count() == c0

    # NEW problem size at the served (NB, dtype): first run pays the
    # thin per-N window programs + unseen buckets; the heavy kernels
    # for already-seen buckets come from the shared cache
    c0 = cc.backend_compile_count()
    _, ex2 = _left_executor(416, 32, seed=2)
    ex2.run(segmented=True)
    first_new_n = cc.backend_compile_count() - c0
    assert first_new_n > 0

    # SECOND run of the new N: zero XLA compiles — the acceptance row
    c0 = cc.backend_compile_count()
    A3, ex3 = _left_executor(416, 32, seed=3)
    ex3.run(segmented=True)
    assert cc.backend_compile_count() == c0
    L = np.tril(A3.to_array())
    A3h = _spd(416, 3)
    err = np.linalg.norm(L @ L.T - A3h) / np.linalg.norm(A3h)
    assert err < 1e-4, err


def test_panel_monolith_shared_across_executors():
    """The whole-DAG fused program is shared by semantic key, not by
    function object — rebuilding an executor never re-traces (the
    wavefront.py jit-by-function-object footgun, panel side)."""
    _, ex1 = _left_executor(256, 64)
    _, ex2 = _left_executor(256, 64, seed=1)
    assert ex1.monolith_cache_key() is not None
    assert ex1.jitted is ex2.jitted


def test_wavefront_segments_shared_across_executors():
    """Satellite: rebuilding a WavefrontExecutor for the same (class,
    bucket) never re-traces — jitted segments come from the
    module-level keyed cache, and a rebuilt executor performs ZERO new
    backend compiles."""
    from parsec_tpu.algorithms.potrf import build_potrf
    from parsec_tpu.compiled.wavefront import (WavefrontExecutor,
                                               plan_taskpool)
    from parsec_tpu.data.matrix import TiledMatrix

    A1 = TiledMatrix.from_array(_spd(256), 64, 64, name="A")
    ex1 = WavefrontExecutor(plan_taskpool(build_potrf(A1)))
    ex1.run_tile_dict_segmented(ex1.make_tiles())

    c0 = cc.backend_compile_count()
    A2 = TiledMatrix.from_array(_spd(256, 1), 64, 64, name="A")
    ex2 = WavefrontExecutor(plan_taskpool(build_potrf(A2)))
    ex2.run_tile_dict_segmented(ex2.make_tiles())
    assert cc.backend_compile_count() == c0
    # the shared fns are literally the same objects
    for key, fn in ex2._segments.items():
        assert ex1._segments.get(key) is fn, key


def test_wavefront_segments_shared_across_problem_sizes():
    """The PARITY claim: the segmented executor's cache is shared
    across waves, runs, AND problem sizes — two sizes at one NB, then
    a second run of the second size with zero new compiles."""
    from parsec_tpu.algorithms.potrf import build_potrf
    from parsec_tpu.compiled.wavefront import (WavefrontExecutor,
                                               plan_taskpool)
    from parsec_tpu.data.matrix import TiledMatrix

    A1 = TiledMatrix.from_array(_spd(320, 5), 64, 64, name="A")
    ex1 = WavefrontExecutor(plan_taskpool(build_potrf(A1)))
    ex1.run_tile_dict_segmented(ex1.make_tiles())

    A2 = TiledMatrix.from_array(_spd(512, 6), 64, 64, name="A")
    ex2 = WavefrontExecutor(plan_taskpool(build_potrf(A2)))
    ex2.run_tile_dict_segmented(ex2.make_tiles())

    c0 = cc.backend_compile_count()
    A3 = TiledMatrix.from_array(_spd(512, 7), 64, 64, name="A")
    ex3 = WavefrontExecutor(plan_taskpool(build_potrf(A3)))
    out = ex3.run_tile_dict_segmented(ex3.make_tiles())
    assert cc.backend_compile_count() == c0
    ex3.write_back_tiles(out)
    L = np.tril(A3.to_array())
    ref = _spd(512, 7)
    assert np.linalg.norm(L @ L.T - ref) / np.linalg.norm(ref) < 1e-4


def test_tpu_device_body_jit_unified():
    """device/tpu.py's one program table: two device modules and two
    chores (two taskpools) of the same stable body share the program of
    one AND the group programs process-wide."""
    from types import SimpleNamespace
    import jax
    from parsec_tpu.core.task import (GROUP_SIZES, Chore, DeviceType, Flow,
                                      FlowAccess)
    from parsec_tpu.device.tpu import TPUDevice

    task = SimpleNamespace(task_class=SimpleNamespace(
        flows=[Flow("x", FlowAccess.RW)]))
    values = [np.ones((4, 4), np.float32)]
    d1, d2 = (TPUDevice(jax.devices()[0]) for _ in range(2))
    c1 = Chore(device_type=DeviceType.TPU, hook=_module_level_body)
    c2 = Chore(device_type=DeviceType.TPU, hook=_module_level_body)
    # distinct chore objects, distinct devices — one table's worth
    p1 = d1._programs(task, c1, values, d1._sig(values))
    c0 = cc.backend_compile_count()
    p2 = d2._programs(task, c2, values, d2._sig(values))
    assert cc.backend_compile_count() == c0
    assert sorted(p1) == sorted((*GROUP_SIZES, 1))
    assert all(p1[size] is p2[size] for size in p1)
    (two,) = p2[1](*values)
    assert (np.asarray(two) == 2.0).all()


def _module_level_body(task, x):
    return x + 1


# ---------------------------------------------------------------------------
# preferential-pjit front end
# ---------------------------------------------------------------------------

def test_compile_with_plan_pjit_path():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from parsec_tpu.compiled.spmd import compile_with_plan, make_mesh

    mesh = make_mesh(8, axis="tiles")
    s = NamedSharding(mesh, P("tiles"))

    def step(d):
        return {k: v * 2 for k, v in d.items()}

    fn = compile_with_plan(step, mesh=mesh, in_shardings=({"a": s},),
                           out_shardings={"a": s}, key=("t-pjit",))
    x = jax.device_put(jnp.arange(32.0).reshape(8, 4), s)
    out = fn({"a": x})
    assert np.allclose(np.asarray(out["a"]),
                       np.arange(32.0).reshape(8, 4) * 2)
    # same key → same cached callable (the pjit product enters the
    # shared store like every other executor program)
    fn2 = compile_with_plan(step, mesh=mesh, in_shardings=({"a": s},),
                            out_shardings={"a": s}, key=("t-pjit",))
    assert fn2 is fn


def test_compile_with_plan_requires_both_shardings():
    from parsec_tpu.compiled.spmd import compile_with_plan, make_mesh
    mesh = make_mesh(8)
    with pytest.raises(ValueError, match="BOTH"):
        compile_with_plan(lambda x: x, mesh=mesh,
                          in_shardings=("whatever",))


def test_compile_with_plan_shard_map_fallback():
    import jax.numpy as jnp
    from parsec_tpu.compiled.spmd import compile_with_plan, make_mesh

    mesh = make_mesh(8, axis="tiles")

    def local_scale(x):          # shard-local: per-slot independent
        return x * 3.0

    fn = compile_with_plan(local_scale, mesh=mesh, key=("t-sm",))
    x = jnp.arange(64.0).reshape(8, 8)
    assert np.allclose(np.asarray(fn(x)), np.asarray(x) * 3.0)


def test_run_sharded_still_correct():
    """run_sharded through the preferential-pjit front end: unchanged
    numerics, and a REBUILT executor re-serves from the shared cache
    with zero new backend compiles."""
    from parsec_tpu.algorithms.potrf import build_potrf
    from parsec_tpu.compiled.spmd import make_mesh, run_sharded
    from parsec_tpu.compiled.wavefront import (WavefrontExecutor,
                                               plan_taskpool)
    from parsec_tpu.data.matrix import TiledMatrix

    mesh = make_mesh(8, axis="tiles")
    Ah = _spd(256, 11)
    A1 = TiledMatrix.from_array(Ah.copy(), 64, 64, name="A")
    ex1 = WavefrontExecutor(plan_taskpool(build_potrf(A1)))
    run_sharded(ex1, mesh=mesh)
    L = np.tril(A1.to_array())
    assert np.linalg.norm(L @ L.T - Ah) / np.linalg.norm(Ah) < 1e-4

    c0 = cc.backend_compile_count()
    A2 = TiledMatrix.from_array(Ah.copy(), 64, 64, name="A")
    ex2 = WavefrontExecutor(plan_taskpool(build_potrf(A2)))
    run_sharded(ex2, mesh=mesh)
    assert cc.backend_compile_count() == c0
