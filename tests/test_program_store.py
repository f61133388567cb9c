"""The chip module's programs through the executor store
(``TPUDevice._build``, ``utils/compile_cache.py``): a second process (the
in-process store cleared, the store's directory kept) loads every program
the first traced and lowered, traces nothing, computes the same bits in
the buffers it was given, and keeps each program's name; the store's key
covers the package's source and a body's own file outside it; a body with
an unstable fingerprint and a Registry with several chip modules leave the
store as it was. CPU platform, ``TPUDevice`` on the CPU backend (the
chip's path), Python engine."""

import contextlib
import functools
import importlib.util
import os
import shutil

import numpy as np
import pytest

import parsec_tpu as parsec
from parsec_tpu import dtd
from parsec_tpu.algorithms import build_geqrf, build_getrf_1d
from parsec_tpu.algorithms import geqrf as geqrf_module
from parsec_tpu.algorithms import getrf as getrf_module
from parsec_tpu.algorithms.getrf import getrf_1d_ipiv_collection
from parsec_tpu.core.task import DeviceType
from parsec_tpu.data import TiledMatrix
from parsec_tpu.utils import compile_cache as cc
from parsec_tpu.utils import mca_param


@contextlib.contextmanager
def _store(path):
    """The executor store (and XLA's cache) at ``path``; the process's
    jax cache config and store state restored afterwards (both are
    process globals the other tests must not inherit)."""
    import jax
    prev = jax.config.jax_compilation_cache_dir
    try:
        cc.enable_compile_cache(str(path))
        yield cc.executor_store()
    finally:
        cc.disable_compile_cache()
        jax.config.update("jax_compilation_cache_dir", prev)


def _entries(store):
    return sorted(os.listdir(store.root))


@pytest.fixture
def make_ctx():
    """Contexts of ``chips`` chip modules, one worker (a deterministic
    order of takes, so equal programs give equal bits), the inline CPU
    device weighted out: the chip's path."""
    made = []
    knobs = ("device.tpu.max_devices", "runtime.native_dtd")

    def make(chips=1):
        mca_param.set("device.tpu.max_devices", chips)
        mca_param.set("runtime.native_dtd", 0)
        ctx = parsec.init(nb_cores=1)
        ctx.start()
        made.append(ctx)
        for d in ctx.devices.devices:
            if d.name == "cpu":
                d.weight = 1e-6
        return ctx

    yield make
    for ctx in made:
        parsec.fini(ctx)
    for knob in knobs:
        mca_param.unset(knob)


def _seeded(shape, seed):
    return np.random.default_rng(seed).uniform(
        -0.5, 0.5, shape).astype(np.float32)


def _on_chip(a0, nb, name="A"):
    """``a0`` in ``nb``-tiles, every tile a ``jax.Array`` (as a cell's)."""
    import jax.numpy as jnp
    A = TiledMatrix.from_array(a0.copy(), nb, nb, name=name)
    for key in A.keys():
        A.write_tile(key, jnp.asarray(A.data_of(key)))
    return A


def _lu1d():
    """The panel-pivoted LU cell's rehearsal: N = 512 in 64-tiles, IB 16;
    GEMM's tile kernel, called by name, is what a trace runs."""
    import jax.numpy as jnp
    A = _on_chip(_seeded((512, 512), 21), 64)
    P = getrf_1d_ipiv_collection(A)
    for k in range(A.mt):
        P.write_tile((k, 0), jnp.zeros((1, 64), jnp.int32))
    return build_getrf_1d(A, P, ib=16), (A, P)


def _qr():
    """The QR cell's rehearsal: N = 256 in 32-tiles, IB 16; TSMQR's tile
    kernel, called by name, is what a trace runs."""
    A = _on_chip(_seeded((256, 256), 22), 32)
    tp = build_geqrf(A, ib=16)
    return tp, (A, tp.g.T)


def _chip(ctx):
    (dev,) = ctx.devices.chips
    return dev


def _programs(dev, tp):
    """``{(the name it should have, its slot in the module's table): (its
    HLO module's name, the bytes of new outputs a launch of it holds)}``
    of every program the module built for ``tp``."""
    out = {}
    for tc in tp.task_classes:
        for chore in tc.incarnations:
            for slot, programs in dev._table.get(id(chore), {}).items():
                if slot == "pinned":
                    continue
                for size, program in programs.items():
                    (module,) = program.runtime_executable().hlo_modules()
                    out[f"jit_parsec_{tc.name}_x{size}", slot] = (
                        module.name, program.held)
    return out


@pytest.mark.parametrize("build,module,kernel", [
    (_lu1d, getrf_module, "gemm_full_tile"),
    (_qr, geqrf_module, "tsmqr_tile"),
], ids=["getrf_1d", "geqrf"])
def test_a_second_process_loads_every_program_and_traces_none(
        make_ctx, tmp_path, monkeypatch, build, module, kernel):
    """Two runs of a pool through one chip module with the store in
    ``tmp_path``, the in-process store cleared before each: the first
    traces, lowers and stores every program; the second loads every one
    from the store (the store's hits), traces none (the body's kernel is
    never called), gives bitwise the same tiles, deletes the same input
    tiles it was given (each program holds what it held), and each
    program keeps its name."""
    traced = [0]
    plain = getattr(module, kernel)

    @functools.wraps(plain)
    def counted(*args, **kwargs):
        traced[0] += 1                  # runs while a body is traced
        return plain(*args, **kwargs)

    monkeypatch.setattr(module, kernel, counted)

    def run():
        import jax
        cc.reset_in_process_cache()     # a new process, the store kept
        traced[0] = 0
        before = cc.cache_stats()
        ctx = make_ctx()
        tp, mats = build()
        given = {(m.name, key): m.data_of(key) for m in mats
                 for key in m.keys() if isinstance(m.data_of(key), jax.Array)}
        ctx.add_taskpool(tp)
        assert ctx.wait(timeout=600)
        dev = _chip(ctx)
        programs = _programs(dev, tp)
        after = cc.cache_stats()
        return {"stats": dev.dump_statistics(), "programs": programs,
                "traced": traced[0],
                "hits": after["store_hits"] - before["store_hits"],
                "misses": after["store_misses"] - before["store_misses"],
                "tiles": {(m.name, key): np.asarray(m.data_of(key))
                          for m in mats for key in m.keys()},
                "deleted": {k for k, x in given.items() if x.is_deleted()}}

    with _store(tmp_path / "cache") as store:
        first = run()
        stored = len(_entries(store))
        second = run()
        assert len(_entries(store)) == stored
    built = first["stats"]["programs_traced"]
    assert built > 0 and first["traced"] > 0
    assert first["stats"]["programs_loaded"] == 0
    assert first["misses"] == built == stored and first["hits"] == 0
    # the second process: every program from the store, nothing traced
    assert second["stats"]["programs_traced"] == 0
    assert second["traced"] == 0
    assert second["stats"]["programs_loaded"] == built == second["hits"]
    assert second["misses"] == 0
    assert len(second["programs"]) == built
    # the same bits, the same buffers given (so the same bytes held
    # anew a launch), the same names
    assert first["tiles"].keys() == second["tiles"].keys()
    for key, tile in first["tiles"].items():
        np.testing.assert_array_equal(second["tiles"][key], tile,
                                      err_msg=str(key))
    assert second["deleted"] == first["deleted"] != set()
    assert second["programs"] == first["programs"]
    for (want, _slot), (got, _held) in second["programs"].items():
        assert got == want


# -- the key ---------------------------------------------------------------

def _times_three(x):
    return x * 3


def test_the_key_covers_the_packages_source(tmp_path, monkeypatch):
    """A copy of the package digests as the package does (its content,
    not its place); one byte changed in one of its modules changes every
    lowering fingerprint, and a program stored under the old source
    misses and is compiled anew."""
    import jax
    copy = tmp_path / "parsec_tpu"
    shutil.copytree(cc._PACKAGE, copy, ignore=shutil.ignore_patterns(
        "__pycache__", "build", "*.so*"))
    key = ("package-source", (4,), "float32")
    sds = jax.ShapeDtypeStruct((4,), np.float32)
    base = cc.lowering_fingerprint(*key)
    monkeypatch.setattr(cc, "_PACKAGE", str(copy))
    try:
        assert cc.lowering_fingerprint(*key) == base
        with _store(tmp_path / "cache") as store:
            cc.reset_in_process_cache()
            cc.cached_jit(_times_three, key=key, example_args=(sds,))
            assert len(_entries(store)) == 1
            path = copy / "ops" / "tile_kernels.py"
            src = path.read_bytes()
            at = src.index(b"kernel")
            path.write_bytes(src[:at] + b"K" + src[at + 1:])
            cc._tree_digest.cache_clear()
            assert cc.lowering_fingerprint(*key) != base
            cc.reset_in_process_cache()
            s0 = cc.cache_stats()
            fn, origin = cc.cached_program(_times_three, key=key,
                                           example_args=(sds,))
            s1 = cc.cache_stats()
            assert origin == "traced"
            assert s1["store_misses"] == s0["store_misses"] + 1
            assert s1["store_hits"] == s0["store_hits"]
            assert len(_entries(store)) == 2
            np.testing.assert_array_equal(
                np.asarray(fn(np.ones(4, np.float32))), np.full(4, 3.0))
    finally:
        cc._tree_digest.cache_clear()


_USER_BODY = """
def helper(c):
    return c + {add}


def body(c):
    return helper(c)
"""


def _user_module(path, add):
    """A body's own module outside the package, written with ``add``,
    imported anew (a new process would)."""
    path.write_text(_USER_BODY.format(add=add))
    cc._file_digest.cache_clear()
    spec = importlib.util.spec_from_file_location("user_body", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _add_to_tiles(ctx, fn, nb=8, nt=2, name="C"):
    """``fn`` on each tile of an ``nt`` x ``nt`` matrix of ones, a pure
    chip body through the DTD front end: the tiles after it."""
    C = TiledMatrix.from_array(np.ones((nt * nb, nt * nb), np.float32),
                               nb, nb, name=name)
    tp = dtd.Taskpool(name)
    ctx.add_taskpool(tp)
    for key in C.keys():
        tp.insert_task(fn, dtd.TileArg(C, key, dtd.INOUT),
                       device=DeviceType.TPU, pure=True)
    tp.wait()
    return C.to_array()


def test_a_body_outside_the_package_keys_its_own_file(make_ctx, tmp_path):
    """The body calls a helper of its own module by name, which the
    body's fingerprint does not cover: a second process loads the
    program while the file is unchanged, and misses once the helper was
    edited, which a stale program would have hidden. The package's own
    bodies add nothing of their files."""
    assert cc.source_digest(getrf_module.build_getrf_1d) == ""
    assert cc.source_digest(functools.partial(np.add, 1)) == ""
    path = tmp_path / "user_body.py"
    runs = []
    with _store(tmp_path / "cache") as store:
        for add in (1, 1, 2):
            cc.reset_in_process_cache()
            mod = _user_module(path, add)
            assert cc.source_digest(functools.partial(mod.body)) != ""
            ctx = make_ctx()
            np.testing.assert_array_equal(
                _add_to_tiles(ctx, mod.body), np.full((16, 16), 1.0 + add))
            stats = _chip(ctx).dump_statistics()
            runs.append((stats["programs_traced"], stats["programs_loaded"]))
        built = runs[0][0]
        assert built > 0
        assert runs == [(built, 0), (0, built), (built, 0)]
        assert len(_entries(store)) == 2 * built


class _Scale:
    """An object a body closes over: its fingerprint falls back to the
    object's id, valid in this process alone."""

    def __init__(self, by):
        self.by = by


def _scaled(scale):
    def body(c):
        return c * scale.by
    return body


def _doubled(c):
    return c * 2.0


@pytest.mark.parametrize("chips,body", [(1, _scaled(_Scale(2.0))),
                                        (2, _doubled)],
                         ids=["unstable_body", "two_chip_modules"])
def test_the_store_is_left_as_it_was(make_ctx, tmp_path, chips, body):
    """A body whose fingerprint is not stable, and any body where the
    Registry has two chip modules (CPU devices of the 8 the tests force):
    programs traced in the process as before, nothing asked of the store
    and nothing written to it."""
    with _store(tmp_path / "cache") as store:
        cc.reset_in_process_cache()
        before = cc.cache_stats()
        ctx = make_ctx(chips)
        assert len(ctx.devices.chips) == chips
        np.testing.assert_array_equal(_add_to_tiles(ctx, body),
                                      np.full((16, 16), 2.0))
        after = cc.cache_stats()
        assert _entries(store) == []
        for counter in ("store_hits", "store_misses"):
            assert after[counter] == before[counter]
        stats = [d.dump_statistics() for d in ctx.devices.chips]
        assert sum(s["programs_loaded"] for s in stats) == 0
        assert sum(s["programs_traced"] for s in stats) >= 1
