"""Runtime race sanitizer tests (analysis/dfsan.py, the `dfsan` PINS
module): vector-clock race detection over tile accesses, the per-tile
version-sequence determinism digest across schedulers and the PR-3
release fast-path knobs, lock-order tracking, and the dynamic
access-mode check."""

import numpy as np
import pytest

import parsec_tpu as parsec
from parsec_tpu.analysis.dfsan import DataflowSanitizer
from parsec_tpu.analysis.fixtures import build_racy
from parsec_tpu.data import LocalCollection, TiledMatrix
from parsec_tpu.dsl import dtd, ptg
from parsec_tpu.utils import mca_param


@pytest.fixture
def san_ctx():
    """A context with the dfsan sanitizer installed, torn down with the
    pins param restored."""
    mca_param.set("pins", "dfsan")
    ctx = parsec.init(nb_cores=4)
    ctx.start()
    try:
        yield ctx
    finally:
        parsec.fini(ctx)
        mca_param.unset("pins")


def _run_dtd_gemm(scheduler, bypass_chain, nb_cores=4, native_dtd=0):
    """One DTD GEMM run under the sanitizer; returns (races, digest).
    ``native_dtd=1`` is the ISSUE 14 acceptance arm: dfsan no longer
    forces the Python engine — the pool runs NATIVELY and the ring-fed
    fold-time replay must produce a per-tile version digest
    bitwise-identical to every Python-engine configuration."""
    mca_param.set("pins", "dfsan")
    mca_param.set("runtime.bypass_chain", bypass_chain)
    mca_param.set("runtime.native_dtd", native_dtd)
    try:
        ctx = parsec.init(nb_cores=nb_cores, scheduler=scheduler)
        ctx.start()
        rng = np.random.default_rng(7)
        A = TiledMatrix.from_array(
            rng.standard_normal((32, 32)).astype(np.float32), 16, 16,
            name="A")
        B = TiledMatrix.from_array(
            rng.standard_normal((32, 32)).astype(np.float32), 16, 16,
            name="B")
        C = TiledMatrix.from_array(np.zeros((32, 32), np.float32), 16, 16,
                                   name="C")
        tp = dtd.Taskpool("gemm_dfsan")
        ctx.add_taskpool(tp)
        from parsec_tpu.algorithms import insert_gemm_dtd
        insert_gemm_dtd(tp, A, B, C)
        tp.flush()
        tp.wait()
        races = [str(r) for r in ctx.dfsan.races]
        digest = ctx.dfsan.digest()
        # ISSUE 14: with the ring-fed replay, the sanitizer keeps the
        # NATIVE engine when the knob (and the toolchain) allows it
        from parsec_tpu import _native
        want_native = bool(native_dtd) and _native.available()
        assert (tp._native is not None) == want_native
        if want_native:
            assert ctx.dfsan.stats["native_replayed_pools"] >= 1
        parsec.fini(ctx)
        return races, digest
    finally:
        mca_param.unset("pins")
        mca_param.unset("runtime.bypass_chain")
        mca_param.unset("runtime.native_dtd")


def test_determinism_digest_across_schedulers_and_release_knobs():
    """Satellite/acceptance: the per-tile version-sequence digest is
    bitwise-identical across both scheduler families (lfq =
    local_queues, gd = global_queues), `runtime.bypass_chain` off, AND
    `runtime.native_dtd`
    on/off (ISSUE 10: the engine knob must never change the observed
    dataflow) — the regression harness for the scheduler/release fast
    paths."""
    digests = set()
    for scheduler in ("lfq", "gd"):
        races, digest = _run_dtd_gemm(scheduler, 1)
        assert not races, races
        digests.add(digest)
    races, digest = _run_dtd_gemm("lfq", 0)        # bypass_chain off
    assert not races, races
    digests.add(digest)
    for native in (0, 1):                          # ISSUE 10 engine knob
        races, digest = _run_dtd_gemm("lfq", 1, native_dtd=native)
        assert not races, races
        digests.add(digest)
    assert len(digests) == 1, f"schedule-dependent digests: {digests}"


def test_dtd_stress_with_sanitizer(san_ctx):
    """Tier-1 DTD stress under the sanitizer: thousands of tasks over a
    shared tile set, WAW chains via retired writers AND in-flight links
    — no races, exact result, deterministic per-tile sequences."""
    n, tiles = 4000, 32
    C = LocalCollection("C", {(i,): 0 for i in range(tiles)})
    tp = dtd.Taskpool("stress_dfsan")
    san_ctx.add_taskpool(tp)

    def bump(x):
        return x + 1

    for i in range(n):
        tp.insert_task(bump, dtd.TileArg(C, (i % tiles,), dtd.INOUT))
    tp.flush()
    tp.wait()
    san = san_ctx.dfsan
    assert not san.races, [str(r) for r in san.races][:5]
    assert sum(C.data_of((i,)) for i in range(tiles)) == n
    seqs = san.version_sequences()
    assert sum(len(s) for s in seqs.values()) == n
    # every tile's writer sequence is its insertion order — strictly
    # increasing seq numbers
    for (_, key), seq in seqs.items():
        nums = [int(s.split("(")[1].rstrip(")")) for s in seq]
        assert nums == sorted(nums)


def test_racy_ptg_detected_even_on_one_worker():
    """Clocks advance along dependency edges only, so the seeded WAW is
    flagged even when a single worker serializes the writers."""
    mca_param.set("pins", "dfsan")
    try:
        for nb_cores in (1, 4):
            ctx = parsec.init(nb_cores=nb_cores)
            ctx.start()
            tp = build_racy()
            ctx.add_taskpool(tp)
            assert ctx.wait(timeout=30)
            kinds = {r.kind for r in ctx.dfsan.races}
            assert "waw" in kinds, \
                f"nb_cores={nb_cores}: {[str(r) for r in ctx.dfsan.races]}"
            waw = next(r for r in ctx.dfsan.races if r.kind == "waw")
            assert "S(0,)" in waw.message       # names the tile
            parsec.fini(ctx)
    finally:
        mca_param.unset("pins")


def test_potrf_clean_and_correct_under_sanitizer(san_ctx, rng):
    from parsec_tpu.algorithms import build_potrf
    from conftest import spd_matrix
    Ah = spd_matrix(rng, 64)
    A = TiledMatrix.from_array(Ah.copy(), 16, 16, name="A")
    tp = build_potrf(A)
    san_ctx.add_taskpool(tp)
    assert san_ctx.wait(timeout=60)
    assert not san_ctx.dfsan.races, \
        [str(r) for r in san_ctx.dfsan.races][:5]
    L = np.tril(A.to_array())
    assert np.allclose(L @ L.T, Ah, atol=1e-2)
    assert san_ctx.dfsan.digest()           # non-empty hex digest
    assert san_ctx.dfsan.stats["writes"] > 0
    assert san_ctx.dfsan.stats["edges"] > 0


def test_ptg_digest_stable_across_runs():
    digests = set()
    for _ in range(2):
        mca_param.set("pins", "dfsan")
        ctx = parsec.init(nb_cores=4)
        ctx.start()
        store = LocalCollection("S", {("x",): 0})
        tp = ptg.Taskpool("chain", N=12, S=store)
        T = tp.task_class(
            "T", params=("i",),
            space=lambda g: ((i,) for i in range(g.N)),
            flows=[ptg.FlowSpec(
                "X", ptg.RW,
                ins=[ptg.In(data=lambda g, i: (g.S, ("x",)),
                            guard=lambda g, i: i == 0),
                     ptg.In(src=("T", lambda g, i: (i - 1,), "X"),
                            guard=lambda g, i: i > 0)],
                outs=[ptg.Out(dst=("T", lambda g, i: (i + 1,), "X"),
                              guard=lambda g, i: i < g.N - 1),
                      ptg.Out(data=lambda g, i: (g.S, ("x",)),
                              guard=lambda g, i: i == g.N - 1)])])

        @T.body
        def body(task, x):
            return x + 1

        ctx.add_taskpool(tp)
        assert ctx.wait(timeout=30)
        assert not ctx.dfsan.races
        digests.add(ctx.dfsan.digest())
        parsec.fini(ctx)
        mca_param.unset("pins")
    assert len(digests) == 1


def test_cross_taskpool_barrier_no_false_positives(san_ctx):
    """Two pools writing the same tile back-to-back: termdet is a full
    sync point, so the second pool's writes must NOT flag against the
    first's (the barrier base covers them)."""
    C = LocalCollection("C", {("x",): 0})

    def inc(x):
        return x + 1

    for name in ("p1", "p2"):
        tp = dtd.Taskpool(name)
        san_ctx.add_taskpool(tp)
        for _ in range(50):
            tp.insert_task(inc, dtd.TileArg(C, ("x",), dtd.INOUT))
        tp.flush()
        tp.wait()
    assert not san_ctx.dfsan.races, \
        [str(r) for r in san_ctx.dfsan.races][:5]
    assert C.data_of(("x",)) == 100


def test_access_mode_violation_at_runtime(san_ctx):
    """A body returning a value for a READ flow (dict return) is the
    dynamic half of the lint's access-violation rule."""
    store = LocalCollection("S", {(0,): 1.0})
    tp = ptg.Taskpool("badret", S=store)
    T = tp.task_class(
        "T", params=("i",), space=lambda g: ((0,),),
        flows=[ptg.FlowSpec(
            "X", ptg.READ,
            ins=[ptg.In(data=lambda g, i: (g.S, (0,)))])])

    @T.body
    def body(task, x):
        return {"X": x + 1.0}       # READ flow must not produce output

    san_ctx.add_taskpool(tp)
    assert san_ctx.wait(timeout=30)
    viol = [r for r in san_ctx.dfsan.races if r.kind == "access-violation"]
    assert viol, [str(r) for r in san_ctx.dfsan.races]
    assert "READ" in viol[0].message and "'X'" in viol[0].message


def test_lock_order_inversion_flagged():
    san = DataflowSanitizer()
    # thread A order: pdep[1] -> dtd-seq[2]
    san.lock_acquired("pdep", 1)
    san.lock_acquired("dtd-seq", 2)
    san.lock_released("dtd-seq", 2)
    san.lock_released("pdep", 1)
    assert not san.races
    # reverse order: inversion
    san.lock_acquired("dtd-seq", 2)
    san.lock_acquired("pdep", 1)
    inv = [r for r in san.races if r.kind == "lock-order"]
    assert inv and "inversion" in inv[0].message


def test_no_lock_inversions_in_runtime(san_ctx):
    """The real release paths (pdep stripes + DTD seq stripes) must be
    inversion-free under load — the PR 3 fast-path guard."""
    C = LocalCollection("C", {(i,): 0 for i in range(8)})
    tp = dtd.Taskpool("locks")
    san_ctx.add_taskpool(tp)

    def bump(x):
        return x + 1

    for i in range(800):
        tp.insert_task(bump, dtd.TileArg(C, (i % 8,), dtd.INOUT))
    tp.flush()
    tp.wait()
    assert not [r for r in san_ctx.dfsan.races if r.kind == "lock-order"]
    assert san_ctx.dfsan.stats["lock_acquires"] > 0


def test_pins_data_events_rebroadcast(san_ctx):
    """dfsan re-fires DATA_READ/DATA_WRITE on the PINS chains so other
    modules can observe tile traffic without their own runtime hooks."""
    from parsec_tpu.profiling.pins import PinsEvent
    seen = {"r": 0, "w": 0}
    san_ctx.pins.register(PinsEvent.DATA_WRITE,
                          lambda t, dc, k: seen.__setitem__(
                              "w", seen["w"] + 1))
    san_ctx.pins.register(PinsEvent.DATA_READ,
                          lambda t, dc, k: seen.__setitem__(
                              "r", seen["r"] + 1))
    C = LocalCollection("C", {("x",): 0})
    tp = dtd.Taskpool("ev")
    san_ctx.add_taskpool(tp)
    for _ in range(10):
        tp.insert_task(lambda x: x + 1, dtd.TileArg(C, ("x",), dtd.INOUT))
    tp.flush()
    tp.wait()
    assert seen["w"] == 10


def test_datarepo_observer_installed(san_ctx):
    from parsec_tpu.core.datarepo import DataRepo
    assert DataRepo.observer is not None
    repo = DataRepo(nb_flows=2)
    ent = repo.lookup_or_create(("k",))
    ent.set(0, 42)
    assert ent.get(0) == 42
    assert san_ctx.dfsan.stats["repo_accesses"] >= 2


def test_sanitizer_reset(san_ctx):
    C = LocalCollection("C", {("x",): 0})
    tp = dtd.Taskpool("r")
    san_ctx.add_taskpool(tp)
    tp.insert_task(lambda x: x + 1, dtd.TileArg(C, ("x",), dtd.INOUT))
    tp.flush()
    tp.wait()
    assert san_ctx.dfsan.version_sequences()
    san_ctx.dfsan.reset()
    assert not san_ctx.dfsan.version_sequences()
    assert not san_ctx.dfsan.races
