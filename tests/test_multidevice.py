"""Per-chip device modules (VERDICT r1 #7): one TPUDevice per visible
jax device, load-balanced by Registry.device_for (reference: per-GPU
module instances, device_cuda_module.c:326). Runs on the virtual
8-device CPU mesh from conftest."""

import numpy as np
import pytest

import parsec_tpu as parsec
from parsec_tpu import dtd
from parsec_tpu.algorithms import insert_gemm_dtd
from parsec_tpu.core.task import DeviceType
from parsec_tpu.data.matrix import TiledMatrix


def _skip_without_multichip():
    import jax
    if len(jax.devices()) < 2:
        import pytest
        pytest.skip("needs >=2 devices (virtual CPU mesh); real-TPU "
                    "runs see one chip")


def test_one_module_per_visible_device():
    _skip_without_multichip()
    ctx = parsec.init(nb_cores=2)
    tpus = ctx.devices.by_type(DeviceType.TPU)
    import jax
    assert len(tpus) == len(jax.devices())
    assert len(tpus) >= 2, "conftest should provide 8 virtual devices"
    ids = {d.jax_device.id for d in tpus}
    assert len(ids) == len(tpus), "modules must pin distinct chips"
    parsec.fini(ctx)


@pytest.mark.parametrize("device_modules", [True, False])
def test_a_missing_chip_is_an_error_and_not_a_cpu_run(monkeypatch,
                                                      device_modules):
    """JAX with no chip falls back to the CPU platform by itself. A
    context started then, by a run that did not ask for the CPU
    platform, would put CPU device modules under a chip's name: it
    raises and names the two ways out, and the second of them
    (``device.tpu.enabled=0``) gives a context without device modules."""
    from parsec_tpu.utils import jax_platform, mca_param
    monkeypatch.setattr(jax_platform, "cpu_requested", lambda: False)
    if device_modules:
        with pytest.raises(RuntimeError, match="no accelerator found") \
                as err:
            parsec.init(nb_cores=1)
        assert "JAX_PLATFORMS=cpu" in str(err.value)
        assert "device.tpu.enabled=0" in str(err.value)
        return
    mca_param.set("device.tpu.enabled", False)
    try:
        ctx = parsec.init(nb_cores=1)
        assert ctx.devices.by_type(DeviceType.TPU) == []
        parsec.fini(ctx)
    finally:
        mca_param.unset("device.tpu.enabled")


def test_dtd_gemm_load_splits_across_devices():
    """A DTD tiled GEMM's tasks spread over multiple device modules.
    This pins the DEVICE-MANAGER plane (per-module load balancing), so
    the pool must take the instrumented Python path — the native DTD
    engine runs bodies inline on the worker and never touches the
    modules (runtime.native_dtd docs)."""
    _skip_without_multichip()
    from parsec_tpu.utils import mca_param
    mca_param.set("runtime.native_dtd", 0)
    try:
        _dtd_gemm_load_split_body()
    finally:
        mca_param.unset("runtime.native_dtd")


def _dtd_gemm_load_split_body():
    rng = np.random.default_rng(0)
    A_h = rng.standard_normal((256, 256)).astype(np.float32)
    B_h = rng.standard_normal((256, 256)).astype(np.float32)
    C_h = rng.standard_normal((256, 256)).astype(np.float32)

    ctx = parsec.init(nb_cores=4)
    ctx.start()
    A = TiledMatrix.from_array(A_h.copy(), 32, 32, name="A")
    B = TiledMatrix.from_array(B_h.copy(), 32, 32, name="B")
    C = TiledMatrix.from_array(C_h.copy(), 32, 32, name="C")
    tp = dtd.Taskpool("gemm")
    ctx.add_taskpool(tp)
    insert_gemm_dtd(tp, A, B, C)
    tp.wait()
    per_dev = {d.name: d.stats.get("tasks", 0)
               for d in ctx.devices.by_type(DeviceType.TPU)}
    parsec.fini(ctx)

    assert np.allclose(C.to_array(), C_h + A_h @ B_h, atol=1e-3)
    busy = [n for n, c in per_dev.items() if c > 0]
    assert len(busy) >= 2, f"no load split: {per_dev}"


def test_group_launch_wide_wave(rng):
    """A worker that selects ready tasks of one pure body has the device
    module issue them as one program: a wide independent wave must
    complete correctly AND register multi-task launches."""
    from parsec_tpu.utils import mca_param

    NT = 32
    X_h = rng.standard_normal((NT * 16, 16)).astype(np.float32)
    X = TiledMatrix.from_array(X_h.copy(), 16, 16, name="X")
    mca_param.set("device.tpu.max_devices", 1)   # one module: big groups
    mca_param.set("runtime.native_dtd", 0)       # the engine a chip gets
    try:
        ctx = parsec.init(nb_cores=2)
        tp = dtd.Taskpool("wide")
        ctx.add_taskpool(tp)
        # one flush of 32 ready tasks: two workers cannot split them into
        # shares that are both under the smallest group
        tp.insert_tasks(lambda x: x * 2.0 + 1.0,
                        [(dtd.TileArg(X, (i, 0), dtd.INOUT),)
                         for i in range(NT)],
                        device=DeviceType.TPU, pure=True)
        tp.wait()
        tpu_stats = [d.dump_statistics() for d in ctx.devices.devices
                     if d.name.startswith("tpu")]
        parsec.fini(ctx)
        np.testing.assert_allclose(X.to_array(), X_h * 2.0 + 1.0,
                                   rtol=1e-6)
        batched = sum(s.get("batched_tasks", 0) for s in tpu_stats)
        batches = sum(s.get("batches", 0) for s in tpu_stats)
        assert batched > batches >= 1, (batched, batches)
        assert sum(s["tasks"] for s in tpu_stats) == NT
    finally:
        mca_param.unset("device.tpu.max_devices")
        mca_param.unset("runtime.native_dtd")


def test_group_launch_uses_batch_hook(rng):
    """A class with a hand-batched hook (shared-flow TRSM shape) must
    launch through it when a worker holds a group of its ready tasks and
    the shared flow holds ONE value across the group — and produce the
    same results."""
    import parsec_tpu as parsec
    from parsec_tpu.data import LocalCollection
    from parsec_tpu.dsl import ptg
    from parsec_tpu.utils import mca_param

    NT = 8
    L = rng.standard_normal((16, 16)).astype(np.float32)
    store = LocalCollection(
        "S", {("l",): L} |
        {("c", i): rng.standard_normal((16, 16)).astype(np.float32)
         for i in range(NT)} | {("y", i): None for i in range(NT)})
    calls = {"hook": 0}

    def batch_hook(Ls, Cs):
        calls["hook"] += 1
        import jax.numpy as jnp
        # full precision: on TPU a bare matmul runs bf16 MXU passes,
        # which the 1e-5 comparison below would fail
        return jnp.matmul(Cs, Ls[0].T, precision="highest")

    mca_param.set("device.tpu.max_devices", 1)
    try:
        # started by wait(): all eight are ready before a worker selects
        ctx = parsec.init(nb_cores=2)
        tp = ptg.Taskpool("trsmish", N=NT, S=store)
        TC = tp.task_class(
            "T", params=("i",),
            space=lambda g: ((i,) for i in range(g.N)),
            flows=[
                ptg.FlowSpec(
                    "L", ptg.READ,
                    ins=[ptg.In(data=lambda g, i: (g.S, ("l",)))]),
                ptg.FlowSpec(
                    "C", ptg.RW,
                    ins=[ptg.In(data=lambda g, i: (g.S, ("c", i)))],
                    outs=[ptg.Out(data=lambda g, i: (g.S, ("y", i)))])])

        @TC.body(batch_hook=batch_hook, batch_hook_shared=("L",))
        def t_body(task, L_, C_):
            import jax.numpy as jnp
            return {"C": jnp.matmul(C_, L_.T, precision="highest")}

        ctx.add_taskpool(tp)
        assert ctx.wait(timeout=300)
        grouped = sum(d.stats["batched_tasks"]
                      for d in ctx.devices.by_type(DeviceType.TPU))
        parsec.fini(ctx)
    finally:
        mca_param.unset("device.tpu.max_devices")
    for i in range(NT):
        np.testing.assert_allclose(
            np.asarray(store.data_of(("y", i))),
            np.asarray(store.data_of(("c", i))) @ L.T, rtol=1e-5,
            atol=1e-5)
    assert calls["hook"] >= 1 and grouped >= 4, "batch_hook never engaged"


# ---- panel-fused flagship under GSPMD (ISSUE r6 satellite) -------------
# In-suite mirror of the driver's dryrun phases 3-4: the panel-fused LU
# (two-store fuser — the Aᵀ L-store plus the A-layout U-carry) with its
# state sharded over the 8-virtual-device mesh. A fuser change that
# breaks partitioning (cross-store reads, the final transpose+select
# merge) now fails in pytest, not only in the driver's dryrun.

@pytest.mark.parametrize("hook", ["solve", "gemm"])
def test_getrf_left_panel_sharded_8dev(hook):
    _skip_without_multichip()
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from parsec_tpu.algorithms.getrf import build_getrf_left
    from parsec_tpu.compiled.panels import PanelExecutor
    from parsec_tpu.compiled.spmd import make_mesh
    from parsec_tpu.compiled.wavefront import plan_taskpool
    from parsec_tpu.utils import mca_param

    n, nb = 256, 32
    rng = np.random.default_rng(7)
    D0 = (rng.standard_normal((n, n)) + 2.0 * n * np.eye(n)) \
        .astype(np.float32)               # the Aᵀ store; factors A = D0ᵀ
    mca_param.set("getrf.trsm_hook", hook)
    try:
        A = TiledMatrix(n, n, nb, nb, name="A")
        ex = PanelExecutor(plan_taskpool(build_getrf_left(A)))
        ref = jax.jit(ex.run_state)({"A": jnp.asarray(D0)})["A"]
        mesh = make_mesh(8, axis="rows")
        sh = NamedSharding(mesh, P("rows"))
        out = jax.jit(ex.run_state, out_shardings={"A": sh})(
            {"A": jax.device_put(D0, sh)})["A"]
    finally:
        mca_param.unset("getrf.trsm_hook")
    # sharded == unsharded (GSPMD must only partition, never change
    # the math) ...
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # ... and the factorization itself is right: packed LU residual
    packed = np.asarray(out).T.astype(np.float64)
    L = np.tril(packed, -1) + np.eye(n)
    U = np.triu(packed)
    A_in = D0.T.astype(np.float64)
    resid = np.linalg.norm(L @ U - A_in) / np.linalg.norm(A_in)
    assert resid <= 1e-5, (hook, resid)


# ---- group launches under 2-rank distribution (VERDICT r3 #8) ----------
# Reference bar: the CUDA manager thread under MPI
# (device_cuda_module.c:2573-2589 + distributed DTD tests) — ranks launch
# their local DTD GEMM tiles in groups while values cross the socket wire.

def _mgr_dist_child(rank, nb_ranks, base_port, q):
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
        import numpy as _np
        from parsec_tpu.comm.socket_engine import SocketCommEngine
        from parsec_tpu.core import context as ctx_mod
        from parsec_tpu import dtd as _dtd
        from parsec_tpu.algorithms import insert_gemm_dtd as _ins
        from parsec_tpu.data.matrix import TiledMatrix as _TM, \
            TwoDimBlockCyclic
        from parsec_tpu.utils import mca_param

        mca_param.set("device.tpu.max_devices", 1)  # one module/rank
        engine = SocketCommEngine(rank, nb_ranks, base_port=base_port)
        # one worker: the four ready tasks of a local row of C tiles
        # reach it in one flush and leave as one group
        ctx = ctx_mod.init(nb_cores=1, comm=engine)
        ctx.start()
        rng = _np.random.default_rng(0)            # same data all ranks
        m, kdim, nb = 256, 64, 64
        A_h = rng.standard_normal((m, kdim)).astype(_np.float32)
        B_h = rng.standard_normal((kdim, m)).astype(_np.float32)
        C_h = rng.standard_normal((m, m)).astype(_np.float32)
        dist = TwoDimBlockCyclic(nb_ranks, 1)
        A = _TM.from_array(A_h, nb, nb, dist=dist, myrank=rank, name="A")
        B = _TM.from_array(B_h, nb, nb, dist=dist, myrank=rank, name="B")
        C = _TM.from_array(C_h.copy(), nb, nb, dist=dist, myrank=rank,
                           name="C")
        tp = _dtd.Taskpool("mgr_gemm")
        ctx.add_taskpool(tp)
        _ins(tp, A, B, C)          # kdim/nb = 1: independent GEMM tasks
        tp.wait()
        tp.flush(C)
        ref = C_h + A_h @ B_h
        for (i, j) in list(C.local_keys()):
            _np.testing.assert_allclose(
                _np.asarray(C.data_of((i, j))),
                ref[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb],
                rtol=1e-4, atol=1e-4)
        stats = [d.dump_statistics() for d in ctx.devices.devices
                 if d.name.startswith("tpu")]
        engine.sync()
        ctx.fini()
        q.put((rank, "ok",
               {"batches": sum(s.get("batches", 0) for s in stats),
                "batched": sum(s.get("batched_tasks", 0)
                               for s in stats)}))
    except BaseException as exc:  # noqa: BLE001 — report to parent
        import traceback
        q.put((rank, "error", f"{exc}\n{traceback.format_exc()}"))


@pytest.mark.parametrize("nranks", [2, 4])
def test_group_launch_socket(nranks):
    """Every rank launches its DTD GEMM tasks through the accelerator
    module while values cross the socket wire: results correct on every
    rank's local tiles AND a rank whose inputs are all local (rank 0 owns
    B) registered a multi-task launch; where B tiles arrive one by one,
    tasks become ready one by one and may go alone.
    4 ranks = the reference's mid-scale MPI test size (SURVEY §4)."""
    import multiprocessing as mp
    from parsec_tpu.comm.pingpong import _free_port_base

    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    base_port = _free_port_base(nranks)
    procs = [ctx.Process(target=_mgr_dist_child,
                         args=(r, nranks, base_port, q))
             for r in range(nranks)]
    for p in procs:
        p.start()
    results = {}
    try:
        for _ in range(nranks):
            rank, status, payload = q.get(timeout=180)
            if status != "ok":
                raise AssertionError(f"rank {rank} failed:\n{payload}")
            results[rank] = payload
    finally:
        for p in procs:
            p.join(timeout=10.0)
            if p.is_alive():
                p.terminate()
    assert results[0]["batches"] >= 1, results
    assert results[0]["batched"] >= 4, results
