"""Multi-process distributed runs over the socket comm engine.

The reference's distributed tests run real MPI with 2-8 ranks on one node
(SURVEY §4); these run real OS processes over the TCP engine: PTG chain
across ranks, a distributed tiled POTRF with 2D-block-cyclic placement,
eager vs rendezvous payload paths, and the fourcounter termdet wave.
"""

import multiprocessing as mp
import os
import socket
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    os.environ.get("PARSEC_SKIP_MP") == "1",
    reason="multiprocess tests disabled")


def _free_port_base(n: int = 8) -> int:
    """Pick a base port with n free consecutive ports (best effort)."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    base = s.getsockname()[1]
    s.close()
    # step away from the probed port to reduce reuse races
    return 20000 + (base % 20000)


def _child_main(fn_name: str, rank: int, nb_ranks: int, base_port: int,
                q, kwargs):
    """Child entry: force CPU jax, build engine+context, run the scenario."""
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
        from parsec_tpu.comm.socket_engine import SocketCommEngine
        from parsec_tpu.core import context as ctx_mod

        engine = SocketCommEngine(rank, nb_ranks, base_port=base_port)
        ctx = ctx_mod.init(nb_cores=2, comm=engine)
        result = globals()[fn_name](ctx, engine, rank, nb_ranks, **kwargs)
        engine.sync()
        engine.sync()     # back-to-back barriers must not deadlock
        ctx.fini()
        q.put((rank, "ok", result))
    except BaseException as exc:  # noqa: BLE001 — report to parent
        import traceback
        q.put((rank, "error", f"{exc}\n{traceback.format_exc()}"))


def _run_ranks(fn_name: str, nb_ranks: int, timeout: float = 120.0,
               **kwargs):
    ctx = mp.get_context("spawn")
    base_port = _free_port_base(nb_ranks)
    q = ctx.Queue()
    procs = [ctx.Process(target=_child_main,
                         args=(fn_name, r, nb_ranks, base_port, q, kwargs))
             for r in range(nb_ranks)]
    for p in procs:
        p.start()
    results = {}
    try:
        for _ in range(nb_ranks):
            rank, status, payload = q.get(timeout=timeout)
            if status != "ok":
                raise AssertionError(f"rank {rank} failed:\n{payload}")
            results[rank] = payload
    finally:
        for p in procs:
            p.join(timeout=10.0)
            if p.is_alive():
                p.terminate()
    return results


class _DistVec:
    """1-D collection of scalar tiles distributed round-robin by index."""

    def __init__(self, n, nb_ranks, my_rank, init=0.0):
        self.n = n
        self.nb_ranks = nb_ranks
        self.my_rank = my_rank
        self.dc_id = 7
        self.v = {i: np.float32(init) for i in range(n)
                  if i % nb_ranks == my_rank}

    def _k(self, key):
        return key[0] if isinstance(key, (tuple, list)) else key

    def rank_of(self, key):
        return self._k(key) % self.nb_ranks

    def data_of(self, key):
        return self.v[self._k(key)]

    def write_tile(self, key, value):
        self.v[self._k(key)] = value


# ------------------------------------------------------------- scenarios
# (run inside child processes; must be module-level for spawn pickling)

def scenario_chain(ctx, engine, rank, nb_ranks, n_steps=12,
                   wait_timeout=60):
    """A dependency chain whose steps round-robin across ranks: every hop
    is a remote activation (eager path)."""
    from parsec_tpu.dsl import ptg

    A = _DistVec(n_steps, nb_ranks, rank)
    tp = ptg.Taskpool("chain", N=n_steps, A=A)
    tp.task_class(
        "STEP", params=("k",),
        space=lambda g: ((k,) for k in range(g.N)),
        affinity=lambda g, k: (g.A, (k,)),
        flows=[ptg.FlowSpec(
            "T", ptg.RW,
            ins=[ptg.In(data=lambda g, k: (g.A, (0,)),
                        guard=lambda g, k: k == 0),
                 ptg.In(src=("STEP", lambda g, k: (k - 1,), "T"),
                        guard=lambda g, k: k > 0)],
            outs=[ptg.Out(dst=("STEP", lambda g, k: (k + 1,), "T"),
                          guard=lambda g, k: k < g.N - 1),
                  ptg.Out(data=lambda g, k: (g.A, (k,)))])])

    @tp.task_class_by_name("STEP").body
    def step_body(task, T):
        return T + 1

    ctx.add_taskpool(tp)
    ctx.start()
    assert ctx.wait(timeout=wait_timeout), \
        f"rank {rank}: chain did not terminate"
    # the final step wrote n_steps to its owner's tile
    last = n_steps - 1
    if last % nb_ranks == rank:
        assert float(A.v[last]) == float(n_steps), A.v
    return float(A.v.get(last, -1))


def scenario_rendezvous(ctx, engine, rank, nb_ranks, nbytes=2 * 1024 * 1024):
    """Ship payloads above the eager limit: exercises the GET/PUT
    rendezvous (the reference's check-comms 100 x 2 MiB bw_test shape)."""
    from parsec_tpu.dsl import ptg
    from parsec_tpu.utils import mca_param
    mca_param.set("comm.eager_limit", 1024)

    n = nbytes // 4
    A = _DistVec(2, nb_ranks, rank)

    class _Big(_DistVec):
        def data_of(self, key):
            return np.full(n, 1.0, dtype=np.float32)

    B = _Big(2, nb_ranks, rank)
    tp = ptg.Taskpool("rdv", A=A, B=B)
    tp.task_class(
        "SRC", params=("k",),
        space=lambda g: ((0,),),
        affinity=lambda g, k: (g.B, (0,)),
        flows=[ptg.FlowSpec(
            "X", ptg.RW,
            ins=[ptg.In(data=lambda g, k: (g.B, (0,)))],
            outs=[ptg.Out(dst=("DST", lambda g, k: (0,), "X"))])])
    tp.task_class(
        "DST", params=("k",),
        space=lambda g: ((0,),),
        affinity=lambda g, k: (g.B, (1,)),
        flows=[ptg.FlowSpec(
            "X", ptg.RW,
            ins=[ptg.In(src=("SRC", lambda g, k: (0,), "X"))],
            outs=[ptg.Out(data=lambda g, k: (g.A, (1,)))])])

    @tp.task_class_by_name("SRC").body
    def src_body(task, X):
        return X * 2

    @tp.task_class_by_name("DST").body
    def dst_body(task, X):
        return X.sum()

    ctx.add_taskpool(tp)
    ctx.start()
    # 120s: under the full real-chip suite's process churn the
    # 2 MiB rendezvous occasionally needs more than 60 (observed
    # one suite-context flake; passes standalone in ~8s)
    assert ctx.wait(timeout=120)
    if B.rank_of((1,)) == rank:
        assert float(A.v[1]) == 2.0 * n
        if B.rank_of((0,)) != rank:
            st = engine.wire_stats()
            # above-eager transfer actually used: pushed segment stream
            # (comm.rdv_push default) or the classic GET/PUT legs
            assert st["gets"] >= 1 or st["segs_recv"] >= 1, st
    return engine.stats["activations_recv"]


def scenario_potrf(ctx, engine, rank, nb_ranks, n=192, nb=32):
    """Distributed tiled Cholesky: 2D-block-cyclic tiles, owner-computes,
    every inter-rank dep a remote activation."""
    from parsec_tpu.algorithms.potrf import build_potrf
    from parsec_tpu.data.matrix import TiledMatrix, TwoDimBlockCyclic

    rng = np.random.default_rng(0)
    M = rng.standard_normal((n, n)).astype(np.float64)
    A_host = (M @ M.T + n * np.eye(n)).astype(np.float32)
    dist = TwoDimBlockCyclic(P=nb_ranks, Q=1)
    A = TiledMatrix.from_array(A_host.copy(), nb, nb, dist=dist,
                               myrank=rank, name="A")
    tp = build_potrf(A)
    ctx.add_taskpool(tp)
    ctx.start()
    assert ctx.wait(timeout=90), f"rank {rank}: potrf did not terminate"
    # each rank checks its local tiles of L against the numpy factor
    L_ref = np.linalg.cholesky(A_host.astype(np.float64))
    for (i, j) in A.local_keys():
        if j > i:
            continue
        tile = np.asarray(A.data_of((i, j)), dtype=np.float64)
        ref = L_ref[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]
        if i == j:
            tile = np.tril(tile)
        err = np.linalg.norm(tile - ref) / max(1e-30, np.linalg.norm(ref))
        assert err < 1e-3, f"rank {rank} tile ({i},{j}) err {err}"
    return len(list(A.local_keys()))


def scenario_potrf_left(ctx, engine, rank, nb_ranks, n=192, nb=32):
    """The left-looking flagship taskpool multi-rank: UPDATE's gathered
    operands resolve remote tiles through the one-sided fetch_tile
    service (CTL-gather ordering makes the fetches race-free)."""
    from parsec_tpu.algorithms.potrf import build_potrf_left
    from parsec_tpu.data.matrix import TiledMatrix, TwoDimBlockCyclic

    rng = np.random.default_rng(0)
    M = rng.standard_normal((n, n)).astype(np.float64)
    A_host = (M @ M.T + n * np.eye(n)).astype(np.float32)
    dist = TwoDimBlockCyclic(P=nb_ranks, Q=1)
    A = TiledMatrix.from_array(A_host.copy(), nb, nb, dist=dist,
                               myrank=rank, name="A")
    tp = build_potrf_left(A)
    ctx.add_taskpool(tp)
    ctx.start()
    assert ctx.wait(timeout=90), \
        f"rank {rank}: potrf_left did not terminate"
    L_ref = np.linalg.cholesky(A_host.astype(np.float64))
    for (i, j) in A.local_keys():
        if j > i:
            continue
        tile = np.asarray(A.data_of((i, j)), dtype=np.float64)
        ref = L_ref[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]
        if i == j:
            tile = np.tril(tile)
        err = np.linalg.norm(tile - ref) / max(1e-30, np.linalg.norm(ref))
        assert err < 1e-3, f"rank {rank} tile ({i},{j}) err {err}"
    return len(list(A.local_keys()))


def scenario_geqrf_hh(ctx, engine, rank, nb_ranks, m=128, n=64, nb=32):
    """Blocked-Householder QR multi-rank: PANEL/REDUCE resolve remote
    column operands through fetch_tile; (V, Xinv) values cross ranks as
    activation payloads."""
    from parsec_tpu.algorithms.geqrf import build_geqrf_hh
    from parsec_tpu.data.matrix import TiledMatrix, TwoDimBlockCyclic

    rng = np.random.default_rng(0)
    A_host = rng.standard_normal((m, n)).astype(np.float32)
    dist = TwoDimBlockCyclic(P=nb_ranks, Q=1)
    A = TiledMatrix.from_array(A_host.copy(), nb, nb, dist=dist,
                               myrank=rank, name="A")
    tp = build_geqrf_hh(A)
    ctx.add_taskpool(tp)
    ctx.start()
    assert ctx.wait(timeout=90), \
        f"rank {rank}: geqrf_hh did not terminate"
    # validate my local tiles of R against a full-gather reference:
    # AtA == RtR is global, so instead check tiles vs numpy qr with the
    # same sign fix applied per panel is overkill — use the invariant
    # on the locally-reconstructable pieces: lower tiles are zero, and
    # the assembled R from ALL ranks (via fetch) satisfies AtA = RtR
    # on rank 0.
    for (i, j) in A.local_keys():
        if i > j:
            np.testing.assert_allclose(
                np.asarray(A.data_of((i, j))), 0.0, atol=1e-4)
    if rank == 0:
        R = np.zeros((m, n), np.float32)
        for i in range(m // nb):
            for j in range(n // nb):
                owner = A.rank_of((i, j))
                t = A.data_of((i, j)) if owner == 0 else \
                    engine.fetch_tile(A, (i, j), owner, scope=tp.name)
                R[i*nb:(i+1)*nb, j*nb:(j+1)*nb] = np.asarray(t)
        np.testing.assert_allclose(R.T @ R, A_host.T @ A_host,
                                   rtol=2e-3, atol=2e-2)
    return 1


def scenario_multi_activate(ctx, engine, rank, nb_ranks):
    """One produced value fanning out to several consumers on one rank
    must cross the wire ONCE (the reference's one-data-per-(dep, rank)
    aggregation): assert a single activation message delivered."""
    from parsec_tpu.dsl import ptg

    A = _DistVec(8, nb_ranks, rank)
    tp = ptg.Taskpool("fan", A=A, NC=3)
    tp.task_class(
        "SRC", params=("k",),
        space=lambda g: ((0,),),
        affinity=lambda g, k: (g.A, (0,)),
        flows=[ptg.FlowSpec(
            "X", ptg.RW,
            ins=[ptg.In(data=lambda g, k: (g.A, (0,)))],
            outs=[ptg.Out(dst=("CONS",
                               lambda g, k: [(j,) for j in range(g.NC)],
                               "X"))])])
    tp.task_class(
        "CONS", params=("j",),
        space=lambda g: ((j,) for j in range(g.NC)),
        affinity=lambda g, j: (g.A, (1,)),       # ALL on rank 1
        flows=[ptg.FlowSpec(
            "X", ptg.RW,
            ins=[ptg.In(src=("SRC", lambda g, j: (0,), "X"))],
            outs=[ptg.Out(data=lambda g, j: (g.A, (2 + j,)))])])

    @tp.task_class_by_name("SRC").body
    def src_body(task, X):
        return np.full(1024, 7.0, dtype=np.float32)

    @tp.task_class_by_name("CONS").body
    def cons_body(task, X):
        return X.sum()

    ctx.add_taskpool(tp)
    ctx.start()
    assert ctx.wait(timeout=60)
    engine.sync()
    if rank == 1:      # consumer rank: 3 deps, ONE activation message
        assert engine.stats["activations_recv"] == 1, engine.stats
        for j in range(3):
            if A.rank_of((2 + j,)) == rank:
                assert float(A.v[2 + j]) == 7.0 * 1024
    return engine.stats["activations_recv"]


def scenario_jax_values(ctx, engine, rank, nb_ranks, n=4096):
    """Bodies produce device-resident jax.Arrays that cross rank
    boundaries: the engine must snapshot them to host numpy at the comm
    boundary (wire_value) on both the eager and rendezvous paths without
    hanging on a surprise sync. Reference capability: datatype
    pack/unpack of device buffers (parsec_comm_engine.h:113-183)."""
    import jax.numpy as jnp
    from parsec_tpu.dsl import ptg
    from parsec_tpu.utils import mca_param
    mca_param.set("comm.eager_limit", 1024)   # n floats >> 1 KiB → rdv

    A = _DistVec(3, nb_ranks, rank)
    tp = ptg.Taskpool("jaxval", A=A, N=n)
    tp.task_class(
        "SRC", params=("k",),
        space=lambda g: ((0,),),
        affinity=lambda g, k: (g.A, (0,)),
        flows=[ptg.FlowSpec(
            "X", ptg.RW,
            ins=[ptg.In(data=lambda g, k: (g.A, (0,)))],
            outs=[ptg.Out(dst=("MID", lambda g, k: (0,), "X"))])])
    tp.task_class(
        "MID", params=("k",),
        space=lambda g: ((0,),),
        affinity=lambda g, k: (g.A, (1,)),
        flows=[ptg.FlowSpec(
            "X", ptg.RW,
            ins=[ptg.In(src=("SRC", lambda g, k: (0,), "X"))],
            outs=[ptg.Out(dst=("DST", lambda g, k: (0,), "X"))])])
    tp.task_class(
        "DST", params=("k",),
        space=lambda g: ((0,),),
        affinity=lambda g, k: (g.A, (2,)),
        flows=[ptg.FlowSpec(
            "X", ptg.RW,
            ins=[ptg.In(src=("MID", lambda g, k: (0,), "X"))],
            outs=[ptg.Out(data=lambda g, k: (g.A, (2,)))])])

    @tp.task_class_by_name("SRC").body(batchable=False)
    def src_body(task, X):
        # rendezvous-sized DEVICE array leaves this rank
        return jnp.full((n,), 2.0, dtype=jnp.float32)

    @tp.task_class_by_name("MID").body(batchable=False)
    def mid_body(task, X):
        assert isinstance(X, np.ndarray), type(X)   # host numpy on arrival
        # eager-sized device scalar result (below the eager limit)
        return jnp.sum(X[:64])

    @tp.task_class_by_name("DST").body(batchable=False)
    def dst_body(task, X):
        assert isinstance(X, (np.ndarray, np.generic, float)), type(X)
        return np.float32(X)

    ctx.add_taskpool(tp)
    ctx.start()
    assert ctx.wait(timeout=60), f"rank {rank}: jaxval did not terminate"
    if A.rank_of((2,)) == rank:
        assert float(A.v[2]) == 128.0, A.v
    return engine.wire_stats()["frames_sent"]


# ----------------------------------------------------------------- tests

def test_chain_2ranks():
    res = _run_ranks("scenario_chain", 2)
    assert len(res) == 2


def test_chain_4ranks():
    res = _run_ranks("scenario_chain", 4, n_steps=16)
    assert len(res) == 4


def test_rendezvous_2ranks():
    _run_ranks("scenario_rendezvous", 2)


def test_potrf_2ranks():
    _run_ranks("scenario_potrf", 2)


def test_potrf_left_2ranks():
    _run_ranks("scenario_potrf_left", 2)


def test_potrf_left_3ranks():
    _run_ranks("scenario_potrf_left", 3)


def test_geqrf_hh_2ranks():
    _run_ranks("scenario_geqrf_hh", 2)


def test_geqrf_hh_3ranks():
    """Blocked-Householder QR with a 3-rank block-cyclic distribution:
    PANEL/REDUCE's gathered fetches cross two remote owners per
    column instead of one."""
    _run_ranks("scenario_geqrf_hh", 3, m=192, n=96, nb=32,
               timeout=180.0)


def test_multi_activate_dedup_2ranks():
    _run_ranks("scenario_multi_activate", 2)


def test_jax_values_2ranks():
    _run_ranks("scenario_jax_values", 2)


def test_jax_values_3ranks():
    _run_ranks("scenario_jax_values", 3)


def test_reenable_after_disable_raises():
    """disable() tears the peer mesh down; a re-enable would start a
    comm thread with zero sockets (silently deaf) — must fail fast."""
    import threading
    from parsec_tpu.comm.socket_engine import SocketCommEngine
    base = _free_port_base()
    engines = {}

    def mk(r):
        engines[r] = SocketCommEngine(r, 2, base_port=base)

    t = threading.Thread(target=mk, args=(1,))
    t.start()
    mk(0)
    t.join(timeout=30)
    try:
        e = engines[0]
        e.enable()
        e.disable()
        with pytest.raises(RuntimeError, match="re-enabled"):
            e.enable()
    finally:
        for eng in engines.values():
            try:
                eng.disable()
            except Exception:
                pass


def test_wire_frames_are_zero_copy():
    """Eager-path array payloads must travel as out-of-band raw buffers
    (protocol-5), not re-serialized through the pickle stream: the
    pickled control part stays tiny and both the sender-side buffer and
    the receiver-side loaded array are VIEWS, not copies."""
    import pickle
    arr = np.arange(65536, dtype=np.float32)       # 256 KiB payload
    msg = {"taskpool": "tp", "class": "HOP", "locals": (3,),
           "flow": "T", "dep_index": 0, "priority": 0, "value": arr}
    bufs = []
    payload = pickle.dumps((0, 0, [msg]), protocol=5,
                           buffer_callback=bufs.append)
    # control part is small; the array is out-of-band
    assert len(payload) < 2048, len(payload)
    assert len(bufs) == 1
    raw = bufs[0].raw()
    assert raw.nbytes == arr.nbytes
    assert np.shares_memory(np.frombuffer(raw, dtype=np.float32), arr)
    # receiver: loading with buffer views over the rx bytes yields an
    # array viewing those bytes — no intermediate host copy
    rx = bytearray(raw)                            # the socket rx buffer
    views = [memoryview(rx)]
    tag, src, msgs = pickle.loads(payload, buffers=views)
    got = msgs[0]["value"]
    np.testing.assert_array_equal(got, arr)
    assert np.shares_memory(got, np.frombuffer(rx, dtype=np.float32))


def test_stage_recv_value_gating():
    """comm.stage_recv=0 passes values through; auto on CPU backends is
    a no-op (stays numpy)."""
    import jax
    from parsec_tpu.comm.socket_engine import SocketCommEngine
    from parsec_tpu.utils import mca_param
    arr = np.ones(4096, dtype=np.float32)
    if jax.default_backend() == "cpu":   # auto mode: cpu backend = no-op
        out = SocketCommEngine.stage_recv_value((arr, {"x": arr}, 3))
        assert isinstance(out[0], np.ndarray)
    mca_param.set("comm.stage_recv", "0")
    try:
        out = SocketCommEngine.stage_recv_value(arr)
        assert out is arr
    finally:
        mca_param.unset("comm.stage_recv")


# ---- comm.thread_multiple (MPI_THREAD_MULTIPLE analog) ------------------

def scenario_chain_thread_multiple(ctx, engine, rank, nb_ranks,
                                   n_steps=12):
    """Same cross-rank chain, but worker threads send frames directly
    (per-peer send locks) instead of funnelling through the comm
    thread's command queue."""
    from parsec_tpu.utils import mca_param
    mca_param.set("comm.thread_multiple", 1)
    try:
        return scenario_chain(ctx, engine, rank, nb_ranks,
                              n_steps=n_steps)
    finally:
        mca_param.unset("comm.thread_multiple")


def scenario_potrf_thread_multiple(ctx, engine, rank, nb_ranks):
    from parsec_tpu.utils import mca_param
    mca_param.set("comm.thread_multiple", 1)
    try:
        return scenario_potrf(ctx, engine, rank, nb_ranks)
    finally:
        mca_param.unset("comm.thread_multiple")


def test_chain_2ranks_thread_multiple():
    res = _run_ranks("scenario_chain_thread_multiple", 2)
    assert len(res) == 2


def test_chain_4ranks_thread_multiple():
    """Direct worker sends under per-peer locks with FOUR ranks: more
    concurrent direct senders per peer socket than 2 ranks ever
    produce (the head-of-line/lock-discipline paths get real
    contention)."""
    res = _run_ranks("scenario_chain_thread_multiple", 4, n_steps=16,
                     timeout=180.0)
    assert len(res) == 4


def test_potrf_2ranks_thread_multiple():
    res = _run_ranks("scenario_potrf_thread_multiple", 2)
    assert len(res) == 2


def scenario_rendezvous_thread_multiple(ctx, engine, rank, nb_ranks):
    """Rendezvous GET/PUT with direct worker sends: the activation ships
    from a worker thread (direct path) while the GET reply and PUT land
    on the comm thread (which must stay funnelled — the comm-thread
    identity guard — or the blocking PUT would deadlock the receive
    loops)."""
    from parsec_tpu.utils import mca_param
    mca_param.set("comm.thread_multiple", 1)
    try:
        return scenario_rendezvous(ctx, engine, rank, nb_ranks)
    finally:
        mca_param.unset("comm.thread_multiple")


def test_rendezvous_2ranks_thread_multiple():
    res = _run_ranks("scenario_rendezvous_thread_multiple", 2)
    assert len(res) == 2


def scenario_rendezvous_roundtrip(ctx, engine, rank, nb_ranks,
                                  nbytes=1 << 20):
    """A >1 MB payload crosses the rendezvous GET/PUT path in BOTH
    directions (rank 0 → 1 → 0) with content verified BITWISE — the
    end-to-end guard for the vectored (sendmsg) large-frame send path:
    a desynchronized byte stream, clipped iovec, or mis-ordered
    queued-bytes remainder corrupts exactly this shape."""
    from parsec_tpu.dsl import ptg
    from parsec_tpu.utils import mca_param
    mca_param.set("comm.eager_limit", 64 * 1024)

    n = nbytes // 4 + 32          # strictly above 1 MiB on the wire
    A = _DistVec(3, nb_ranks, rank)

    class _Src(_DistVec):
        def data_of(self, key):
            return np.arange(n, dtype=np.float32)

    B = _Src(3, nb_ranks, rank)   # placement: indices 0,2 → rank 0; 1 → rank 1
    tp = ptg.Taskpool("rdvrt", A=A, B=B)
    tp.task_class(
        "S0", params=("k",),
        space=lambda g: ((0,),),
        affinity=lambda g, k: (g.B, (0,)),
        flows=[ptg.FlowSpec(
            "X", ptg.RW,
            ins=[ptg.In(data=lambda g, k: (g.B, (0,)))],
            outs=[ptg.Out(dst=("S1", lambda g, k: (0,), "X"))])])
    tp.task_class(
        "S1", params=("k",),
        space=lambda g: ((0,),),
        affinity=lambda g, k: (g.B, (1,)),
        flows=[ptg.FlowSpec(
            "X", ptg.RW,
            ins=[ptg.In(src=("S0", lambda g, k: (0,), "X"))],
            outs=[ptg.Out(dst=("S2", lambda g, k: (0,), "X"))])])
    tp.task_class(
        "S2", params=("k",),
        space=lambda g: ((0,),),
        affinity=lambda g, k: (g.B, (2,)),
        flows=[ptg.FlowSpec(
            "X", ptg.RW,
            ins=[ptg.In(src=("S1", lambda g, k: (0,), "X"))],
            outs=[ptg.Out(data=lambda g, k: (g.A, (2,)))])])

    # powers of two keep every f32 op exact → bitwise-assertable result
    @tp.task_class_by_name("S0").body
    def s0_body(task, X):
        return X * 0.5

    @tp.task_class_by_name("S1").body
    def s1_body(task, X):
        return X * -4.0

    @tp.task_class_by_name("S2").body
    def s2_body(task, X):
        return X

    ctx.add_taskpool(tp)
    ctx.start()
    assert ctx.wait(timeout=120), f"rank {rank}: roundtrip stalled"
    if A.rank_of((2,)) == rank:
        expect = np.arange(n, dtype=np.float32) * -2.0
        np.testing.assert_array_equal(np.asarray(A.v[2]), expect)
    st = engine.wire_stats()
    # each rank received one >1 MB value: a pushed segment stream
    # (comm.rdv_push default) or one classic rendezvous GET
    assert st["gets"] >= 1 or st["segs_recv"] >= 1, st
    return st["gets"] + st["segs_recv"]


def scenario_rendezvous_roundtrip_thread_multiple(ctx, engine, rank,
                                                  nb_ranks):
    """Same ≥1 MB both-directions rendezvous, with worker threads
    direct-sending (the vectored send path under per-peer lock
    contention instead of comm-thread funnelling)."""
    from parsec_tpu.utils import mca_param
    mca_param.set("comm.thread_multiple", 1)
    try:
        return scenario_rendezvous_roundtrip(ctx, engine, rank, nb_ranks)
    finally:
        mca_param.unset("comm.thread_multiple")


def test_rendezvous_1m_roundtrip_2ranks():
    res = _run_ranks("scenario_rendezvous_roundtrip", 2)
    assert sum(res.values()) >= 2, res     # one stream/GET per direction


def test_rendezvous_1m_roundtrip_thread_multiple():
    res = _run_ranks("scenario_rendezvous_roundtrip_thread_multiple", 2)
    assert sum(res.values()) >= 2, res


def scenario_rendezvous_roundtrip_classic(ctx, engine, rank, nb_ranks):
    """comm.rdv_push=0: the classic registered-memory GET/PUT rendezvous
    must keep working bitwise — it is the fallback protocol and the
    reference-parity path (remote_dep_mpi.c:1963-2118)."""
    from parsec_tpu.utils import mca_param
    mca_param.set("comm.rdv_push", 0)
    try:
        result = scenario_rendezvous_roundtrip(ctx, engine, rank, nb_ranks)
        st = engine.wire_stats()
        assert st["gets"] >= 1 and st["segs_recv"] == 0, st
        return result
    finally:
        mca_param.unset("comm.rdv_push")


def test_rendezvous_1m_roundtrip_classic_getput():
    res = _run_ranks("scenario_rendezvous_roundtrip_classic", 2)
    assert sum(res.values()) >= 2, res


def scenario_getrf_left_2ranks(ctx, engine, rank, nb_ranks, n=192, nb=32):
    """The left-looking LU taskpool multi-rank: UPDC/UPDR's gathered L/U
    operands resolve remote tiles through the one-sided fetch service
    (same pattern as potrf_left; no-pivot LU on a diagonally-dominant
    input)."""
    import scipy.linalg as sla
    from parsec_tpu.algorithms.getrf import build_getrf_left
    from parsec_tpu.data.matrix import TiledMatrix, TwoDimBlockCyclic

    rng = np.random.default_rng(4)
    A_host = (rng.standard_normal((n, n)) + 2.0 * n * np.eye(n)) \
        .astype(np.float32)
    dist = TwoDimBlockCyclic(P=nb_ranks, Q=1)
    A = TiledMatrix.from_array(A_host.copy(), nb, nb, dist=dist,
                               myrank=rank, name="A")
    tp = build_getrf_left(A)
    ctx.add_taskpool(tp)
    ctx.start()
    assert ctx.wait(timeout=90), \
        f"rank {rank}: getrf_left did not terminate"
    # no-pivot LU reference: diagonal dominance makes partial pivoting
    # pick the diagonal, so scipy's P is the identity
    P, L_ref, U_ref = sla.lu(A_host.astype(np.float64))
    assert np.allclose(P, np.eye(n)), "reference pivoted unexpectedly"
    packed_ref = np.tril(L_ref, -1) + U_ref
    for (i, j) in A.local_keys():
        tile = np.asarray(A.data_of((i, j)), dtype=np.float64)
        ref = packed_ref[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]
        err = np.linalg.norm(tile - ref) / max(1e-30, np.linalg.norm(ref))
        assert err < 1e-3, f"rank {rank} tile ({i},{j}) err {err}"
    return len(list(A.local_keys()))


def test_getrf_left_2ranks():
    res = _run_ranks("scenario_getrf_left_2ranks", 2)
    assert len(res) == 2


# ---- 4/8-rank scale (reference MPI_TEST_CMD_LIST nprocs up to 8,
# /root/reference/tests/CMakeLists.txt:925-952; SURVEY §4) ---------------

def test_potrf_left_4ranks():
    """The flagship left-looking taskpool at 4 real processes: gathered
    UPDATE operands fetch across a 4-rank mesh (tree fan-outs and the
    full-mesh wireup get depth they never see at 2-3 ranks)."""
    _run_ranks("scenario_potrf_left", 4, n=256, nb=32)


def scenario_chain_fourcounter(ctx, engine, rank, nb_ranks, n_steps=64):
    """Cross-rank chain under the four-counter termdet wave: every rank
    oscillates busy/idle per hop, so waves launch continuously and the
    rank-0 coordinator is raced by all peers' requests and replies —
    the interleavings an 8-rank mesh produces and 2 ranks never do."""
    from parsec_tpu.utils import mca_param
    mca_param.set("termdet", "fourcounter")
    try:
        # 150 s wait: 8 children × (jax import + 2 workers + comm
        # thread) share ONE cpu under the full suite — passes in ~30 s
        # standalone, needs the margin in suite context
        return scenario_chain(ctx, engine, rank, nb_ranks,
                              n_steps=n_steps, wait_timeout=150)
    finally:
        mca_param.unset("termdet")


def test_chain_fourcounter_8ranks():
    _run_ranks("scenario_chain_fourcounter", 8, n_steps=64,
               timeout=300.0)


def scenario_bcast_binomial(ctx, engine, rank, nb_ranks, nb=16):
    """Binomial-tree broadcast over an nb_ranks-rank mesh: one tile per
    rank, so the tree's inner hops are REAL remote activations — at 8
    ranks the tree has depth 3 (the first configuration where a
    non-root node forwards to multiple children)."""
    from parsec_tpu.data.matrix import TiledMatrix, TwoDimBlockCyclic
    from parsec_tpu.data.matrix_ops import build_broadcast

    nt = nb_ranks                    # one block-row per rank
    host = np.zeros((nt * nb, nb), np.float32)
    host[:nb] = np.arange(nb * nb, dtype=np.float32).reshape(nb, nb)
    dist = TwoDimBlockCyclic(P=nb_ranks, Q=1)
    A = TiledMatrix.from_array(host.copy(), nb, nb, dist=dist,
                               myrank=rank, name="A")
    tp = build_broadcast(A, root=(0, 0))
    ctx.add_taskpool(tp)
    ctx.start()
    assert ctx.wait(timeout=90), f"rank {rank}: bcast did not terminate"
    root_tile = host[:nb]
    for (i, j) in A.local_keys():
        np.testing.assert_array_equal(np.asarray(A.data_of((i, j))),
                                      root_tile)
    return len(list(A.local_keys()))


def test_bcast_binomial_8ranks():
    res = _run_ranks("scenario_bcast_binomial", 8, timeout=180.0)
    assert len(res) == 8


# ---- distributed DTD stress: parked activations at 4 ranks --------------
# Reference bar: remote_dep_mpi.c:1935-1961 (activations parked until the
# local replay discovers their task) + insert_function.h:131-142 (sliding
# window). SURVEY §7 calls this interaction "easy to get subtly wrong";
# randomized per-rank insertion delays force remote values to race ahead
# of local discovery, a tiny window forces mid-insertion drain, and
# pseudo-random placement churns affinity across all 4 ranks.

def scenario_dtd_stress(ctx, engine, rank, nb_ranks, n_tasks=240):
    import time as _t
    from parsec_tpu.dsl import dtd
    from parsec_tpu.utils import mca_param

    class _FullVec(_DistVec):
        # DTD replay reads placement tiles on EVERY rank — hold all
        # keys; dc_id must be UNIQUE per collection (the tile registry
        # keys by it; _DistVec's shared default would alias P and A)
        def __init__(self, n, nb_ranks, my_rank, init=0.0, dc_id=61):
            super().__init__(n, nb_ranks, my_rank, init)
            self.v = {i: np.float32(init) for i in range(n)}
            self.dc_id = dc_id

    class _HashVec(_FullVec):
        # placement churn: pseudo-random but replay-identical owner per
        # index (a pure function of the key, same on every rank)
        def rank_of(self, key):
            k = self._k(key)
            return (k * 2654435761 % 97) % self.nb_ranks

    mca_param.set("dtd.window_size", 8)       # force mid-insertion drain
    mca_param.set("dtd.threshold_size", 4)
    try:
        P = _HashVec(n_tasks, nb_ranks, rank, dc_id=61)   # placement
        A = _FullVec(1, nb_ranks, rank, init=1.0, dc_id=62)  # datum
        tp = dtd.Taskpool("stress")
        ctx.add_taskpool(tp)
        ctx.start()

        def step(p, x, k=0):
            # contractive map (factors 0.5..1.25, product < 1 per
            # period): values stay O(1) over hundreds of steps, so the
            # bitwise float32 comparison is meaningful
            return np.float32(x * np.float32(0.5 + (k % 7) * 0.125)
                              + np.float32(k % 3))

        rng = np.random.default_rng(1000 + rank)   # DIFFERENT per rank
        for k in range(n_tasks):
            # the replay itself is identical on every rank; only the
            # TIMING differs — this is what races remote activations
            # against local discovery (the parked path)
            if rng.random() < 0.2:
                _t.sleep(float(rng.uniform(0, 0.004)))
            tp.insert_task(
                lambda p, x, k=k: step(p, x, k),
                dtd.TileArg(P, (k,), dtd.INPUT, affinity=True),
                dtd.TileArg(A, (0,), dtd.INOUT))
        tp.wait()
        tp.flush(A)
        parked = tp.parked_activations
    finally:
        mca_param.unset("dtd.window_size")
        mca_param.unset("dtd.threshold_size")
    return (float(A.v[0]) if A.rank_of((0,)) == rank else None, parked)


def test_dtd_stress_parked_4ranks():
    """240-task INOUT chain with churned placement over 4 real
    processes, randomized insertion timing, window=8: results must be
    bitwise-identical to the single-rank execution AND the parked-
    activation path must actually have fired somewhere."""
    n_tasks = 240
    res = _run_ranks("scenario_dtd_stress", 4, n_tasks=n_tasks,
                     timeout=180.0)
    # single-rank reference (same float32 op order)
    x = np.float32(1.0)
    for k in range(n_tasks):
        x = np.float32(x * np.float32(0.5 + (k % 7) * 0.125)
                       + np.float32(k % 3))
    vals = [v for (v, _p) in res.values() if v is not None]
    assert len(vals) == 1, res
    assert vals[0] == float(x), (vals[0], float(x))
    total_parked = sum(p for (_v, p) in res.values())
    assert total_parked > 0, \
        f"parked-activation path never fired: {res}"


# ---- failure detection (peer death must abort, not hang) ----------------

def _death_child(rank, nb_ranks, base_port, q):
    """Child for the peer-death test: a cross-rank chain with slow
    bodies; rank 1 reports its pid then keeps running (the parent
    SIGKILLs it mid-chain); survivors must RAISE promptly — the
    reference gets this from MPI's default error handler +
    parsec_abort (runtime.h:33-37), not from timeouts."""
    import os
    import time
    import traceback
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
        from parsec_tpu.comm.socket_engine import SocketCommEngine
        from parsec_tpu.core import context as ctx_mod
        from parsec_tpu.dsl import ptg

        engine = SocketCommEngine(rank, nb_ranks, base_port=base_port)
        ctx = ctx_mod.init(nb_cores=2, comm=engine)
        n_steps = 200
        A = _DistVec(n_steps, nb_ranks, rank)
        tp = ptg.Taskpool("deathchain", N=n_steps, A=A)
        tp.task_class(
            "STEP", params=("k",),
            space=lambda g: ((k,) for k in range(g.N)),
            affinity=lambda g, k: (g.A, (k,)),
            flows=[ptg.FlowSpec(
                "T", ptg.RW,
                ins=[ptg.In(data=lambda g, k: (g.A, (0,)),
                            guard=lambda g, k: k == 0),
                     ptg.In(src=("STEP", lambda g, k: (k - 1,), "T"),
                            guard=lambda g, k: k > 0)],
                outs=[ptg.Out(dst=("STEP", lambda g, k: (k + 1,), "T"),
                              guard=lambda g, k: k < g.N - 1),
                      ptg.Out(data=lambda g, k: (g.A, (k,)))])])

        # batchable=False: a compiled body would trace the sleep away
        # and finish the chain in milliseconds — the kill must land
        # mid-flight
        @tp.task_class_by_name("STEP").body(batchable=False)
        def step_body(task, T):
            time.sleep(0.02)     # keep the chain in flight for seconds
            return T + 1

        ctx.add_taskpool(tp)
        ctx.start()
        if rank == 1:
            q.put((rank, "ready", os.getpid()))
            time.sleep(300)      # parent SIGKILLs this process
            return
        t0 = time.monotonic()
        try:
            ctx.wait(timeout=90)
            q.put((rank, "no-error", None))
        except RuntimeError as exc:
            elapsed = time.monotonic() - t0
            ctx.fini()           # teardown after failure must not hang
            q.put((rank, "raised", (elapsed, str(exc))))
    except BaseException as exc:  # noqa: BLE001 — report to parent
        q.put((rank, "error", f"{exc}\n{traceback.format_exc()}"))


def test_peer_death_aborts_survivor():
    """SIGKILL one rank mid-run: the survivor's ctx.wait must raise a
    diagnostic naming the dead peer well before any timeout."""
    import signal
    import time
    ctx = mp.get_context("spawn")
    base_port = _free_port_base(2)
    q = ctx.Queue()
    procs = [ctx.Process(target=_death_child, args=(r, 2, base_port, q))
             for r in range(2)]
    for p in procs:
        p.start()
    try:
        rank, status, pid = q.get(timeout=60)
        assert (rank, status) == (1, "ready"), (rank, status)
        time.sleep(1.0)                      # chain is mid-flight
        os.kill(pid, signal.SIGKILL)
        rank, status, payload = q.get(timeout=60)
        assert rank == 0
        assert status == "raised", (status, payload)
        elapsed, message = payload
        # detection is socket-close-driven: prompt, not timeout-driven
        assert elapsed < 30.0, f"took {elapsed:.1f}s — timeout, not detection"
        assert "peer rank 1" in message, message
    finally:
        for p in procs:
            p.join(timeout=10.0)
            if p.is_alive():
                p.terminate()


@pytest.mark.parametrize("payload_bytes,eager_limit,path", [
    (1024, 256 * 1024, "eager"),
    (128 * 1024, 64 * 1024, "rendezvous"),
])
def test_the_two_rank_hop_harness_runs_both_wire_paths(payload_bytes,
                                                       eager_limit, path):
    """``comm/pingpong.py measure_latency`` is what ROADMAP B3's cell
    will take (two ranks, every hop one remote activation carrying the
    payload). Nothing else in the tree calls it: here it runs to its
    end below and above the eager limit and names the path the payload
    took."""
    from parsec_tpu.comm.pingpong import measure_latency
    hops = 16
    got = measure_latency(payload_bytes=payload_bytes, hops=hops,
                          eager_limit=eager_limit, timeout=120.0)
    assert got["path"] == path and got["payload_bytes"] == payload_bytes
    assert got["hops"] == hops
    assert 0.0 < got["p50_us"] <= got["p90_us"] <= got["p99_us"]
