"""Activate→data latency pingpong over the socket comm engine.

The reference measures comm latency with ``tests/apps/pingpong`` (a
2-rank JDF bouncing a tile back and forth) and instruments per-message
timelines that ``check-comms.py`` asserts on. This module is the TPU
build's equivalent: a chain taskpool whose steps alternate ownership
between two ranks, so EVERY hop is one remote activation carrying the
payload — p50 hop time IS the "remote_dep p50 activate→data latency" of
BASELINE.md (eager inline path below ``comm.eager_limit``, registered-
memory GET/PUT rendezvous above it).

Run as a harness (spawns its own 2 ranks):

    from parsec_tpu.comm.pingpong import measure_latency
    stats = measure_latency(payload_bytes=1024, hops=200)
    # {'p50_us': ..., 'p90_us': ..., 'path': 'eager', ...}
"""

from __future__ import annotations

import multiprocessing as mp
import socket
import time
from typing import Dict

import numpy as np


def _free_port_base(n_ranks: int = 2, tries: int = 64) -> int:
    """A base port with ``n_ranks`` consecutive bindable ports — actually
    verified by binding each one (racy-but-rare: released before use)."""
    rng = np.random.default_rng()
    for _ in range(tries):
        base = 21000 + int(rng.integers(0, 20000))
        socks = []
        try:
            for r in range(n_ranks):
                s = socket.socket()
                s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                s.bind(("127.0.0.1", base + r))
                socks.append(s)
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError("no free consecutive port range found")


class _AlternatingVec:
    """1-D scalar-tile collection alternating ownership by index."""

    def __init__(self, n: int, nb_ranks: int, my_rank: int,
                 payload_f32: int, device: bool = False):
        self.n = n
        self.nb_ranks = nb_ranks
        self.my_rank = my_rank
        self.dc_id = 11
        self.payload_f32 = payload_f32
        self.v = {}
        if self.rank_of((0,)) == my_rank:
            init = np.zeros(payload_f32, dtype=np.float32)
            if device:
                import jax
                init = jax.device_put(init)
            self.v[0] = init

    def _k(self, key):
        return key[0] if isinstance(key, (tuple, list)) else key

    def rank_of(self, key):
        return self._k(key) % self.nb_ranks

    def data_of(self, key):
        return self.v[self._k(key)]

    def write_tile(self, key, value):
        self.v[self._k(key)] = value


def _build_chain(hops: int, A, device: bool = False):
    from ..dsl import ptg

    tp = ptg.Taskpool("pingpong", N=hops, A=A)
    tp.task_class(
        "HOP", params=("k",),
        space=lambda g: ((k,) for k in range(g.N)),
        affinity=lambda g, k: (g.A, (k,)),
        flows=[ptg.FlowSpec(
            "T", ptg.RW,
            ins=[ptg.In(data=lambda g, k: (g.A, (0,)),
                        guard=lambda g, k: k == 0),
                 ptg.In(src=("HOP", lambda g, k: (k - 1,), "T"),
                        guard=lambda g, k: k > 0)],
            outs=[ptg.Out(dst=("HOP", lambda g, k: (k + 1,), "T"),
                          guard=lambda g, k: k < g.N - 1),
                  ptg.Out(data=lambda g, k: (g.A, (g.N - 1,)),
                          guard=lambda g, k: k == g.N - 1)])])

    hop_times = []

    # batchable=False: the timestamp side effect must run per execution
    # on the host — a jit-cached body would stamp only at trace time
    @tp.task_class_by_name("HOP").body(batchable=False)
    def hop_body(task, T):
        hop_times.append(time.perf_counter())
        if device:
            # device-resident payload round trip: the hop's work runs on
            # the accelerator, so every wire crossing pays the real
            # D2H-at-send / stage-to-device-at-receive path
            import jax.numpy as jnp
            return jnp.asarray(T) + 1.0
        return T + 1.0

    return tp, hop_times


def _claim_chip(rank: int) -> None:
    """Device-payload ranks need ONE CHIP EACH: a chip belongs to the
    first process that initialises libtpu on it. Restrict this process
    to chip ``rank`` before any backend comes up, then require that the
    backend did come up on an accelerator — on a one-chip host rank 1
    fails here, by name, instead of hanging on rank 0's chip. A run
    started on the CPU platform (tests) keeps its CPU devices."""
    import os

    from ..utils.jax_platform import cpu_requested
    if cpu_requested():
        return
    os.environ["TPU_VISIBLE_CHIPS"] = str(rank)
    os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
    os.environ["TPU_PROCESS_BOUNDS"] = "1,1,1"
    import jax
    if jax.devices()[0].platform == "cpu":
        raise RuntimeError(
            f"device-payload pingpong rank {rank}: no chip {rank} on "
            "this host (JAX fell back to the CPU platform). This row "
            "needs one chip per rank; on a one-chip host the device "
            "hop one process can measure is measure_ici_latency")


def _rank_main(rank: int, nb_ranks: int, base_port: int, hops: int,
               payload_f32: int, eager_limit: int, q,
               device: bool = False, knobs=None) -> None:
    try:
        from ..comm.socket_engine import SocketCommEngine
        from ..core import context as ctx_mod
        from ..utils import mca_param

        knobs = dict(knobs or {})

        from ..utils.benchenv import pin_wire_bench_env
        from ..utils.jax_platform import pin_cpu_platform

        mca_param.set("comm.eager_limit", eager_limit)
        if not device:
            # host-payload latency rows measure the WIRE: the rank runs
            # on the CPU platform (it must not claim a chip) and the
            # shared pins keep stage-through reads + receive staging
            # from routing payloads through a device. tpu_off=False:
            # the hop still dispatches through a device module, as it
            # always has; on this platform that module is a CPU one.
            pin_cpu_platform()
            pin_wire_bench_env(tpu_off=False, overrides=knobs)
        else:
            _claim_chip(rank)
            for _k, _v in knobs.items():
                mca_param.set(_k, _v)
        engine = SocketCommEngine(rank, nb_ranks, base_port=base_port)
        ctx = ctx_mod.init(nb_cores=1, comm=engine)
        A = _AlternatingVec(hops, nb_ranks, rank, payload_f32,
                            device=device)
        tp, hop_times = _build_chain(hops, A, device=device)
        ctx.add_taskpool(tp)
        t0 = time.perf_counter()
        ctx.start()         # enables the comm thread; hop stamps carry
        ok = ctx.wait(timeout=300)   # the per-hop timing signal
        t1 = time.perf_counter()
        engine.sync()
        ctx.fini()
        if not ok:
            raise RuntimeError(f"rank {rank}: pingpong did not terminate")
        # per-hop latency from consecutive local execution stamps: my
        # hops run every 2nd step, so consecutive stamps span exactly
        # one round trip (out + back) = 2 hops
        stamps = np.asarray(hop_times)
        rtt = np.diff(stamps)
        q.put((rank, "ok", {"total_s": t1 - t0,
                            "hop_us": (rtt / 2 * 1e6).tolist()}))
    except BaseException as exc:  # noqa: BLE001 — report to parent
        import traceback
        q.put((rank, "error", f"{exc}\n{traceback.format_exc()}"))


def measure_latency(payload_bytes: int = 1024, hops: int = 200,
                    eager_limit: int = 256 * 1024,
                    timeout: float = 300.0,
                    device_payload: bool = False,
                    knobs: Dict = None) -> Dict:
    """Spawn 2 ranks, bounce a ``payload_bytes`` array ``hops`` times,
    return percentile activate→data latencies in microseconds.
    ``device_payload=True``: the payload lives on the accelerator at
    each end — hops measure the full device→wire→device path (async
    segmented D2H at send, per-segment device_put at receive under
    ``comm.device_pipeline``; the round-5 blocking snapshot/restage
    path under ``=0`` — the bench's A/B arms). ``knobs``: extra MCA
    params pinned in BOTH rank processes (e.g. the device-plane A/B
    arm and a matched ``comm.segment_bytes``)."""
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    base_port = _free_port_base()
    payload_f32 = max(payload_bytes // 4, 1)
    procs = [ctx.Process(target=_rank_main,
                         args=(r, 2, base_port, hops, payload_f32,
                               eager_limit, q, device_payload, knobs))
             for r in range(2)]
    for p in procs:
        p.start()
    results = {}
    try:
        for _ in range(2):
            rank, status, payload = q.get(timeout=timeout)
            if status != "ok":
                raise RuntimeError(f"rank {rank} failed:\n{payload}")
            results[rank] = payload
    finally:
        # a rank that failed leaves its peer waiting on the wire: do not
        # wait for that one, and do not return before both are GONE —
        # a rank that owns a chip holds it until its process has exited
        for p in procs:
            if len(results) == 2:
                p.join(timeout=10.0)
            if p.is_alive():
                p.terminate()
                p.join(timeout=10.0)
            if p.is_alive():
                p.kill()
                p.join()

    # drop each rank's warmup hops (connection + first-touch costs)
    # BEFORE concatenating — rank 1's warmup sits mid-array otherwise
    per_rank = [r["hop_us"][2:] if len(r["hop_us"]) > 4 else r["hop_us"]
                for r in results.values()]
    hop_us = np.asarray(sum(per_rank, []))
    real_bytes = payload_f32 * 4
    return {
        "payload_bytes": real_bytes,
        "path": "eager" if real_bytes <= eager_limit else "rendezvous",
        "hops": hops,
        "p50_us": float(np.percentile(hop_us, 50)),
        "p90_us": float(np.percentile(hop_us, 90)),
        "p99_us": float(np.percentile(hop_us, 99)),
        "total_s": max(r["total_s"] for r in results.values()),
    }


def measure_ici_latency(payload_bytes: int = 1 << 16, hops: int = 64,
                        timeout: float = 120.0) -> Dict:
    """Same-mesh device-direct hop (the ICI row): two loopback ranks in
    ONE process whose comm mesh is registered over the visible jax
    devices (``compiled.spmd.register_comm_mesh``), bouncing a
    device-resident payload with ``comm.device_direct`` forced on. Each
    hop moves the tile as an XLA device-to-device ``device_put`` — the
    payload never touches host memory, and the engines' wire counters
    see only CONTROL frames. Returns hop percentiles plus the measured
    per-hop wire bytes and the payload size (the host-bypass proof:
    wire bytes ≈ control-frame size ≪ payload)."""
    import jax
    import parsec_tpu as parsec
    from ..compiled import spmd
    from ..termdet import FourCounterTermdet
    from ..utils import mca_param
    from .local import LocalCommEngine

    # this harness runs INSIDE the bench process: snapshot the knob
    # overrides and any registered comm mesh, and restore them after —
    # unset() would destroy a caller's explicit pins
    _KNOBS = ("comm.device_direct", "comm.stage_recv",
              "runtime.stage_reads")
    saved = {k: mca_param.override_of(k) for k in _KNOBS}
    saved_mesh = spmd.comm_mesh()
    mca_param.set("comm.device_direct", "1")
    mca_param.set("comm.stage_recv", "0")
    mca_param.set("runtime.stage_reads", "0")
    spmd.register_comm_mesh(spmd.make_mesh())
    engines = LocalCommEngine.make_fabric(2)
    ctxs, tps, times, vecs = [], [], [], []
    try:
        for r in range(2):
            ctx = parsec.init(nb_cores=1, comm=engines[r])
            A = _AlternatingVec(hops, 2, r, max(payload_bytes // 4, 1),
                                device=True)
            tp, hop_times = _build_chain(hops, A, device=True)
            tp.monitor = FourCounterTermdet(comm=engines[r])
            ctxs.append(ctx)
            tps.append(tp)
            times.append(hop_times)
            vecs.append(A)
            ctx.add_taskpool(tp)
        for ctx in ctxs:
            ctx.start()
        for ctx in ctxs:
            if not ctx.wait(timeout=timeout):
                raise RuntimeError("ICI pingpong did not terminate")
        stats = engines[0].stats
        msgs = max(stats["activations_sent"], 1)
        wire_per_hop = stats["bytes_sent"] / msgs
    finally:
        for ctx in ctxs:
            parsec.fini(ctx)
        if saved_mesh is not None:
            spmd.register_comm_mesh(saved_mesh[0], saved_mesh[1])
        else:
            spmd.unregister_comm_mesh()
        for key in _KNOBS:
            mca_param.restore_override(key, saved[key])
    per_rank = [t[2:] if len(t) > 4 else list(t) for t in times]
    hop_us = []
    for t in per_rank:
        d = np.diff(np.asarray(t)) / 2 * 1e6
        hop_us.extend(d.tolist())
    hop_us = np.asarray(hop_us) if hop_us else np.asarray([0.0])
    return {
        "payload_bytes": max(payload_bytes // 4, 1) * 4,
        "hops": hops,
        "devices": len(jax.devices()),
        # where the payload sat at rest: the seed tile on rank 0 and the
        # last hop's write-back on its owner — two ids on a multi-chip
        # host mean the hop really crossed chips
        "payload_device_ids": sorted(
            {d.id for A in vecs for v in A.v.values()
             for d in v.devices()}),
        "p50_us": float(np.percentile(hop_us, 50)),
        "p90_us": float(np.percentile(hop_us, 90)),
        "wire_bytes_per_hop": round(float(wire_per_hop), 1),
        "host_bypass": bool(wire_per_hop < max(payload_bytes // 8,
                                               4096)),
    }
