"""The mesh-scoped tenant of the serving isolation test
(``tests/test_serving_isolation.py``): a round-robin 1-D collection over
the ranks of a socket mesh, a PTG chain pool whose every link hops to
the next rank's tile, and the peer rank's main, which can SIGKILL itself
after a number of completed tasks (``comm.fault_inject=kill``). The
test runs rank 0 itself beside a rank-local sibling pool and checks
that the kill aborts the mesh-scoped pool alone.
"""

from __future__ import annotations

import time

import numpy as np

_CHAIN_TILES = 8            # distributed tenant: tiles per rank round


class _DistVec:
    """Round-robin 1-D collection spanning the mesh."""

    def __init__(self, name: str, n: int, nb_ranks: int, my_rank: int):
        self.name = name
        self.n = n
        self.nb_ranks = nb_ranks
        self.myrank = my_rank
        self.dc_id = 977
        self.v = {(i,): np.float32(i + 0.5) for i in range(n)
                  if i % nb_ranks == my_rank}

    @staticmethod
    def _k(key):
        return (key[0],) if isinstance(key, (tuple, list)) else (key,)

    def rank_of(self, key) -> int:
        return self._k(key)[0] % self.nb_ranks

    def data_of(self, key):
        return self.v[self._k(key)]

    def write_tile(self, key, value) -> None:
        self.v[self._k(key)] = value

    def keys(self):
        return [(i,) for i in range(self.n)]

    def is_local(self, key) -> bool:
        return self.rank_of(key) == self.myrank


def _build_dist_chain(X, n_tiles: int, rounds: int, delay_s: float):
    """The cross-rank pool: per tile a ``rounds``-deep chain
    whose every link hops to the next rank's tile (cross-rank halo
    traffic each step) with a small per-task delay so the pool spans
    the sibling's rounds and the injected kill lands mid-load."""
    from ..dsl import ptg

    tp = ptg.Taskpool("dist_chain", X=X, N=n_tiles, T=rounds, D=delay_s)
    C = tp.task_class(
        "C", params=("t", "i"),
        space=lambda g: ((t, i) for t in range(g.T) for i in range(g.N)),
        affinity=lambda g, t, i: (g.X, ((i + t) % g.N,)),
        flows=[ptg.FlowSpec(
            "S", ptg.RW,
            ins=[ptg.In(data=lambda g, t, i: (g.X, (i,)),
                        guard=lambda g, t, i: t == 0),
                 ptg.In(src=("C", lambda g, t, i: (t - 1, i), "S"),
                        guard=lambda g, t, i: t > 0)],
            outs=[ptg.Out(dst=("C", lambda g, t, i: (t + 1, i), "S"),
                          guard=lambda g, t, i: t < g.T - 1),
                  ptg.Out(data=lambda g, t, i: (g.X, (i,)),
                          guard=lambda g, t, i: t == g.T - 1)])])

    @C.body(batchable=False)
    def c_body(task, S):
        time.sleep(tp.g.D)
        return np.float32(S * np.float32(1.0009765625))

    return tp


def _peer_main(rank: int, nb_ranks: int, base_port: int, rounds: int,
               delay_s: float, kill_after: int, q) -> None:
    """Rank 1 of the mesh: runs its share of the distributed pool.
    With ``kill_after`` > 0 this rank SIGKILLs itself
    (``comm.fault_inject=kill`` → os._exit) after that many completed
    tasks: the mid-load rank death."""
    try:
        from ..comm.socket_engine import SocketCommEngine
        from ..core import context as ctx_mod
        from ..utils import mca_param

        from ..utils.benchenv import pin_wire_bench_env
        pin_wire_bench_env()
        if kill_after > 0:
            mca_param.set("comm.fault_inject", "kill")
            mca_param.set("comm.fault_inject_rank", rank)
            mca_param.set("comm.fault_inject_after", kill_after)
            mca_param.set("comm.fault_inject_unit", "tasks")
        engine = SocketCommEngine(rank, nb_ranks, base_port=base_port)
        ctx = ctx_mod.init(nb_cores=2, comm=engine)
        X = _DistVec("XD", _CHAIN_TILES, nb_ranks, rank)
        tp = _build_dist_chain(X, _CHAIN_TILES, rounds, delay_s)
        ctx.add_taskpool(tp)
        ctx.start()
        ok = ctx.wait(timeout=120)
        vals = {i: float(X.data_of((i,))) for i in range(_CHAIN_TILES)
                if X.rank_of((i,)) == rank}
        engine.sync()
        ctx.fini()
        q.put((rank, "ok", {"terminated": ok, "vals": vals}))
    except BaseException as exc:  # noqa: BLE001 — report to parent
        import traceback
        q.put((rank, "error", f"{exc}\n{traceback.format_exc()}"))
