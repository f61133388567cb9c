"""Tiled QR factorization (flat-tree DPLASMA dgeqrf) as a PTG taskpool.

The BASELINE.md "PTG dgeqrf reduction-tree stress" config. Task classes
mirror zgeqrf.jdf (panel factorization + trailing update per step k)
over two collections, A (nb x nb tiles) and T (ib x nb tiles):

    GEQRT(k):     A(k,k) = Q R: R in the upper triangle, V (unit lower)
                  under it, T(k,k)
    TSQRT(m,k):   [R; A(m,k)] = Q [R'; 0]: R' over R in A(k,k) (GEQRT's V
                  stays), V2 in A(m,k), T(m,k)
                  (flat reduction tree down column k: m = k+1 .. MT-1)
    UNMQR(k,n):   row-panel update A(k,n) <- Q_kk^T A(k,n)
    TSMQR(m,n,k): stacked-pair update [A(k,n); A(m,n)] <- Q_mk^T [..]

On completion A holds the factored form upstream's dgeqrf leaves: R in
the upper triangle, the Householder vectors below it (what dormqr, dorgqr
and dgeqrs go on to read), and T holds the block reflectors' triangular
factors, the T_j of a tile side by side (ops/tile_kernels.py). Q is the
product over k ascending of Q_kk and then Q_mk, m ascending; A = Q R.

The factorization runs in the storage of A and T, as upstream's does:
every class writes each of its results to its tile, the running row
A(k,n) and the trailing tile A(m,n) of a TSMQR too, so the version
before it is freed (algorithms/potrf.py build_potrf, PERF.md section 6,
PR 27), and an UNMQR's and a TSMQR's updates are made in the buffers
the tiles lie in on a chip module (``Chore.donates``). Every flow carries
its tile, so the taskpool runs on the host runtime and on the compiled
wavefront/SPMD executors.
"""

from __future__ import annotations

from typing import Optional

from ..core.task import GROUP_SIZES
from ..dsl import ptg
from ..data.matrix import TiledMatrix
from ..ops.tile_kernels import geqrt_tile, tsmqr_tile, tsqrt_tile, unmqr_tile


def geqrf_t_collection(A: TiledMatrix, ib: int) -> TiledMatrix:
    """descT of ``A``: a tile of ``ib`` x nb beside every tile of A on
    and under the diagonal (a tile is made when it is first written)."""
    if A.nb % ib:
        raise ValueError(f"ib={ib} does not divide nb={A.nb}")
    return TiledMatrix(A.mt * ib, A.nt * A.nb, ib, A.nb, dist=A.dist,
                       dtype=A.dtype, name=f"{A.name}_T")


def _row(kernel, shared, *stacks):
    """``kernel(*shared, *tiles)`` over the members of a row that shares
    the operands ``shared`` (V and T here), their tiles stacked. A chip module's group (eight members at
    most) is unrolled: XLA then reads each member where it lies and
    writes it where it goes, and a launch of four costs the chip what
    the four cost alone; one product over the stack paid a copy of every
    tile in and out, 0.44 ms a 2048-tile TSMQR against 0.31 (PERF.md
    section 6, PR 33). An executor's wave of any width is one vmap."""
    import jax
    import jax.numpy as jnp
    n = stacks[0].shape[0]
    if n > GROUP_SIZES[0]:
        return jax.vmap(kernel, in_axes=(None,) * len(shared) +
                        (0,) * len(stacks))(*shared, *stacks)
    outs = [kernel(*shared, *(s[i] for s in stacks)) for i in range(n)]
    if isinstance(outs[0], tuple):
        return tuple(jnp.stack(o) for o in zip(*outs))
    return jnp.stack(outs)


def build_geqrf(A: TiledMatrix, T: Optional[TiledMatrix] = None,
                ib: Optional[int] = None) -> ptg.Taskpool:
    """Build the GEQRF taskpool over tiled matrix ``A`` (MT >= NT) and its
    ``T`` (made here from ``A`` and ``ib``, one block a tile by default,
    where none is given; the pool's ``g.T``)."""
    MT, NT = A.mt, A.nt
    if MT < NT:
        raise ValueError("GEQRF needs MT >= NT (tall or square tile grid)")
    if A.mb != A.nb:
        raise ValueError("GEQRF needs square tiles (mb == nb)")
    if T is None:
        T = geqrf_t_collection(A, ib or A.nb)
    ib = T.mb
    if (T.mt, T.nt, T.nb) != (MT, NT, A.nb) or A.nb % ib:
        raise ValueError("T needs a tile of ib x nb per tile of A, "
                         "ib a divisor of nb")
    tp = ptg.Taskpool("geqrf", A=A, T=T, MT=MT, NT=NT)

    def kept(dc, key_fn):
        """The write-back every written flow ends with (in place)."""
        return ptg.Out(data=lambda g, *p: (getattr(g, dc), key_fn(*p)))

    GEQRT = tp.task_class(
        "GEQRT", params=("k",),
        space=lambda g: ((k,) for k in range(g.NT)),
        affinity=lambda g, k: (g.A, (k, k)),
        priority=lambda g, k: 4 * (g.NT - k) ** 2,
        flows=[
            ptg.FlowSpec(
                "A", ptg.RW,
                tile=lambda g, k: (g.A, (k, k)),
                ins=[ptg.In(data=lambda g, k: (g.A, (k, k)),
                            guard=lambda g, k: k == 0),
                     ptg.In(src=("TSMQR", lambda g, k: (k, k, k - 1), "A2"),
                            guard=lambda g, k: k > 0)],
                outs=[ptg.Out(dst=("UNMQR",
                                   lambda g, k: [(k, n)
                                                 for n in range(k + 1, g.NT)],
                                   "V")),
                      ptg.Out(dst=("TSQRT", lambda g, k: (k + 1, k), "R"),
                              guard=lambda g, k: k + 1 < g.MT),
                      kept("A", lambda k: (k, k))]),
            ptg.FlowSpec(
                "T", ptg.WRITE,
                tile=lambda g, k: (g.T, (k, k)),
                outs=[ptg.Out(dst=("UNMQR",
                                   lambda g, k: [(k, n)
                                                 for n in range(k + 1, g.NT)],
                                   "T")),
                      kept("T", lambda k: (k, k))]),
        ])

    TSQRT = tp.task_class(
        "TSQRT", params=("m", "k"),
        space=lambda g: ((m, k) for k in range(g.NT)
                         for m in range(k + 1, g.MT)),
        affinity=lambda g, m, k: (g.A, (m, k)),
        priority=lambda g, m, k: 3 * (g.NT - k) ** 2 - m,
        flows=[
            # the diagonal tile: R' over R, GEQRT's V under it kept
            ptg.FlowSpec(
                "R", ptg.RW,
                tile=lambda g, m, k: (g.A, (k, k)),
                ins=[ptg.In(src=("GEQRT", lambda g, m, k: (k,), "A"),
                            guard=lambda g, m, k: m == k + 1),
                     ptg.In(src=("TSQRT", lambda g, m, k: (m - 1, k), "R"),
                            guard=lambda g, m, k: m > k + 1)],
                outs=[ptg.Out(dst=("TSQRT", lambda g, m, k: (m + 1, k), "R"),
                              guard=lambda g, m, k: m + 1 < g.MT),
                      kept("A", lambda m, k: (k, k))]),
            # A(m,k) goes in, V2 comes out in its place
            ptg.FlowSpec(
                "A", ptg.RW,
                tile=lambda g, m, k: (g.A, (m, k)),
                ins=[ptg.In(data=lambda g, m, k: (g.A, (m, k)),
                            guard=lambda g, m, k: k == 0),
                     ptg.In(src=("TSMQR", lambda g, m, k: (m, k, k - 1),
                                 "A2"),
                            guard=lambda g, m, k: k > 0)],
                outs=[ptg.Out(dst=("TSMQR",
                                   lambda g, m, k: [(m, n, k)
                                                    for n in range(k + 1,
                                                                   g.NT)],
                                   "V")),
                      kept("A", lambda m, k: (m, k))]),
            ptg.FlowSpec(
                "T", ptg.WRITE,
                tile=lambda g, m, k: (g.T, (m, k)),
                outs=[ptg.Out(dst=("TSMQR",
                                   lambda g, m, k: [(m, n, k)
                                                    for n in range(k + 1,
                                                                   g.NT)],
                                   "T")),
                      kept("T", lambda m, k: (m, k))]),
        ])

    UNMQR = tp.task_class(
        "UNMQR", params=("k", "n"),
        space=lambda g: ((k, n) for k in range(g.NT)
                         for n in range(k + 1, g.NT)),
        affinity=lambda g, k, n: (g.A, (k, n)),
        priority=lambda g, k, n: 3 * (g.NT - k) ** 2 - n,
        flows=[
            ptg.FlowSpec(
                "V", ptg.READ,
                tile=lambda g, k, n: (g.A, (k, k)),
                ins=[ptg.In(src=("GEQRT", lambda g, k, n: (k,), "A"))]),
            ptg.FlowSpec(
                "T", ptg.READ,
                tile=lambda g, k, n: (g.T, (k, k)),
                ins=[ptg.In(src=("GEQRT", lambda g, k, n: (k,), "T"))]),
            ptg.FlowSpec(
                "C", ptg.RW,
                tile=lambda g, k, n: (g.A, (k, n)),
                ins=[ptg.In(data=lambda g, k, n: (g.A, (k, n)),
                            guard=lambda g, k, n: k == 0),
                     ptg.In(src=("TSMQR", lambda g, k, n: (k, n, k - 1),
                                 "A2"),
                            guard=lambda g, k, n: k > 0)],
                outs=[ptg.Out(dst=("TSMQR",
                                   lambda g, k, n: (k + 1, n, k), "C1"),
                              guard=lambda g, k, n: k + 1 < g.MT),
                      kept("A", lambda k, n: (k, n))]),
        ])

    TSMQR = tp.task_class(
        "TSMQR", params=("m", "n", "k"),
        space=lambda g: ((m, n, k) for k in range(g.NT)
                         for m in range(k + 1, g.MT)
                         for n in range(k + 1, g.NT)),
        affinity=lambda g, m, n, k: (g.A, (m, n)),
        priority=lambda g, m, n, k: (g.NT - k) ** 2 - m - n,
        flows=[
            ptg.FlowSpec(
                "V", ptg.READ,
                tile=lambda g, m, n, k: (g.A, (m, k)),
                ins=[ptg.In(src=("TSQRT", lambda g, m, n, k: (m, k),
                                 "A"))]),
            ptg.FlowSpec(
                "T", ptg.READ,
                tile=lambda g, m, n, k: (g.T, (m, k)),
                ins=[ptg.In(src=("TSQRT", lambda g, m, n, k: (m, k),
                                 "T"))]),
            # running row-k tile A(k,n), reduced down the column
            ptg.FlowSpec(
                "C1", ptg.RW,
                tile=lambda g, m, n, k: (g.A, (k, n)),
                ins=[ptg.In(src=("UNMQR", lambda g, m, n, k: (k, n), "C"),
                            guard=lambda g, m, n, k: m == k + 1),
                     ptg.In(src=("TSMQR",
                                 lambda g, m, n, k: (m - 1, n, k), "C1"),
                            guard=lambda g, m, n, k: m > k + 1)],
                outs=[ptg.Out(dst=("TSMQR",
                                   lambda g, m, n, k: (m + 1, n, k), "C1"),
                              guard=lambda g, m, n, k: m + 1 < g.MT),
                      kept("A", lambda m, n, k: (k, n))]),
            # trailing tile A(m,n)
            ptg.FlowSpec(
                "A2", ptg.RW,
                tile=lambda g, m, n, k: (g.A, (m, n)),
                ins=[ptg.In(data=lambda g, m, n, k: (g.A, (m, n)),
                            guard=lambda g, m, n, k: k == 0),
                     ptg.In(src=("TSMQR",
                                 lambda g, m, n, k: (m, n, k - 1), "A2"),
                            guard=lambda g, m, n, k: k > 0)],
                outs=[
                    ptg.Out(dst=("GEQRT", lambda g, m, n, k: (k + 1,), "A"),
                            guard=lambda g, m, n, k: m == k + 1 and
                            n == k + 1),
                    ptg.Out(dst=("TSQRT", lambda g, m, n, k: (m, k + 1), "A"),
                            guard=lambda g, m, n, k: m > k + 1 and
                            n == k + 1),
                    ptg.Out(dst=("UNMQR", lambda g, m, n, k: (k + 1, n), "C"),
                            guard=lambda g, m, n, k: m == k + 1 and
                            n > k + 1),
                    ptg.Out(dst=("TSMQR",
                                 lambda g, m, n, k: (m, n, k + 1), "A2"),
                            guard=lambda g, m, n, k: m > k + 1 and
                            n > k + 1),
                    kept("A", lambda m, n, k: (m, n)),
                ]),
        ])

    # Every class declares its stacked form (the members' READ flows
    # stacked, the wavefront executor's convention): a body without one
    # gets unrolled group programs at first sight. The members of a row
    # of TSMQRs share V2 and T, those of a row of UNMQRs V and T: one
    # operand, not one a member. GEQRT and TSQRT are serial chains, one
    # task a column at a time: their stacked form is for an executor's
    # wave (one of each column in flight) and declares the chain's own
    # tile shared, which no two tasks the host runtime holds ever do, so
    # a chip module never builds (nor compiles, steps later, when four
    # columns' TSQRTs happen to be ready together) a group program it
    # has no use for.
    import jax

    @GEQRT.body(batch_hook=lambda As: dict(zip(
        ("A", "T"), jax.vmap(lambda a: geqrt_tile(a, ib))(As))),
        batch_hook_shared=("A",))
    def geqrt_body(task, A_, Tv):
        return dict(zip(("A", "T"), geqrt_tile(A_, ib)))

    @TSQRT.body(batch_hook=lambda Rs, As: dict(zip(
        ("R", "A", "T"),
        jax.vmap(lambda r, a: tsqrt_tile(r, a, ib))(Rs, As))),
        batch_hook_shared=("R",))
    def tsqrt_body(task, R, A_, Tv):
        return dict(zip(("R", "A", "T"), tsqrt_tile(R, A_, ib)))

    # The updates run where their tiles lie (``donates``): the version of
    # A(k,n) or A(m,n) an UNMQR or a TSMQR takes in has no other reader
    # (a chain in m and in k, one successor a version, and the tile of A
    # it came from is the one the task writes), so a chip module hands
    # its buffer to the program for the flow's output, as upstream's
    # kernels update C in place. A TSMQR still queued behind a busy chip
    # then holds no second copy of its two tiles (PERF.md section 6, PR
    # 33); its results come in the order of its flows, C1 then A2, each
    # into its own buffer. GEQRT's and TSQRT's diagonal tile has the
    # row's UNMQRs reading V beside the chain, and stays as it is.
    @UNMQR.body(batch_hook=lambda Vs, Ts, Cs: _row(
        unmqr_tile, (Vs[0], Ts[0]), Cs), batch_hook_shared=("V", "T"),
        donates=("C",))
    def unmqr_body(task, V, T_, C):
        return unmqr_tile(V, T_, C)

    @TSMQR.body(batch_hook=lambda Vs, Ts, C1s, A2s: _row(
        tsmqr_tile, (Vs[0], Ts[0]), C1s, A2s), batch_hook_shared=("V", "T"),
        donates=("C1", "A2"))
    def tsmqr_body(task, V, T_, C1, A2):
        return tsmqr_tile(V, T_, C1, A2)

    return tp


def geqrf_flops(m: int, n: int) -> float:
    """Useful FLOPs of an m×n QR (LAPACK count, m ≥ n)."""
    return 2.0 * m * n * n - 2.0 * n ** 3 / 3.0 + m * n + n * n / 2.0


def build_geqrf_hh(A: TiledMatrix) -> ptg.Taskpool:
    """Blocked-Householder tiled QR (panel-fused flagship form).

    :func:`build_geqrf` mirrors the classic 4-kernel dgeqrf JDF, whose
    TSQRT/TSMQR recurrences serialize down each block column — the
    per-tile shape, not the MXU shape. This variant concentrates each
    step the way :func:`~.potrf.build_potrf_left` does for Cholesky:

        PANEL(k):     factor the whole block column A[k:, k] at once
                      (CholeskyQR2 + exact orthogonal-completion
                      reconstruction — ops.tile_kernels.panel_qr_tile);
                      emits the reconstruction pair (V, X⁻¹) as a
                      task→task VALUE (no collection placement)
        REDUCE(n,k):  Y_n = X⁻ᵀ·Vᵀ·A[k:, n] — the panel-wide reduction
                      for trailing block column n
        APPLY(m,n,k): A[m,n] ← A[m,n] − V_m·Y_n — rank-nb tile update
        ZEROV(m,k):   zero the reflector storage below the diagonal
                      (A holds R + zeros on completion, like build_geqrf)

    ASAP leveling yields exactly three waves per step —
    [PANEL(k)], [REDUCE(·,k)+ZEROV(·,k)], [APPLY(·,·,k)] — and the wave
    fuser lowers each to a handful of dense ops on the Aᵀ store: the
    whole trailing update is two large matmuls per step
    (Hᵀ·C = C − V·X⁻ᵀ·(Vᵀ·C)). Measured ~35× the flat-DAG tile-dict
    throughput on a v5e chip (see bench.py geqrf config).

    Distribution: PANEL/REDUCE resolve gathered column operands with
    the direct-memory pattern of reference JDF bodies — local tiles
    from the collection, remote tiles through the one-sided
    :meth:`~..comm.engine.CommEngine.fetch_tile` (CTL-gather ordering
    makes both race-free) — so the same taskpool runs single-process
    panel-fused AND multi-rank. Reference analog: the tree-reduction
    dgeqrf family (reference parsec/data_dist/matrix/reduce_col.jdf) —
    the panel here plays the whole reduction tree in one fused kernel.
    """
    MT, NT = A.mt, A.nt
    if MT < NT:
        raise ValueError("GEQRF needs MT >= NT (tall or square tile grid)")
    if A.mb != A.nb:
        raise ValueError("build_geqrf_hh needs square tiles (mb == nb)")
    nb = A.nb
    tp = ptg.Taskpool("geqrf_hh", A=A, MT=MT, NT=NT)

    PANEL = tp.task_class(
        "PANEL", params=("k",),
        space=lambda g: ((k,) for k in range(g.NT)),
        affinity=lambda g, k: (g.A, (k, k)),
        priority=lambda g, k: 3 * (g.NT - k) ** 2,
        flows=[
            # orders PANEL after every below-diagonal tile of column k
            # is written back (the direct collection reads in the body)
            ptg.FlowSpec(
                "G", ptg.CTL,
                ins=[ptg.In(src=("APPLY",
                                 lambda g, k: [(m, k, k - 1)
                                               for m in range(k + 1, g.MT)],
                                 "G"),
                            gather=True,
                            guard=lambda g, k: k > 0)]),
            ptg.FlowSpec(
                "Z", ptg.CTL,
                outs=[ptg.Out(dst=("ZEROV",
                                   lambda g, k: [(m, k)
                                                 for m in range(k + 1, g.MT)],
                                   "P"))]),
            ptg.FlowSpec(
                "C", ptg.RW,
                tile=lambda g, k: (g.A, (k, k)),
                ins=[ptg.In(data=lambda g, k: (g.A, (k, k)),
                            guard=lambda g, k: k == 0),
                     ptg.In(src=("APPLY", lambda g, k: (k, k, k - 1), "C"),
                            guard=lambda g, k: k > 0)],
                outs=[ptg.Out(data=lambda g, k: (g.A, (k, k)))]),
            # the reconstruction pair (V, X^-1): a task->task value with
            # no tile placement — the fuser carries it in state
            ptg.FlowSpec(
                "V", ptg.WRITE,
                outs=[ptg.Out(dst=("REDUCE",
                                   lambda g, k: [(n, k)
                                                 for n in range(k + 1, g.NT)],
                                   "V")),
                      ptg.Out(dst=("APPLY",
                                   lambda g, k: [(m, n, k)
                                                 for n in range(k + 1, g.NT)
                                                 for m in range(k, g.MT)],
                                   "V"))]),
        ])

    ZEROV = tp.task_class(
        "ZEROV", params=("m", "k"),
        space=lambda g: ((m, k) for k in range(g.NT)
                         for m in range(k + 1, g.MT)),
        affinity=lambda g, m, k: (g.A, (m, k)),
        priority=lambda g, m, k: 1,
        flows=[
            ptg.FlowSpec(
                "P", ptg.CTL,
                ins=[ptg.In(src=("PANEL", lambda g, m, k: (k,), "Z"))]),
            ptg.FlowSpec(
                "C", ptg.WRITE,
                tile=lambda g, m, k: (g.A, (m, k)),
                outs=[ptg.Out(data=lambda g, m, k: (g.A, (m, k)))]),
        ])

    REDUCE = tp.task_class(
        "REDUCE", params=("n", "k"),
        space=lambda g: ((n, k) for k in range(g.NT)
                         for n in range(k + 1, g.NT)),
        affinity=lambda g, n, k: (g.A, (k, n)),
        priority=lambda g, n, k: 2 * (g.NT - k) ** 2 - n,
        flows=[
            # orders REDUCE's direct column-n reads after step k-1's
            # writers of that column
            ptg.FlowSpec(
                "G", ptg.CTL,
                ins=[ptg.In(src=("APPLY",
                                 lambda g, n, k: [(m, n, k - 1)
                                                  for m in range(k, g.MT)],
                                 "G"),
                            gather=True,
                            guard=lambda g, n, k: k > 0)]),
            ptg.FlowSpec(
                "V", ptg.READ,
                ins=[ptg.In(src=("PANEL", lambda g, n, k: (k,), "V"))]),
            ptg.FlowSpec(
                "Y", ptg.WRITE,
                outs=[ptg.Out(dst=("APPLY",
                                   lambda g, n, k: [(m, n, k)
                                                    for m in range(k, g.MT)],
                                   "Y"))]),
        ])

    APPLY = tp.task_class(
        "APPLY", params=("m", "n", "k"),
        space=lambda g: ((m, n, k) for k in range(g.NT)
                         for n in range(k + 1, g.NT)
                         for m in range(k, g.MT)),
        affinity=lambda g, m, n, k: (g.A, (m, n)),
        priority=lambda g, m, n, k: (g.NT - k) ** 2 - m - n,
        flows=[
            ptg.FlowSpec(
                "V", ptg.READ,
                ins=[ptg.In(src=("PANEL", lambda g, m, n, k: (k,), "V"))]),
            ptg.FlowSpec(
                "Y", ptg.READ,
                ins=[ptg.In(src=("REDUCE", lambda g, m, n, k: (n, k),
                                 "Y"))]),
            ptg.FlowSpec(
                "C", ptg.RW,
                tile=lambda g, m, n, k: (g.A, (m, n)),
                ins=[ptg.In(data=lambda g, m, n, k: (g.A, (m, n)),
                            guard=lambda g, m, n, k: k == 0),
                     ptg.In(src=("APPLY",
                                 lambda g, m, n, k: (m, n, k - 1), "C"),
                            guard=lambda g, m, n, k: k > 0)],
                outs=[
                    # unconditional write-back: the NEXT step's
                    # PANEL/REDUCE read this column straight from the
                    # collection (CTL-gather ordering)
                    ptg.Out(data=lambda g, m, n, k: (g.A, (m, n))),
                    ptg.Out(dst=("APPLY",
                                 lambda g, m, n, k: (m, n, k + 1), "C"),
                            guard=lambda g, m, n, k: k + 1 < n and
                            k + 1 <= m),
                    ptg.Out(dst=("PANEL", lambda g, m, n, k: (n,), "C"),
                            guard=lambda g, m, n, k: m == n and
                            k == n - 1),
                ]),
            ptg.FlowSpec(
                "G", ptg.CTL,
                outs=[
                    ptg.Out(dst=("PANEL", lambda g, m, n, k: (n,), "G"),
                            guard=lambda g, m, n, k: k == n - 1 and m > n),
                    ptg.Out(dst=("REDUCE",
                                 lambda g, m, n, k: (n, k + 1), "G"),
                            guard=lambda g, m, n, k: k + 1 < n and
                            m >= k + 1),
                ]),
        ])

    # the CTL-gather contract guarantees every gathered APPLY has
    # written its tile back (on its owner rank) before these bodies
    # run; local tiles read directly, remote tiles through the
    # CONCURRENT one-sided batch fetch (comm.engine.resolve_column_tiles
    # — the potrf_left pattern; same taskpool runs single-process
    # panel-fused AND multi-rank). No caching: unlike POTRF's final
    # factored columns, trailing tiles change every step.
    @PANEL.body(batchable=False)
    def panel_body(task, C, Vv):
        import numpy as np
        from ..comm.engine import resolve_column_tiles
        g = task.taskpool.g
        (k,) = task.locals
        col = [np.asarray(C, dtype=np.float32)]
        col += resolve_column_tiles(
            task, g.A, [(m, k) for m in range(k + 1, g.MT)])
        P = np.concatenate(col, axis=0)
        Qr, R = np.linalg.qr(P)                 # reduced: (mk, nb), (nb, nb)
        d = np.diagonal(Qr[:nb])
        s = np.where(d >= 0, -1.0, 1.0).astype(np.float32)
        Qr = Qr * s[None, :]
        R = R * s[:, None]
        V = Qr.copy()
        V[:nb] -= np.eye(nb, dtype=np.float32)
        X = np.eye(nb, dtype=np.float32) - Qr[:nb]
        Xinv = np.linalg.inv(X)
        dt = np.asarray(C).dtype
        return {"C": R.astype(dt), "V": (V, Xinv)}

    @ZEROV.body(batchable=False)
    def zerov_body(task, Cv):
        import numpy as np
        g = task.taskpool.g
        return {"C": np.zeros((g.A.mb, g.A.nb), dtype=g.A.dtype)}

    @REDUCE.body(batchable=False)
    def reduce_body(task, V, Yv):
        import numpy as np
        from ..comm.engine import resolve_column_tiles
        g = task.taskpool.g
        n, k = task.locals
        Vp, Xinv = V
        C = np.concatenate(
            resolve_column_tiles(
                task, g.A, [(m, n) for m in range(k, g.MT)]), axis=0)
        # Hᵀ·C = C − V·X⁻¹·(Vᵀ·C)  (H = I − V·X⁻ᵀ·Vᵀ)
        return {"Y": Xinv @ (Vp.T @ C)}

    @APPLY.body(batchable=False)
    def apply_body(task, V, Y, C):
        import numpy as np
        m, n, k = task.locals
        Vp, _Xinv = V
        nb_ = Y.shape[0]
        Vm = Vp[(m - k) * nb_:(m - k + 1) * nb_]
        out = np.asarray(C, dtype=np.float32) - Vm @ Y
        return {"C": out.astype(np.asarray(C).dtype)}

    tp.wave_fuser = _geqrf_hh_wave_fuser
    tp.requires_fuser = True     # PANEL/REDUCE bodies read the
    #                              collection directly (CTL-gather)
    return tp


def _geqrf_hh_wave_fuser(wave, geoms):
    """Lower one blocked-Householder QR wave to Aᵀ-dense ops
    (compiled.panels contract).

    Wave shapes per step k: [PANEL(k)] → panel_qr_tile on the contiguous
    panel slice, R + zeros written as one row-panel DUS, (Vᵀ, X⁻¹)
    stashed in the carry; [REDUCE(·,k)(+ZEROV(·,k))] → one tall matmul
    W = (Cᵀ·Vᵀᵀ)·X⁻¹ into the carry (the ZEROV writes were already
    folded into the panel DUS); [APPLY(·,·,k)] → Cᵀ − W·Vᵀ, one matmul
    + one trailing-slab DUS."""
    (geom,) = geoms.values()      # single-collection DAG
    import jax.numpy as jnp
    from ..ops.tile_kernels import (matmul_precision, panel_qr_tile)

    prec = matmul_precision()

    def mm(a, b):
        return jnp.matmul(a, b, preferred_element_type=jnp.float32,
                          precision=prec)

    names = sorted(g.tc.name for g in wave)
    mb, nb = geom.mb, geom.nb
    MT, NT = geom.mt, geom.nt

    if names == ["PANEL"]:
        (grp,) = wave
        if len(grp.tasks) != 1:
            return None
        (k,) = grp.tasks[0]

        def do_panel(st, k=k):
            D = st[geom.name]
            c = geom.cols(k)
            Pt = D[c, k * mb:MT * mb]
            Vt, Xinv, R = panel_qr_tile(Pt)
            st["_qr_Vt"], st["_qr_Xinv"] = Vt, Xinv
            row = jnp.concatenate(
                [R.T, jnp.zeros((nb, (MT - k - 1) * mb), R.dtype)],
                axis=1) if MT - k - 1 else R.T
            # one contiguous row-panel write: Rᵀ + the ZEROV zeros
            st[geom.name] = D.at[c, k * mb:].set(row.astype(D.dtype))
            return st

        return do_panel

    if "REDUCE" in names or names == ["ZEROV"]:
        if not set(names) <= {"REDUCE", "ZEROV"}:
            return None
        red = next((g for g in wave if g.tc.name == "REDUCE"), None)
        zer = next((g for g in wave if g.tc.name == "ZEROV"), None)
        ks = {t[-1] for g in (red, zer) if g is not None for t in g.tasks}
        if len(ks) != 1:
            return None
        k = ks.pop()
        if zer is not None and \
                sorted(zer.tasks) != [(m, k) for m in range(k + 1, MT)]:
            return None
        if red is None:
            return lambda st: st      # zeros already written by do_panel
        if sorted(red.tasks) != [(n, k) for n in range(k + 1, NT)]:
            return None

        def do_reduce(st, k=k):
            D = st[geom.name]
            Ct = D[(k + 1) * nb:, k * mb:MT * mb]
            W = mm(Ct, st["_qr_Vt"].T)
            st["_qr_W"] = mm(W, st["_qr_Xinv"].T)
            return st

        return do_reduce

    if names == ["APPLY"]:
        (grp,) = wave
        ks = {t[2] for t in grp.tasks}
        if len(ks) != 1:
            return None
        k = ks.pop()
        want = {(m, n) for n in range(k + 1, NT) for m in range(k, MT)}
        if {(m, n) for (m, n, _k) in grp.tasks} != want:
            return None

        def do_apply(st, k=k):
            D = st[geom.name]
            Ct = D[(k + 1) * nb:, k * mb:MT * mb]
            Vt = st.pop("_qr_Vt")
            W = st.pop("_qr_W")
            st.pop("_qr_Xinv", None)
            new = Ct - mm(W, Vt)
            st[geom.name] = D.at[(k + 1) * nb:, k * mb:MT * mb].set(
                new.astype(D.dtype))
            return st

        return do_apply

    return None
