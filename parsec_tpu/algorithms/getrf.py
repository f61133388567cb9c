"""Tiled LU factorizations as PTG taskpools: which builder is which LU.

* :func:`build_getrf`, :func:`build_getrf_left`: **no pivoting**
  (DPLASMA ``dgetrf_nopiv``). Valid for diagonally dominant or otherwise
  well-conditioned matrices only; a general matrix gives garbage or NaN.
* :func:`build_getrf_incpiv`: **incremental (pairwise) pivoting**
  (DPLASMA ``dgetrf_incpiv``, ``src/zgetrf_incpiv.jdf``): any
  nonsingular matrix; the factored form is A (U and the multipliers), L
  and IPIV, what ``dgetrs_incpiv`` goes on to read. This is the general
  LU the benchmark's ``dgetrf_incpiv_ptg_host`` runs.

Neither is LAPACK's partial pivoting over a whole panel (``zgetrf_1d``:
a task over a range of tiles), which no builder here gives yet.

Completes the DPLASMA-class dense-factorization trio next to
:mod:`~.potrf` and :mod:`~.geqrf`. The right-looking no-pivoting form
mirrors the classic dgetrf JDF:

    GETRF(k):     A[k,k] ← packed LU (unit-lower L, upper U)
    TRSM_U(k,n):  A[k,n] ← L[k,k]⁻¹·A[k,n]       (row panel, n > k)
    TRSM_L(m,k):  A[m,k] ← A[m,k]·U[k,k]⁻¹       (column panel, m > k)
    GEMM(m,n,k):  A[m,n] −= A[m,k]·A[k,n]

No pivoting: valid for the diagonally-dominant / well-conditioned
regime the accelerator tile-LU formulation targets (the reference
ships the same contract in its nopiv PTG examples; pivoted in-tile
fallback = ``jax.lax.linalg.lu`` at user level). On completion A holds
the packed factors (L unit-lower below the diagonal, U on/above).

:func:`build_getrf_left` is the panel-fused flagship form — the LU
analog of :func:`~.potrf.build_potrf_left`: UPDC/UPDR concentrate each
tile's updates at its step, ASAP leveling yields three waves per step
([UPDC(·,k)+UPDR(k,·)], [GETRF(k)], [TRSM_L(·,k)+TRSM_U(k,·)]), and the
wave fuser lowers each to one or two large matmuls over the Aᵀ store.
"""

from __future__ import annotations

from typing import Optional

from ..core.reshape import UPPER_TILE
from ..dsl import ptg
from ..data.matrix import TiledMatrix
from .geqrf import _row
from ..ops.tile_kernels import (PANEL_COMPILER_OPTIONS, gemm_full_tile,
                                gemm_tile, gessm_tile, getrf_incpiv_tile,
                                getrf_nopiv_tile, getrf_panel_tiles,
                                laswp_tiles, ssssm_tile, swptrsm_tiles,
                                trsm_lower_unit, trsm_upper_right,
                                tstrf_tile)
from ..utils import compile_cache, mca_param

# Compiled-path panel-TRSM kernel for the fused LU — the POTRF
# trsm_hook ported to BOTH LU solve stages (the structural delta vs the
# Cholesky fuser: LU pays TWO triangular panel solves per step where
# POTRF pays one). "gemm" factors the diagonal tile and derives L⁻¹/U⁻¹
# in ONE matmul-rich Schur recursion (ops.lu_inv_tile), so the column
# panel (·U⁻¹, via U⁻ᵀ on the transposed store) and row panel (L⁻¹·)
# each run as one MXU matmul; it squares the factors' condition-number
# contribution, same trade as POTRF's knob. "inherit" (default) follows
# potrf.trsm_hook so existing callers that set the POTRF knob keep
# getting the coupled behavior shipped through round 5.
mca_param.register("getrf.trsm_hook", "inherit",
                   help="compiled-path panel-TRSM kernel for the fused "
                        "LU: solve (exact wide triangular solves, "
                        "reference numerics) | gemm (diagonal-inversion "
                        "MXU matmuls via lu_inv_tile; squares the "
                        "factors' condition-number contribution) | "
                        "inherit (follow potrf.trsm_hook)")
compile_cache.register_trace_knob("getrf.trsm_hook")


def _trsm_inv_mode() -> bool:
    hook = str(mca_param.get("getrf.trsm_hook", "inherit"))
    if hook == "inherit":
        hook = str(mca_param.get("potrf.trsm_hook", "solve"))
    return hook == "gemm"


def _check(A: TiledMatrix) -> int:
    if A.mt != A.nt:
        raise ValueError("GETRF needs a square tile grid")
    if A.mb != A.nb:
        raise ValueError("GETRF needs square tiles (mb == nb)")
    return A.nt


def build_getrf(A: TiledMatrix) -> ptg.Taskpool:
    """Right-looking tiled LU WITHOUT PIVOTING (``dgetrf_nopiv``: the
    dgetrf JDF shape). For a general matrix use
    :func:`build_getrf_incpiv`."""
    NT = _check(A)
    tp = ptg.Taskpool("getrf", A=A, NT=NT)

    GETRF = tp.task_class(
        "GETRF", params=("k",),
        space=lambda g: ((k,) for k in range(g.NT)),
        affinity=lambda g, k: (g.A, (k, k)),
        priority=lambda g, k: 3 * (g.NT - k) ** 2,
        flows=[ptg.FlowSpec(
            "T", ptg.RW,
            tile=lambda g, k: (g.A, (k, k)),
            ins=[ptg.In(data=lambda g, k: (g.A, (k, k)),
                        guard=lambda g, k: k == 0),
                 ptg.In(src=("GEMM", lambda g, k: (k, k, k - 1), "C"),
                        guard=lambda g, k: k > 0)],
            outs=[ptg.Out(dst=("TRSM_L",
                               lambda g, k: [(m, k)
                                             for m in range(k + 1, g.NT)],
                               "T")),
                  ptg.Out(dst=("TRSM_U",
                               lambda g, k: [(k, n)
                                             for n in range(k + 1, g.NT)],
                               "T")),
                  ptg.Out(data=lambda g, k: (g.A, (k, k)))])])

    TRSM_L = tp.task_class(
        "TRSM_L", params=("m", "k"),
        space=lambda g: ((m, k) for k in range(g.NT)
                         for m in range(k + 1, g.NT)),
        affinity=lambda g, m, k: (g.A, (m, k)),
        priority=lambda g, m, k: 2 * (g.NT - k) ** 2 - m,
        flows=[
            ptg.FlowSpec(
                "T", ptg.READ,
                tile=lambda g, m, k: (g.A, (k, k)),
                ins=[ptg.In(src=("GETRF", lambda g, m, k: (k,), "T"))]),
            ptg.FlowSpec(
                "C", ptg.RW,
                tile=lambda g, m, k: (g.A, (m, k)),
                ins=[ptg.In(data=lambda g, m, k: (g.A, (m, k)),
                            guard=lambda g, m, k: k == 0),
                     ptg.In(src=("GEMM", lambda g, m, k: (m, k, k - 1),
                                 "C"),
                            guard=lambda g, m, k: k > 0)],
                outs=[ptg.Out(dst=("GEMM",
                                   lambda g, m, k: [(m, n, k)
                                                    for n in
                                                    range(k + 1, g.NT)],
                                   "L")),
                      ptg.Out(data=lambda g, m, k: (g.A, (m, k)))])])

    TRSM_U = tp.task_class(
        "TRSM_U", params=("k", "n"),
        space=lambda g: ((k, n) for k in range(g.NT)
                         for n in range(k + 1, g.NT)),
        affinity=lambda g, k, n: (g.A, (k, n)),
        priority=lambda g, k, n: 2 * (g.NT - k) ** 2 - n,
        flows=[
            ptg.FlowSpec(
                "T", ptg.READ,
                tile=lambda g, k, n: (g.A, (k, k)),
                ins=[ptg.In(src=("GETRF", lambda g, k, n: (k,), "T"))]),
            ptg.FlowSpec(
                "C", ptg.RW,
                tile=lambda g, k, n: (g.A, (k, n)),
                ins=[ptg.In(data=lambda g, k, n: (g.A, (k, n)),
                            guard=lambda g, k, n: k == 0),
                     ptg.In(src=("GEMM", lambda g, k, n: (k, n, k - 1),
                                 "C"),
                            guard=lambda g, k, n: k > 0)],
                outs=[ptg.Out(dst=("GEMM",
                                   lambda g, k, n: [(m, n, k)
                                                    for m in
                                                    range(k + 1, g.NT)],
                                   "U")),
                      ptg.Out(data=lambda g, k, n: (g.A, (k, n)))])])

    GEMM = tp.task_class(
        "GEMM", params=("m", "n", "k"),
        space=lambda g: ((m, n, k) for k in range(g.NT)
                         for m in range(k + 1, g.NT)
                         for n in range(k + 1, g.NT)),
        affinity=lambda g, m, n, k: (g.A, (m, n)),
        priority=lambda g, m, n, k: (g.NT - k) ** 2 - m - n,
        flows=[
            ptg.FlowSpec(
                "L", ptg.READ,
                tile=lambda g, m, n, k: (g.A, (m, k)),
                ins=[ptg.In(src=("TRSM_L", lambda g, m, n, k: (m, k),
                                 "C"))]),
            ptg.FlowSpec(
                "U", ptg.READ,
                tile=lambda g, m, n, k: (g.A, (k, n)),
                ins=[ptg.In(src=("TRSM_U", lambda g, m, n, k: (k, n),
                                 "C"))]),
            ptg.FlowSpec(
                "C", ptg.RW,
                tile=lambda g, m, n, k: (g.A, (m, n)),
                ins=[ptg.In(data=lambda g, m, n, k: (g.A, (m, n)),
                            guard=lambda g, m, n, k: k == 0),
                     ptg.In(src=("GEMM",
                                 lambda g, m, n, k: (m, n, k - 1), "C"),
                            guard=lambda g, m, n, k: k > 0)],
                outs=[
                    ptg.Out(dst=("GEMM",
                                 lambda g, m, n, k: (m, n, k + 1), "C"),
                            guard=lambda g, m, n, k:
                            k + 1 < min(m, n)),
                    ptg.Out(dst=("GETRF", lambda g, m, n, k: (k + 1,),
                                 "T"),
                            guard=lambda g, m, n, k: m == k + 1 and
                            n == k + 1),
                    ptg.Out(dst=("TRSM_L", lambda g, m, n, k: (m, k + 1),
                                 "C"),
                            guard=lambda g, m, n, k: n == k + 1 and
                            m > k + 1),
                    ptg.Out(dst=("TRSM_U", lambda g, m, n, k: (k + 1, n),
                                 "C"),
                            guard=lambda g, m, n, k: m == k + 1 and
                            n > k + 1),
                ])])

    @GETRF.body
    def getrf_body(task, T):
        return getrf_nopiv_tile(T)

    @TRSM_L.body
    def trsm_l_body(task, T, C):
        return {"C": trsm_upper_right(T, C)}

    @TRSM_U.body
    def trsm_u_body(task, T, C):
        return {"C": trsm_lower_unit(T, C)}

    @GEMM.body
    def gemm_body(task, L, U, C):
        return gemm_tile(C, L, U, alpha=-1.0, beta=1.0)

    return tp


def build_getrf_left(A: TiledMatrix) -> ptg.Taskpool:
    """Left-looking tiled LU WITHOUT PIVOTING, the panel-fused flagship form (the
    :func:`~.potrf.build_potrf_left` analog). Each column-panel tile
    (UPDC) and row-panel tile (UPDR) receives ALL its k' < k
    contributions in one task that CTL-gathers its producer TRSMs and
    resolves their tiles with the direct-memory gathered-operand
    pattern (local reads / one-sided batched fetches) — the same
    taskpool runs single-process panel-fused AND multi-rank."""
    NT = _check(A)
    tp = ptg.Taskpool("getrf_left", A=A, NT=NT)

    # producers gathered by UPDC(m, k): column k's operands L[m, j<k]
    # and U[j<k, k]; by UPDR(k, n): L[k, j<k] and U[j<k, n]
    UPDC = tp.task_class(
        "UPDC", params=("m", "k"),
        space=lambda g: ((m, k) for k in range(1, g.NT)
                         for m in range(k, g.NT)),
        affinity=lambda g, m, k: (g.A, (m, k)),
        priority=lambda g, m, k: 2 * (g.NT - k) ** 2 - m + 1,
        flows=[
            ptg.FlowSpec(
                "GL", ptg.CTL,
                ins=[ptg.In(src=("TRSM_L",
                                 lambda g, m, k: [(m, j)
                                                  for j in range(k)],
                                 "G"),
                            gather=True)]),
            ptg.FlowSpec(
                "GU", ptg.CTL,
                ins=[ptg.In(src=("TRSM_U",
                                 lambda g, m, k: [(j, k)
                                                  for j in range(k)],
                                 "G"),
                            gather=True)]),
            ptg.FlowSpec(
                "C", ptg.RW,
                tile=lambda g, m, k: (g.A, (m, k)),
                ins=[ptg.In(data=lambda g, m, k: (g.A, (m, k)))],
                outs=[ptg.Out(dst=("GETRF", lambda g, m, k: (k,), "T"),
                              guard=lambda g, m, k: m == k),
                      ptg.Out(dst=("TRSM_L", lambda g, m, k: (m, k), "C"),
                              guard=lambda g, m, k: m > k)])])

    UPDR = tp.task_class(
        "UPDR", params=("k", "n"),
        space=lambda g: ((k, n) for k in range(1, g.NT)
                         for n in range(k + 1, g.NT)),
        affinity=lambda g, k, n: (g.A, (k, n)),
        priority=lambda g, k, n: 2 * (g.NT - k) ** 2 - n + 1,
        flows=[
            ptg.FlowSpec(
                "GL", ptg.CTL,
                ins=[ptg.In(src=("TRSM_L",
                                 lambda g, k, n: [(k, j)
                                                  for j in range(k)],
                                 "G"),
                            gather=True)]),
            ptg.FlowSpec(
                "GU", ptg.CTL,
                ins=[ptg.In(src=("TRSM_U",
                                 lambda g, k, n: [(j, n)
                                                  for j in range(k)],
                                 "G"),
                            gather=True)]),
            ptg.FlowSpec(
                "C", ptg.RW,
                tile=lambda g, k, n: (g.A, (k, n)),
                ins=[ptg.In(data=lambda g, k, n: (g.A, (k, n)))],
                outs=[ptg.Out(dst=("TRSM_U", lambda g, k, n: (k, n),
                                   "C"))])])

    GETRF = tp.task_class(
        "GETRF", params=("k",),
        space=lambda g: ((k,) for k in range(g.NT)),
        affinity=lambda g, k: (g.A, (k, k)),
        priority=lambda g, k: 3 * (g.NT - k) ** 2,
        flows=[ptg.FlowSpec(
            "T", ptg.RW,
            tile=lambda g, k: (g.A, (k, k)),
            ins=[ptg.In(data=lambda g, k: (g.A, (k, k)),
                        guard=lambda g, k: k == 0),
                 ptg.In(src=("UPDC", lambda g, k: (k, k), "C"),
                        guard=lambda g, k: k > 0)],
            outs=[ptg.Out(dst=("TRSM_L",
                               lambda g, k: [(m, k)
                                             for m in range(k + 1, g.NT)],
                               "T")),
                  ptg.Out(dst=("TRSM_U",
                               lambda g, k: [(k, n)
                                             for n in range(k + 1, g.NT)],
                               "T")),
                  ptg.Out(data=lambda g, k: (g.A, (k, k)))])])

    TRSM_L = tp.task_class(
        "TRSM_L", params=("m", "k"),
        space=lambda g: ((m, k) for k in range(g.NT)
                         for m in range(k + 1, g.NT)),
        affinity=lambda g, m, k: (g.A, (m, k)),
        priority=lambda g, m, k: 2 * (g.NT - k) ** 2 - m,
        flows=[
            ptg.FlowSpec(
                "T", ptg.READ,
                tile=lambda g, m, k: (g.A, (k, k)),
                ins=[ptg.In(src=("GETRF", lambda g, m, k: (k,), "T"))]),
            ptg.FlowSpec(
                "C", ptg.RW,
                tile=lambda g, m, k: (g.A, (m, k)),
                ins=[ptg.In(data=lambda g, m, k: (g.A, (m, k)),
                            guard=lambda g, m, k: k == 0),
                     ptg.In(src=("UPDC", lambda g, m, k: (m, k), "C"),
                            guard=lambda g, m, k: k > 0)],
                outs=[ptg.Out(data=lambda g, m, k: (g.A, (m, k)))]),
            ptg.FlowSpec(
                "G", ptg.CTL,
                outs=[ptg.Out(
                    dst=("UPDC",
                         lambda g, m, k: [(m, kk)
                                          for kk in range(k + 1,
                                                          min(m, g.NT - 1)
                                                          + 1)],
                         "GL")),
                    ptg.Out(
                    dst=("UPDR",
                         lambda g, m, k: [(m, n)
                                          for n in range(m + 1, g.NT)],
                         "GL"))])])

    TRSM_U = tp.task_class(
        "TRSM_U", params=("k", "n"),
        space=lambda g: ((k, n) for k in range(g.NT)
                         for n in range(k + 1, g.NT)),
        affinity=lambda g, k, n: (g.A, (k, n)),
        priority=lambda g, k, n: 2 * (g.NT - k) ** 2 - n,
        flows=[
            ptg.FlowSpec(
                "T", ptg.READ,
                tile=lambda g, k, n: (g.A, (k, k)),
                ins=[ptg.In(src=("GETRF", lambda g, k, n: (k,), "T"))]),
            ptg.FlowSpec(
                "C", ptg.RW,
                tile=lambda g, k, n: (g.A, (k, n)),
                ins=[ptg.In(data=lambda g, k, n: (g.A, (k, n)),
                            guard=lambda g, k, n: k == 0),
                     ptg.In(src=("UPDR", lambda g, k, n: (k, n), "C"),
                            guard=lambda g, k, n: k > 0)],
                outs=[ptg.Out(data=lambda g, k, n: (g.A, (k, n)))]),
            ptg.FlowSpec(
                "G", ptg.CTL,
                outs=[ptg.Out(
                    dst=("UPDR",
                         lambda g, k, n: [(kk, n)
                                          for kk in range(k + 1, n)],
                         "GU")),
                    ptg.Out(
                    dst=("UPDC",
                         lambda g, k, n: [(m, n)
                                          for m in range(n, g.NT)],
                         "GU"))])])

    @UPDC.body(batchable=False)
    def updc_body(task, C):
        import numpy as np
        from ..comm.engine import resolve_column_tiles
        g = task.taskpool.g
        m, k = task.locals
        Ls = resolve_column_tiles(task, g.A, [(m, j) for j in range(k)])
        Us = resolve_column_tiles(task, g.A, [(j, k) for j in range(k)])
        acc = np.asarray(C, dtype=np.float32).copy()
        for Lj, Uj in zip(Ls, Us):
            acc -= Lj @ Uj
        return acc.astype(np.asarray(C).dtype)

    @UPDR.body(batchable=False)
    def updr_body(task, C):
        import numpy as np
        from ..comm.engine import resolve_column_tiles
        g = task.taskpool.g
        k, n = task.locals
        Ls = resolve_column_tiles(task, g.A, [(k, j) for j in range(k)])
        Us = resolve_column_tiles(task, g.A, [(j, n) for j in range(k)])
        acc = np.asarray(C, dtype=np.float32).copy()
        for Lj, Uj in zip(Ls, Us):
            acc -= Lj @ Uj
        return acc.astype(np.asarray(C).dtype)

    @GETRF.body
    def getrf_body(task, T):
        return getrf_nopiv_tile(T)

    @TRSM_L.body(batchable=False)
    def trsm_l_body(task, T, C):
        return {"C": trsm_upper_right(T, C)}

    @TRSM_U.body(batchable=False)
    def trsm_u_body(task, T, C):
        return {"C": trsm_lower_unit(T, C)}

    tp.wave_fuser = _getrf_left_wave_fuser
    tp.requires_fuser = True     # UPDC/UPDR bodies resolve gathered
    #                              operands outside per-tile flows
    return tp


def _getrf_left_wave_fuser(wave, geoms):
    """Lower one left-looking LU wave to Aᵀ-dense ops (compiled.panels
    contract). Wave shapes per step k:
    [UPDC(·,k)+UPDR(k,·)] → two large matmuls into the carry;
    [GETRF(k)] → in-tile packed LU (Schur recursion);
    [TRSM_L(·,k)+TRSM_U(k,·)] → two triangular applies + two DUS.

    Storage: TWO stores, each with a SINGLE row-panel DUS chain —
    the L/diag panels land in the collection's Aᵀ store
    (write [c, k·mb:], exactly POTRF's shape) and the U row panels in
    an A-layout carry ``st["_us"]`` (write [k·nb:(k+1)·nb, (k+1)·mb:]).
    Interleaving both chains on ONE array defeats XLA's in-place DUS
    scheduling and costs a full store copy per step — measured 7 ms/step
    (= 168 ms of the 314 ms round-3 total) at N=24576 on a v5e; the
    two-store split is ~0 ms/step. The final GETRF wave merges the U
    store back with one transpose+select (us.T lands exactly on the
    Aᵀ-store's U-tile region), so the executor's output contract (one
    packed-LU array per collection) is unchanged.

    Round-5 structure findings (N=32768, NB=1024, captured):
    row panels are produced in A-LAYOUT (see do_update) so no
    two-large-dims transpose appears in the graph; measured floor with
    the sequential in-tile kernels stubbed is ~65 TF/s (run 0.358 s),
    of which ~147 ms is slice/DUS/merge structure — the matmuls run at
    ~73% MXU efficiency on their share. Variants measured SLOWER and
    reverted: rank-2 base elimination (tile_kernels._lu_base note),
    splitting the concat into two DUS writes (54.7 vs 56.9-59.7),
    lax.dot_general axis-0 contractions (46.0).

    Round-6 rework (getrf.trsm_hook=gemm): the sequential per-step tail
    was the in-tile LU (two triangular solves per recursion level) PLUS
    two standalone nb-sized tri_inv_tile recursions in the TRSM wave.
    ``lu_inv_tile`` folds all three into one Schur recursion whose
    panel solves are matmuls against the child inverses — triangular
    solves survive only at the ≤64 base case — and the GETRF wave
    stashes L⁻¹/U⁻¹ in the carry so the TRSM wave is two pure MXU
    matmuls (exactly POTRF's stash-the-inverse shape)."""
    (geom,) = geoms.values()
    import jax
    import jax.numpy as jnp
    from ..ops.tile_kernels import (getrf_nopiv_tile, lu_inv_tile,
                                    lu_split, matmul_precision,
                                    tri_inv_tile)

    prec = matmul_precision()

    def mm(a, b):
        return jnp.matmul(a, b, preferred_element_type=jnp.float32,
                          precision=prec)

    names = sorted(g.tc.name for g in wave)
    mb, nb = geom.mb, geom.nb
    MT, NT = geom.mt, geom.nt
    inv_mode = _trsm_inv_mode()

    if names in (["UPDC"], ["UPDC", "UPDR"]):
        updc = next(g for g in wave if g.tc.name == "UPDC")
        ks = {t[1] for t in updc.tasks}
        if len(ks) != 1:
            return None
        k = ks.pop()
        if sorted(updc.tasks) != [(m, k) for m in range(k, MT)]:
            return None
        updr = next((g for g in wave if g.tc.name == "UPDR"), None)
        if updr is not None and sorted(updr.tasks) != \
                [(k, n) for n in range(k + 1, NT)]:
            return None

        def do_update(st, k=k):
            D = st[geom.name]
            us = st["_us"]       # exists: TRSM(0) precedes every update
            r0 = k * nb
            # column panel (Aᵀ rows = block-col k): Uᵀ[:k,k]·Lᵀ[k:,:k];
            # U factors read from the A-layout U store (transpose folds
            # into the dot), L factors from the Aᵀ collection store
            Ut = us[0:k * nb, k * mb:(k + 1) * mb].T   # (nb, k*nb)
            Lt = D[0:k * nb, k * mb:]             # (k*nb, mk)
            st["_lu_col"] = D[r0:r0 + nb, k * mb:] - mm(Ut, Lt)
            if k + 1 < NT:
                # row panel in A-LAYOUT (nb, T): A[k,j>k] - L[k,:k]·U[:k,j>k].
                # Round-4 computed it Aᵀ-oriented via us[...].T with BOTH
                # dims large — XLA materializes that transpose, ~1 GB-
                # class copies per step (~46 GB over the run, measured
                # +55 ms). A-layout needs only (x, nb) transposes (the
                # L row read and the residual base, ≤130 MB each) and
                # reads the U store straight.
                Lrow = D[0:k * nb, k * mb:(k + 1) * mb].T    # (nb, k*nb)
                Ublk = us[0:k * nb, (k + 1) * mb:]           # (k*nb, T)
                baseA = D[(k + 1) * nb:, k * mb:(k + 1) * mb].T  # (nb, T)
                st["_lu_rowA"] = baseA - mm(Lrow, Ublk)
            return st

        return do_update

    if names == ["GETRF"]:
        (grp,) = wave
        if len(grp.tasks) != 1:
            return None
        (k,) = grp.tasks[0]

        def do_getrf(st, k=k, last=(k == NT - 1)):
            D = st[geom.name]
            c = slice(k * nb, (k + 1) * nb)
            colk = st.pop("_lu_col", None)
            diag = colk[:, :nb].T if colk is not None \
                else D[c, k * mb:(k + 1) * mb].T
            if inv_mode and not last:
                # factor + both inverses in ONE matmul-rich recursion;
                # the TRSM wave consumes the stashed inverses as plain
                # matmuls (POTRF's _potrf_inv carry, for both stages).
                # The last step has no TRSM wave — plain factor.
                LU, Linv, Uinv = lu_inv_tile(diag)
                st["_lu_Linv"] = Linv
                st["_lu_Uinv"] = Uinv
            else:
                LU = getrf_nopiv_tile(diag)
            st["_lu_T"] = LU
            if last:
                D = D.at[c, k * mb:].set(LU.T)
                us = st.pop("_us", None)
                if us is not None:
                    # fold the U store back into the collection store:
                    # us.T is Uᵀ in Aᵀ layout, i.e. every U tile (k, j>k)
                    # already sits at its Aᵀ-store position — one
                    # transpose+select instead of NT strided DUS
                    bi = jnp.arange(D.shape[0]) // nb
                    bj = jnp.arange(D.shape[1]) // mb
                    D = jnp.where(bi[:, None] > bj[None, :], us.T, D)
                st[geom.name] = D
            else:
                if colk is not None:
                    st["_lu_col_rest"] = colk[:, nb:]
            return st

        return do_getrf

    if names in (["TRSM_L"], ["TRSM_L", "TRSM_U"]):
        tl = next(g for g in wave if g.tc.name == "TRSM_L")
        ks = {t[1] for t in tl.tasks}
        if len(ks) != 1:
            return None
        k = ks.pop()
        if sorted(tl.tasks) != [(m, k) for m in range(k + 1, MT)]:
            return None
        tu = next((g for g in wave if g.tc.name == "TRSM_U"), None)
        if tu is not None and sorted(tu.tasks) != \
                [(k, n) for n in range(k + 1, NT)]:
            return None

        def do_trsm(st, k=k):
            D = st[geom.name]
            c = slice(k * nb, (k + 1) * nb)
            LU = st.pop("_lu_T", None)
            if LU is None:
                LU = D[c, k * mb:(k + 1) * mb].T
            col = st.pop("_lu_col_rest", None)
            if col is None:       # k == 0: no update wave preceded
                col = D[c, (k + 1) * mb:]
            rowA = st.pop("_lu_rowA", None)       # A-layout (nb, T)
            if rowA is None:
                rowA = D[(k + 1) * nb:, k * mb:(k + 1) * mb].T
            if inv_mode:
                # MAGMA-style: both panel solves are MXU matmuls
                # against the inverses the GETRF wave stashed (derived
                # inside the factorization recursion — no standalone
                # tri_inv_tile passes)
                Linv = st.pop("_lu_Linv", None)
                Uinv = st.pop("_lu_Uinv", None)
                if Linv is None or Uinv is None:
                    # robustness: recompute from the packed factor
                    L, U = lu_split(LU)
                    Linv = tri_inv_tile(L) if Linv is None else Linv
                    Uinv = tri_inv_tile(U.T).T if Uinv is None else Uinv
                solved_col = mm(Uinv.T, col)       # (U^-T)·colᵀ
                solved_rowA = mm(Linv, rowA)       # L^-1·A[k, j>k]
            else:
                L, U = lu_split(LU)
                solved_col = jax.lax.linalg.triangular_solve(
                    U, col, left_side=True, lower=False,
                    transpose_a=True)
                solved_rowA = jax.lax.linalg.triangular_solve(
                    L, rowA, left_side=True, lower=True,
                    unit_diagonal=True)
            # panel writes, ONE DUS chain per store: L/diag row panel
            # into the Aᵀ collection store, U row panel into the
            # A-layout U carry (two chains on one array would cost a
            # full store copy per step — see the fuser docstring).
            # solved_rowA is ALREADY A-layout — no transpose at write.
            # concat-then-one-DUS beats two adjacent DUS's here
            # (measured 56.9 vs 54.7 TF/s at N=32768: the second DUS
            # breaks XLA's in-place chain)
            D = D.at[c, k * mb:].set(
                jnp.concatenate([LU.T, solved_col.astype(D.dtype)],
                                axis=1))
            us = st.get("_us")
            if us is None:
                us = jnp.zeros_like(D)
            st["_us"] = us.at[k * nb:(k + 1) * nb, (k + 1) * mb:].set(
                solved_rowA.astype(D.dtype))
            st[geom.name] = D
            return st

        return do_trsm

    return None


def getrf_flops(n: int) -> float:
    """Useful FLOPs of an n×n LU (LAPACK working note 41's count for
    ``dgetrf``: n³/3 − n/3 multiplications, n³/3 − n²/2 + n/6
    additions), whichever builder ran it."""
    return 2.0 * n ** 3 / 3.0 - n ** 2 / 2.0 - n / 6.0


# ---- incremental pivoting (DPLASMA dgetrf_incpiv) -----------------------

def getrf_l_collection(A: TiledMatrix, ib: int) -> TiledMatrix:
    """descL of ``A``: a tile of ``ib`` x nb beside every tile of A under
    the diagonal, the blocks' L11 of its TSTRF side by side."""
    if A.nb % ib:
        raise ValueError(f"ib={ib} does not divide nb={A.nb}")
    return TiledMatrix(A.mt * ib, A.nt * A.nb, ib, A.nb, dist=A.dist,
                       dtype=A.dtype, name=f"{A.name}_L")


def getrf_ipiv_collection(A: TiledMatrix) -> TiledMatrix:
    """descIPIV of ``A``: nb int32 beside every tile of A on and under
    the diagonal (a 1 x nb tile; ops/tile_kernels.py says what a diagonal
    tile's and a pair's hold)."""
    import numpy as np
    return TiledMatrix(A.mt, A.nt * A.nb, 1, A.nb, dist=A.dist,
                       dtype=np.int32, name=f"{A.name}_IPIV")


def _over(old, new):
    """``new``, in the buffer ``old`` lies in: a flow whose tile is
    written without being read still names its input, so that a chip
    module can give the tile's buffer to the program (``Chore.donates``)
    and a launch holds nothing new."""
    import jax.numpy as jnp
    return jnp.where(jnp.zeros((), bool), old, new)


def build_getrf_incpiv(A: TiledMatrix, L: Optional[TiledMatrix] = None,
                       IPIV: Optional[TiledMatrix] = None,
                       ib: Optional[int] = None) -> ptg.Taskpool:
    """Tile LU by incremental pivoting (DPLASMA ``dgetrf_incpiv``,
    ``zgetrf_incpiv.jdf``'s four classes) over ``A`` (square, nb x nb
    tiles), ``L`` (ib x nb tiles) and ``IPIV`` (int32), the two made here
    from ``A`` and ``ib`` where none is given (the pool's ``g.L``,
    ``g.IPIV``), k = 0..NT-1, m, n = k+1..NT-1:

        GETRF(k):     P_k A(k,k) = L_kk U_kk, pivots inside the tile;
                      writes A(k,k) and IPIV(k,k)
        GESSM(k,n):   A(k,n) <- L_kk^-1 P_k A(k,n)
        TSTRF(k,m):   the pivoted LU of the stack [U; A(m,k)], U the
                      upper triangle of tile (k,k) as the TSTRF before it
                      left it, ib columns at a time; writes U, the
                      multipliers over A(m,k), L(m,k) and IPIV(m,k), and
                      hands its SSSSMs the blocks' L11^-1 (W, a value)
        SSSSM(k,m,n): [A(k,n); A(m,n)] <- TSTRF(k,m)'s interchanges and
                      eliminations applied, block by block

    On completion U is the upper triangle of A, and the transformation
    that took A to it is stored in task order in A's lower part, L and
    IPIV (``benchmark/configs/dgetrf_incpiv_ptg_host_reference.py
    apply_l`` reads it back).

    **One tile, two regions, two lives.** Upstream types the dependencies
    of GETRF's tile: its lower part (``[type = LOWER_TILE]``) goes to the
    row's GESSMs, its upper part (``[type = UPPER_TILE]``) down the
    column's chain of TSTRFs, each of which rewrites it, and both end in
    A(k,k). Here U travels as a value of its own from GETRF through the
    chain, each TSTRF updating it in the buffer it lies in, and the last
    one's write-back merges it into the tile A(k,k) holds, in place
    (``Out(region=UPPER_TILE)``, core/reshape.py): the tile is never made
    twice. That merge deletes the array the GESSMs were handed, so the
    last TSTRF waits for them (a CTL gather; they are long gone by then).

    The factorization runs in the storage of A, L and IPIV: every class
    writes its results to their tiles, and on a chip module every tile
    is updated in the buffer it lies in (``Chore.donates``; L's and
    IPIV's tiles are written, not read, and still name their input for
    that). Two values are made beside the tiles: GETRF's U, merged into
    its tile at the chain's end, and TSTRF's W (ib x nb: the blocks'
    L11^-1, which 1240 SSSSMs of a 16 x 16 grid would else each work out
    from L for themselves), gone with the pair's last SSSSM. Priorities are build_geqrf's, its
    twin in shape.
    """
    NT = _check(A)
    if L is None:
        L = getrf_l_collection(A, ib or A.nb)
    if IPIV is None:
        IPIV = getrf_ipiv_collection(A)
    ib = L.mb
    if (L.mt, L.nt, L.nb) != (NT, NT, A.nb) or A.nb % ib or \
            (IPIV.mt, IPIV.nt, IPIV.mb, IPIV.nb) != (NT, NT, 1, A.nb):
        raise ValueError("L needs a tile of ib x nb and IPIV one of 1 x nb "
                         "per tile of A, ib a divisor of nb")
    tp = ptg.Taskpool("getrf_incpiv", A=A, L=L, IPIV=IPIV, NT=NT)

    def kept(dc, key_fn):
        """The write-back every written tile ends with (in place)."""
        return ptg.Out(data=lambda g, *p: (getattr(g, dc), key_fn(*p)))

    def a_in(cls_params):
        """A tile of A as step k finds it: the matrix's at k = 0, else
        what SSSSM(k-1, ·, ·) left."""
        return [ptg.In(data=lambda g, *p: (g.A, cls_params(*p)[1:]),
                       guard=lambda g, *p: cls_params(*p)[0] == 0),
                ptg.In(src=("SSSSM",
                            lambda g, *p: (cls_params(*p)[0] - 1,
                                           *cls_params(*p)[1:]), "A2"),
                       guard=lambda g, *p: cls_params(*p)[0] > 0)]

    def row(k, g):
        return range(k + 1, g.NT)

    GETRF = tp.task_class(
        "GETRF", params=("k",),
        space=lambda g: ((k,) for k in range(g.NT)),
        affinity=lambda g, k: (g.A, (k, k)),
        priority=lambda g, k: 4 * (g.NT - k) ** 2,
        flows=[
            ptg.FlowSpec(
                "A", ptg.RW,
                tile=lambda g, k: (g.A, (k, k)),
                ins=a_in(lambda k: (k, k, k)),
                outs=[ptg.Out(dst=("GESSM",
                                   lambda g, k: [(k, n) for n in row(k, g)],
                                   "L")),
                      kept("A", lambda k: (k, k))]),
            ptg.FlowSpec(
                "IPIV", ptg.RW,
                tile=lambda g, k: (g.IPIV, (k, k)),
                ins=[ptg.In(data=lambda g, k: (g.IPIV, (k, k)))],
                outs=[ptg.Out(dst=("GESSM",
                                   lambda g, k: [(k, n) for n in row(k, g)],
                                   "P")),
                      kept("IPIV", lambda k: (k, k))]),
            # the upper triangle on its way down the column: a value,
            # no tile of its own
            ptg.FlowSpec(
                "U", ptg.WRITE,
                outs=[ptg.Out(dst=("TSTRF", lambda g, k: (k, k + 1), "U"),
                              guard=lambda g, k: k + 1 < g.NT)]),
        ])

    GESSM = tp.task_class(
        "GESSM", params=("k", "n"),
        space=lambda g: ((k, n) for k in range(g.NT) for n in row(k, g)),
        affinity=lambda g, k, n: (g.A, (k, n)),
        priority=lambda g, k, n: 3 * (g.NT - k) ** 2 - n,
        flows=[
            ptg.FlowSpec(
                "L", ptg.READ,
                tile=lambda g, k, n: (g.A, (k, k)),
                ins=[ptg.In(src=("GETRF", lambda g, k, n: (k,), "A"))]),
            ptg.FlowSpec(
                "P", ptg.READ,
                tile=lambda g, k, n: (g.IPIV, (k, k)),
                ins=[ptg.In(src=("GETRF", lambda g, k, n: (k,), "IPIV"))]),
            ptg.FlowSpec(
                "C", ptg.RW,
                tile=lambda g, k, n: (g.A, (k, n)),
                ins=a_in(lambda k, n: (k, k, n)),
                outs=[ptg.Out(dst=("SSSSM",
                                   lambda g, k, n: (k, k + 1, n), "A1")),
                      kept("A", lambda k, n: (k, n))]),
            # it has read the tile whose upper part the column's last
            # TSTRF merges over
            ptg.FlowSpec(
                "G", ptg.CTL,
                outs=[ptg.Out(dst=("TSTRF",
                                   lambda g, k, n: (k, g.NT - 1), "G"))]),
        ])

    TSTRF = tp.task_class(
        "TSTRF", params=("k", "m"),
        space=lambda g: ((k, m) for k in range(g.NT) for m in row(k, g)),
        affinity=lambda g, k, m: (g.A, (m, k)),
        priority=lambda g, k, m: 3 * (g.NT - k) ** 2 - m,
        flows=[
            ptg.FlowSpec(
                "U", ptg.RW,
                ins=[ptg.In(src=("GETRF", lambda g, k, m: (k,), "U"),
                            guard=lambda g, k, m: m == k + 1),
                     ptg.In(src=("TSTRF", lambda g, k, m: (k, m - 1), "U"),
                            guard=lambda g, k, m: m > k + 1)],
                outs=[ptg.Out(dst=("TSTRF", lambda g, k, m: (k, m + 1), "U"),
                              guard=lambda g, k, m: m + 1 < g.NT),
                      ptg.Out(data=lambda g, k, m: (g.A, (k, k)),
                              guard=lambda g, k, m: m + 1 == g.NT,
                              region=UPPER_TILE)]),
            # A(m,k) goes in, the multipliers come out in its place
            ptg.FlowSpec(
                "A", ptg.RW,
                tile=lambda g, k, m: (g.A, (m, k)),
                ins=a_in(lambda k, m: (k, m, k)),
                outs=[ptg.Out(dst=("SSSSM",
                                   lambda g, k, m: [(k, m, n)
                                                    for n in row(k, g)],
                                   "L21")),
                      kept("A", lambda k, m: (m, k))]),
            # what dgetrs_incpiv reads; the pair's SSSSMs get W
            ptg.FlowSpec(
                "L", ptg.RW,
                tile=lambda g, k, m: (g.L, (m, k)),
                ins=[ptg.In(data=lambda g, k, m: (g.L, (m, k)))],
                outs=[kept("L", lambda k, m: (m, k))]),
            ptg.FlowSpec(
                "IPIV", ptg.RW,
                tile=lambda g, k, m: (g.IPIV, (m, k)),
                ins=[ptg.In(data=lambda g, k, m: (g.IPIV, (m, k)))],
                outs=[ptg.Out(dst=("SSSSM",
                                   lambda g, k, m: [(k, m, n)
                                                    for n in row(k, g)],
                                   "P")),
                      kept("IPIV", lambda k, m: (m, k))]),
            # the blocks' L11^-1, for the pair's SSSSMs: a value, no
            # tile, no part of the factored form
            ptg.FlowSpec(
                "W", ptg.WRITE,
                outs=[ptg.Out(dst=("SSSSM",
                                   lambda g, k, m: [(k, m, n)
                                                    for n in row(k, g)],
                                   "W"))]),
            ptg.FlowSpec(
                "G", ptg.CTL,
                ins=[ptg.In(src=("GESSM",
                                 lambda g, k, m: [(k, n) for n in row(k, g)],
                                 "G"),
                            gather=True,
                            guard=lambda g, k, m: m + 1 == g.NT)]),
        ])

    SSSSM = tp.task_class(
        "SSSSM", params=("k", "m", "n"),
        space=lambda g: ((k, m, n) for k in range(g.NT) for m in row(k, g)
                         for n in row(k, g)),
        affinity=lambda g, k, m, n: (g.A, (m, n)),
        priority=lambda g, k, m, n: (g.NT - k) ** 2 - m - n,
        flows=[
            ptg.FlowSpec(
                "L21", ptg.READ,
                tile=lambda g, k, m, n: (g.A, (m, k)),
                ins=[ptg.In(src=("TSTRF", lambda g, k, m, n: (k, m), "A"))]),
            ptg.FlowSpec(
                "W", ptg.READ,
                ins=[ptg.In(src=("TSTRF", lambda g, k, m, n: (k, m), "W"))]),
            ptg.FlowSpec(
                "P", ptg.READ,
                tile=lambda g, k, m, n: (g.IPIV, (m, k)),
                ins=[ptg.In(src=("TSTRF", lambda g, k, m, n: (k, m),
                                 "IPIV"))]),
            # the running row-k tile A(k,n), down the column
            ptg.FlowSpec(
                "A1", ptg.RW,
                tile=lambda g, k, m, n: (g.A, (k, n)),
                ins=[ptg.In(src=("GESSM", lambda g, k, m, n: (k, n), "C"),
                            guard=lambda g, k, m, n: m == k + 1),
                     ptg.In(src=("SSSSM",
                                 lambda g, k, m, n: (k, m - 1, n), "A1"),
                            guard=lambda g, k, m, n: m > k + 1)],
                outs=[ptg.Out(dst=("SSSSM",
                                   lambda g, k, m, n: (k, m + 1, n), "A1"),
                              guard=lambda g, k, m, n: m + 1 < g.NT),
                      kept("A", lambda k, m, n: (k, n))]),
            # the trailing tile A(m,n): step k+1's input
            ptg.FlowSpec(
                "A2", ptg.RW,
                tile=lambda g, k, m, n: (g.A, (m, n)),
                ins=a_in(lambda k, m, n: (k, m, n)),
                outs=[
                    ptg.Out(dst=("GETRF", lambda g, k, m, n: (k + 1,), "A"),
                            guard=lambda g, k, m, n: m == k + 1 and
                            n == k + 1),
                    ptg.Out(dst=("GESSM", lambda g, k, m, n: (k + 1, n),
                                 "C"),
                            guard=lambda g, k, m, n: m == k + 1 and
                            n > k + 1),
                    ptg.Out(dst=("TSTRF", lambda g, k, m, n: (k + 1, m),
                                 "A"),
                            guard=lambda g, k, m, n: m > k + 1 and
                            n == k + 1),
                    ptg.Out(dst=("SSSSM",
                                 lambda g, k, m, n: (k + 1, m, n), "A2"),
                            guard=lambda g, k, m, n: m > k + 1 and
                            n > k + 1),
                    kept("A", lambda k, m, n: (m, n)),
                ]),
        ])

    # Stacked forms as build_geqrf declares them: a row of GESSMs shares
    # L and P, a row of SSSSMs L21, W and P (one operand, not one a
    # member: an int32 tile among them); GETRF and TSTRF are serial
    # chains whose stacked form is an executor's and declares the chain's
    # own value shared, so that a chip module never builds a group
    # program for them.
    import jax

    def getrf(a, p):
        lu, perm = getrf_incpiv_tile(a)
        return {"A": lu, "IPIV": _over(p, perm),
                "U": jax.numpy.triu(lu)}

    def tstrf(u, a, l, p):
        u, a, l_new, piv, w = tstrf_tile(u, a, ib)
        return {"U": u, "A": a, "L": _over(l, l_new),
                "IPIV": _over(p, piv), "W": w}

    @GETRF.body(batch_hook=lambda As, Ps: jax.vmap(getrf)(As, Ps),
                batch_hook_shared=("A",), donates=("A", "IPIV"))
    def getrf_body(task, A_, P, U):
        return getrf(A_, P)

    @TSTRF.body(batch_hook=lambda Us, As, Ls, Ps: jax.vmap(tstrf)(
        Us, As, Ls, Ps), batch_hook_shared=("U",),
        donates=("U", "A", "L", "IPIV"))
    def tstrf_body(task, U, A_, L_, P, W):
        return tstrf(U, A_, L_, P)

    @GESSM.body(batch_hook=lambda Ls, Ps, Cs: _row(
        gessm_tile, (Ls[0], Ps[0]), Cs), batch_hook_shared=("L", "P"),
        donates=("C",))
    def gessm_body(task, L_, P, C):
        return gessm_tile(L_, P, C)

    def ssssm(l21, w, p, a1, a2):
        return ssssm_tile(a1, a2, w, l21, p)

    @SSSSM.body(batch_hook=lambda L21s, Ws, Ps, A1s, A2s: _row(
        ssssm, (L21s[0], Ws[0], Ps[0]), A1s, A2s),
        batch_hook_shared=("L21", "W", "P"), donates=("A1", "A2"))
    def ssssm_body(task, L21, W, P, A1, A2):
        return ssssm(L21, W, P, A1, A2)

    return tp


# ---- partial pivoting over whole panels (DPLASMA dgetrf_1d) -------------

def getrf_1d_ipiv_collection(A: TiledMatrix) -> TiledMatrix:
    """descIPIV of ``A`` for :func:`build_getrf_1d`: nb int32 a panel (a
    1 x nb tile, key ``(k, 0)``): LAPACK's interchange indices, 0-based
    from the panel's first row (ops/tile_kernels.py
    ``getrf_panel_tiles``)."""
    import numpy as np
    return TiledMatrix(A.mt, A.nb, 1, A.nb, dist=A.dist, dtype=np.int32,
                       name=f"{A.name}_IPIV")


def build_getrf_1d(A: TiledMatrix, IPIV: Optional[TiledMatrix] = None,
                   ib: Optional[int] = None) -> ptg.Taskpool:
    """Tile LU with partial pivoting over whole panels (DPLASMA
    ``dgetrf_1d``, ``zgetrf_1d.jdf``'s four classes) over ``A`` (square,
    nb x nb tiles) and ``IPIV`` (``getrf_1d_ipiv_collection``; made here
    where none is given: the pool's ``g.IPIV``), inner block ``ib``
    (default nb), k = 0..NT-1:

        GETRF(k):      P_k [A(k..NT-1, k)] = L U, the pivot of every
                       column sought down the WHOLE remaining block
                       column; writes the column's tiles and IPIV(k)
        SWPTRSM(k,n):  n > k: IPIV(k)'s interchanges applied to the
                       tiles A(k..NT-1, n), then A(k,n) <- L_kk^-1 A(k,n)
        GEMM(k,m,n):   m, n > k: A(m,n) -= A(m,k) A(k,n), full float32
        SWPBACK(k,n):  n < k: IPIV(k)'s interchanges applied to the
                       finished tiles A(k..NT-1, n) of L

    On completion P A = L U in LAPACK's ``dgetrf`` form: U in the upper
    triangle of A, the unit lower L under it with every interchange
    applied to it (what ``dgetrs`` reads), the interchange indices of
    panel k in IPIV(k). Every multiplier is at most 1 over the whole
    column, which is what incremental pivoting
    (:func:`build_getrf_incpiv`) gives up.

    **A flow over a range of tiles.** GETRF, SWPTRSM and SWPBACK read
    and rewrite a block column from row k down: their flow's value is
    the ordered LIST of those tiles (``ptg.In(gather=True)`` /
    ``ptg.Out(scatter=True)``), gathered from the step before's GEMMs
    (the collection at k = 0) and handed on element by element: GETRF's
    diagonal tile to the row's SWPTRSMs as L, its tile (m,k) to row m's
    GEMMs as the left operand and on to SWPBACK(k+1,k); SWPTRSM's first
    tile to column n's GEMMs as the right operand, its tile (m,n) to
    GEMM(k,m,n) as C. Every tile is an operand of its task's launch, and
    is updated in the buffer it lies in (``Chore.donates``, element by
    element): the factorization runs in the storage of A and IPIV.

    SWPBACK(k+1,k) rewrites the tiles the step's GEMMs read as their
    left operand, so it waits for them: for GETRF(k+1) (its pivots,
    which gathered column k+1's GEMMs) and, by a CTL gather, for the
    SWPTRSM(k+1,n) of the other columns (each gathered its column's).

    Priorities favour the next panel (look-ahead): GETRF 4(NT-k)^2,
    SWPTRSM 3(NT-k)^2 - n (the next panel's column first), the GEMMs
    of column k+1 2(NT-k)^2 - m, the other GEMMs (NT-k)^2 - m - n,
    SWPBACK 0: it is on no path to a panel.
    """
    NT = _check(A)
    if IPIV is None:
        IPIV = getrf_1d_ipiv_collection(A)
    ib = ib or A.nb
    if A.nb % ib or (IPIV.mt, IPIV.nt, IPIV.mb, IPIV.nb) != (NT, 1, 1, A.nb):
        raise ValueError("IPIV needs one tile of 1 x nb a panel of A, and "
                         "ib has to divide nb")
    tp = ptg.Taskpool("getrf_1d", A=A, IPIV=IPIV, NT=NT)

    def below(k, g):
        return range(k, g.NT)

    def column(g, k, n):
        """The tiles (k..NT-1, n) of A."""
        return [(g.A, (m, n)) for m in below(k, g)]

    def column_in(col):
        """A block column from row k down as step k finds it: the
        matrix's at k = 0, else what the GEMMs of step k-1 left.
        ``col(*params) -> (k, n)``."""
        return [ptg.In(data=lambda g, *p: column(g, *col(*p)), gather=True,
                       guard=lambda g, *p: col(*p)[0] == 0),
                ptg.In(src=("GEMM",
                            lambda g, *p: [(col(*p)[0] - 1, m, col(*p)[1])
                                           for m in below(col(*p)[0], g)],
                            "C"),
                       gather=True, guard=lambda g, *p: col(*p)[0] > 0)]

    def rest_to(at):
        """A list's tiles after the first, on to the ONE task ``at``
        names (which gathers them); the first goes elsewhere."""
        return lambda g, *p: [[]] + [[at(*p)]] * (g.NT - p[0] - 1)

    GETRF = tp.task_class(
        "GETRF", params=("k",),
        space=lambda g: ((k,) for k in range(g.NT)),
        affinity=lambda g, k: (g.A, (k, k)),
        priority=lambda g, k: 4 * (g.NT - k) ** 2,
        flows=[
            ptg.FlowSpec(
                "A", ptg.RW,
                tile=lambda g, k: column(g, k, k),
                ins=column_in(lambda k: (k, k)),
                outs=[
                    # (k,k): L for the row's SWPTRSMs; (m,k): the left
                    # operand of row m's GEMMs
                    ptg.Out(dst=("SWPTRSM",
                                 lambda g, k: [[(k, n) for n in
                                                range(k + 1, g.NT)]] +
                                 [[]] * (g.NT - k - 1), "L"),
                            scatter=True),
                    ptg.Out(dst=("GEMM",
                                 lambda g, k: [[]] + [
                                     [(k, m, n) for n in range(k + 1, g.NT)]
                                     for m in range(k + 1, g.NT)], "A"),
                            scatter=True),
                    ptg.Out(dst=("SWPBACK",
                                 rest_to(lambda k: (k + 1, k)),
                                 "C"),
                            scatter=True, guard=lambda g, k: k + 1 < g.NT),
                    # the diagonal tile is final; the others' write-back
                    # is their last SWPBACK's
                    ptg.Out(data=lambda g, k: [(g.A, (k, k))] +
                            [None] * (g.NT - k - 1), scatter=True)]),
            ptg.FlowSpec(
                "IPIV", ptg.RW,
                tile=lambda g, k: (g.IPIV, (k, 0)),
                ins=[ptg.In(data=lambda g, k: (g.IPIV, (k, 0)))],
                outs=[ptg.Out(dst=("SWPTRSM",
                                   lambda g, k: [(k, n) for n in
                                                 range(k + 1, g.NT)], "P")),
                      ptg.Out(dst=("SWPBACK",
                                   lambda g, k: [(k, n) for n in range(k)],
                                   "P")),
                      ptg.Out(data=lambda g, k: (g.IPIV, (k, 0)))]),
        ])

    SWPTRSM = tp.task_class(
        "SWPTRSM", params=("k", "n"),
        space=lambda g: ((k, n) for k in range(g.NT)
                         for n in range(k + 1, g.NT)),
        affinity=lambda g, k, n: (g.A, (k, n)),
        priority=lambda g, k, n: 3 * (g.NT - k) ** 2 - n,
        flows=[
            ptg.FlowSpec(
                "L", ptg.READ,
                tile=lambda g, k, n: (g.A, (k, k)),
                ins=[ptg.In(src=("GETRF", lambda g, k, n: (k,), "A"))]),
            ptg.FlowSpec(
                "P", ptg.READ,
                tile=lambda g, k, n: (g.IPIV, (k, 0)),
                ins=[ptg.In(src=("GETRF", lambda g, k, n: (k,), "IPIV"))]),
            ptg.FlowSpec(
                "C", ptg.RW,
                tile=lambda g, k, n: column(g, k, n),
                ins=column_in(lambda k, n: (k, n)),
                outs=[
                    # (k,n): a finished tile of U, the right operand of
                    # column n's GEMMs; (m,n): GEMM(k,m,n)'s C
                    ptg.Out(dst=("GEMM",
                                 lambda g, k, n: [[(k, m, n) for m in
                                                   range(k + 1, g.NT)]] +
                                 [[]] * (g.NT - k - 1), "B"),
                            scatter=True),
                    ptg.Out(dst=("GEMM",
                                 lambda g, k, n: [[]] + [
                                     [(k, m, n)]
                                     for m in range(k + 1, g.NT)], "C"),
                            scatter=True),
                    ptg.Out(data=lambda g, k, n: [(g.A, (k, n))] +
                            [None] * (g.NT - k - 1), scatter=True)]),
            # its column's GEMMs of step k-1 have read their left
            # operands, which SWPBACK(k, k-1) rewrites
            ptg.FlowSpec(
                "G", ptg.CTL,
                outs=[ptg.Out(dst=("SWPBACK",
                                   lambda g, k, n: (k, k - 1), "G"),
                              guard=lambda g, k, n: k > 0 and n > k)]),
        ])

    GEMM = tp.task_class(
        "GEMM", params=("k", "m", "n"),
        space=lambda g: ((k, m, n) for k in range(g.NT)
                         for m in range(k + 1, g.NT)
                         for n in range(k + 1, g.NT)),
        affinity=lambda g, k, m, n: (g.A, (m, n)),
        priority=lambda g, k, m, n: (
            2 * (g.NT - k) ** 2 - m if n == k + 1
            else (g.NT - k) ** 2 - m - n),
        flows=[
            ptg.FlowSpec(
                "A", ptg.READ,
                tile=lambda g, k, m, n: (g.A, (m, k)),
                ins=[ptg.In(src=("GETRF", lambda g, k, m, n: (k,), "A"))]),
            ptg.FlowSpec(
                "B", ptg.READ,
                tile=lambda g, k, m, n: (g.A, (k, n)),
                ins=[ptg.In(src=("SWPTRSM", lambda g, k, m, n: (k, n),
                                 "C"))]),
            ptg.FlowSpec(
                "C", ptg.RW,
                tile=lambda g, k, m, n: (g.A, (m, n)),
                ins=[ptg.In(src=("SWPTRSM", lambda g, k, m, n: (k, n),
                                 "C"))],
                outs=[ptg.Out(dst=("GETRF", lambda g, k, m, n: (k + 1,),
                                   "A"),
                              guard=lambda g, k, m, n: n == k + 1),
                      ptg.Out(dst=("SWPTRSM",
                                   lambda g, k, m, n: (k + 1, n), "C"),
                              guard=lambda g, k, m, n: n > k + 1)]),
        ])

    SWPBACK = tp.task_class(
        "SWPBACK", params=("k", "n"),
        space=lambda g: ((k, n) for k in range(g.NT) for n in range(k)),
        affinity=lambda g, k, n: (g.A, (k, n)),
        priority=lambda g, k, n: 0,
        flows=[
            ptg.FlowSpec(
                "P", ptg.READ,
                tile=lambda g, k, n: (g.IPIV, (k, 0)),
                ins=[ptg.In(src=("GETRF", lambda g, k, n: (k,), "IPIV"))]),
            # column n of L from row k down: GETRF(n)'s tiles, then what
            # the SWPBACK of the panel before left (one producer, named
            # once a tile)
            ptg.FlowSpec(
                "C", ptg.RW,
                tile=lambda g, k, n: column(g, k, n),
                ins=[ptg.In(src=("GETRF",
                                 lambda g, k, n: [(n,)] * (g.NT - k), "A"),
                            gather=True, guard=lambda g, k, n: k == n + 1),
                     ptg.In(src=("SWPBACK",
                                 lambda g, k, n: [(k - 1, n)] * (g.NT - k),
                                 "C"),
                            gather=True, guard=lambda g, k, n: k > n + 1)],
                outs=[
                    # row k takes no later interchange: (k,n) is final
                    ptg.Out(dst=("SWPBACK",
                                 rest_to(lambda k, n: (k + 1, n)), "C"),
                            scatter=True,
                            guard=lambda g, k, n: k + 1 < g.NT),
                    ptg.Out(data=lambda g, k, n: [(g.A, (k, n))] +
                            [None] * (g.NT - k - 1), scatter=True)]),
            ptg.FlowSpec(
                "G", ptg.CTL,
                ins=[ptg.In(src=("SWPTRSM",
                                 lambda g, k, n: [(k, c) for c in
                                                  range(k + 1, g.NT)], "G"),
                            gather=True,
                            guard=lambda g, k, n: k == n + 1 and
                            k + 1 < g.NT)]),
        ])

    import jax

    def getrf(tiles, p):
        tiles, ipiv = getrf_panel_tiles(tiles, ib)
        return {"A": tiles, "IPIV": _over(p, ipiv)}

    # a serial chain: the stacked form is declared as build_getrf_incpiv
    # declares GETRF's, so that a chip module builds no group program
    # for a class whose tasks never meet (one a list length as it is)
    @GETRF.body(batch_hook=lambda As, Ps: jax.vmap(getrf)(As, Ps),
                batch_hook_shared=("A",), donates=("A", "IPIV"),
                compiler_options=PANEL_COMPILER_OPTIONS)
    def getrf_body(task, A_, P):
        return getrf(A_, P)

    @SWPTRSM.body(donates=("C",))
    def swptrsm_body(task, L_, P, C):
        return {"C": swptrsm_tiles(L_, P, C)}

    @GEMM.body(donates=("C",))
    def gemm_body(task, A_, B_, C):
        return gemm_full_tile(A_, B_, C)

    @SWPBACK.body(donates=("C",))
    def swpback_body(task, P, C):
        return {"C": laswp_tiles(C, P)}

    return tp
