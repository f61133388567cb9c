"""Tile-level kernels (jnp; MXU-friendly shapes).

These are the FLOP-carrying bodies of the shipped linear-algebra
taskpools — the role CUDA kernels in user .jdf BODY sections play in the
reference (e.g. DPLASMA's dpotrf/dgemm tiles). All operate on full
(mb × nb) tiles; ``preferred_element_type=float32`` keeps MXU accumulation
in f32 even for bf16 tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..utils import compile_cache, mca_param

# On TPU, f32 matmuls default to bf16 MXU passes (~1e-2 relative error).
# "highest" runs the 6-pass f32 emulation — DPLASMA-grade accuracy at a
# throughput cost; "default" is the TPU-native speed setting.
mca_param.register("ops.matmul_precision", "default",
                   help="MXU precision for tile matmuls: default|high|highest")
# these knobs choose what gets TRACED into compiled tile kernels —
# every shared/persistent compile-cache key snapshots them
compile_cache.register_trace_knob("ops.matmul_precision")


def matmul_precision():
    """The configured MXU precision for tile matmuls (None = TPU-native
    bf16 passes; 'highest' = 6-pass f32 emulation). Public so non-LA
    bodies (attention, FFN, ring attention) honor the same knob."""
    p = str(mca_param.get("ops.matmul_precision", "default"))
    return None if p == "default" else p


_prec = matmul_precision


def gemm_tile(C, A, B, alpha=1.0, beta=1.0, ta=False, tb=False):
    """C ← α·op(A)·op(B) + β·C (tile GEMM)."""
    opA = A.T if ta else A
    opB = B.T if tb else B
    acc = jnp.matmul(opA, opB, preferred_element_type=jnp.float32,
                     precision=_prec())
    return (alpha * acc + beta * C).astype(C.dtype)


def syrk_tile(C, A, alpha=-1.0, beta=1.0):
    """C ← α·A·Aᵀ + β·C (symmetric rank-k update, lower)."""
    acc = jnp.matmul(A, A.T, preferred_element_type=jnp.float32,
                     precision=_prec())
    return (alpha * acc + beta * C).astype(C.dtype)


def trsm_tile(B, L):
    """B ← B·L⁻ᵀ — right-solve with the lower-triangular factor L of the
    panel tile (the dpotrf TRSM update: A[m,k] = A[m,k] L[k,k]^-T)."""
    x = jax.scipy.linalg.solve_triangular(
        L.astype(jnp.float32), B.astype(jnp.float32).T,
        lower=True, trans=0)
    return x.T.astype(B.dtype)


def trsm_tiles_wide(L, Bs):
    """Batched B_i ← B_i·L⁻ᵀ with a SHARED factor L, formulated as ONE
    wide-RHS triangular solve: L · Y = [B₁ᵀ | B₂ᵀ | …]. On TPU this is
    several times faster than vmapping per-tile solves (batched
    triangular-solve lowering is poor); used as the TRSM batch_hook in
    the compiled POTRF path."""
    nbatch, nb, _ = Bs.shape
    rhs = jnp.swapaxes(Bs, 1, 2).transpose(1, 0, 2).reshape(nb, nbatch * nb)
    Y = jax.scipy.linalg.solve_triangular(
        L.astype(jnp.float32), rhs.astype(jnp.float32), lower=True)
    return Y.reshape(nb, nbatch, nb).transpose(1, 2, 0).astype(Bs.dtype)


def potrf_tile(A):
    """A ← chol(A) lower (diagonal-tile Cholesky)."""
    return jnp.linalg.cholesky(A.astype(jnp.float32)).astype(A.dtype)


# ---- MXU-rich variants of the triangular kernels -----------------------
# XLA's triangular_solve and cholesky lower to blocked substitution whose
# throughput on TPU is a small fraction of matmul peak (measured ~20-50
# GF/s/chip at nb=2048 vs ~178 TF/s for batched GEMM). The compiled POTRF
# path therefore reformulates both around matmuls, the MAGMA/DPLASMA GPU
# trick (invert the diagonal block once, turn every solve into a GEMM);
# the reference gets the same effect by linking vendor BLAS into .jdf
# bodies (dplasma's dpotrf_L gpu chores).

mca_param.register("ops.tri_base", 256,
                   help="base block size for matmul-rich triangular "
                        "kernels (tri_inv_tile / potrf_tile_blocked)")
compile_cache.register_trace_knob("ops.tri_base")


def tri_inv_tile(L, base: int = 0):
    """L⁻¹ of a lower-triangular tile via recursive block inversion:
    [[L11, 0], [L21, L22]]⁻¹ = [[L11⁻¹, 0], [-L22⁻¹·L21·L11⁻¹, L22⁻¹]].
    All flops above the base case are matmuls."""
    base = base or int(mca_param.get("ops.tri_base", 256))
    Lf = L.astype(jnp.float32)

    def rec(T):
        n = T.shape[0]
        if n <= base or n % 2:
            return jax.lax.linalg.triangular_solve(
                T, jnp.eye(n, dtype=T.dtype), left_side=True, lower=True)
        h = n // 2
        i11 = rec(T[:h, :h])
        i22 = rec(T[h:, h:])
        i21 = -jnp.matmul(
            jnp.matmul(i22, T[h:, :h], preferred_element_type=jnp.float32,
                       precision=_prec()),
            i11, preferred_element_type=jnp.float32, precision=_prec())
        top = jnp.concatenate([i11, jnp.zeros((h, n - h), T.dtype)], axis=1)
        return jnp.concatenate([top, jnp.concatenate([i21, i22], axis=1)],
                               axis=0)

    return rec(Lf).astype(L.dtype)


def chol_inv_tile(A, base: int = 0):
    """(L, L⁻¹) of an SPD tile with its block columns walked by ONE
    compiled loop body: :func:`potrf_tile_blocked`'s right-looking
    arithmetic (XLA's cholesky on a ``base``-sized diagonal block, its
    inverse, panel solve and trailing update as matmuls) and the inverse
    by block forward substitution with the diagonal inverses the walk
    already has, ``X[j,:] = L_jj⁻¹·(E_j − L[j,:j]·X[:j,:])``.

    Why a loop: a program's text lives in HBM beside its data, and
    XLA:TPU's cholesky and triangular_solve cost 1.2 and 0.4 MB of it a
    call at base 256 whatever else is compiled. Unrolled,
    ``potrf_tile_blocked`` + ``tri_inv_tile`` are 8.3 MB a 1024-tile
    (measured with the TPU compiler, PERF.md §6 PR 30); a program that
    factors 64 diagonal tiles carries 0.53 GB of it, this form 64 ×
    2.6 MB. The mesh lowering of left-looking POTRF factors its diagonal
    tiles with it; the one-chip fusers keep chol-then-invert, whose text
    keys their stored programs."""
    n = A.shape[0]
    b = base or int(mca_param.get("ops.tri_base", 256))
    if n % b:
        b = n                     # one block: plain cholesky + inverse
    f32 = jnp.float32
    eye = jnp.eye(b, dtype=f32)
    row = jnp.arange(n)[:, None]

    def mm(x, y):
        return jnp.matmul(x, y, preferred_element_type=f32,
                          precision=_prec())

    def column(j, carry):
        S, L, X = carry           # trailing matrix, factor, inverse
        o = j * b
        l11 = jnp.linalg.cholesky(jax.lax.dynamic_slice(S, (o, o), (b, b)))
        i11 = jax.lax.linalg.triangular_solve(l11, eye, left_side=True,
                                              lower=True)
        # block column j below the diagonal block: L21 = A21·L11⁻ᵀ
        panel = jnp.where(row >= o + b, mm(
            jax.lax.dynamic_slice(S, (0, o), (n, b)), i11.T), 0.0)
        S = S - mm(panel, panel.T)
        # block row j of L⁻¹ from the rows above it; L[j, :j] is final
        # and X's rows from j on are still zero. The diagonal block is
        # L11⁻¹ itself, not its product with a one
        x = -mm(i11, mm(jax.lax.dynamic_slice(L, (o, 0), (b, n)), X))
        X = jax.lax.dynamic_update_slice(
            X, jax.lax.dynamic_update_slice(x, i11, (0, o)), (o, 0))
        L = jax.lax.dynamic_update_slice(
            L, jax.lax.dynamic_update_slice(panel, l11, (o, 0)), (0, o))
        return S, L, X

    zero = jnp.zeros((n, n), f32)
    _, L, X = jax.lax.fori_loop(
        0, n // b, column, (jnp.asarray(A, f32), zero, zero))
    return L.astype(A.dtype), X.astype(A.dtype)


def potrf_tile_blocked(A, base: int = 0):
    """Blocked right-looking in-tile Cholesky: factor a ``base``-sized
    diagonal block with the XLA cholesky, invert it (cheap at base size),
    and apply panel solve + trailing update as matmuls. Keeps the MXU
    busy where ``jnp.linalg.cholesky`` on the full tile would serialize."""
    base = base or int(mca_param.get("ops.tri_base", 256))
    n = A.shape[0]
    if n <= base:
        return potrf_tile(A)
    Af = jnp.asarray(A, jnp.float32)
    L = jnp.zeros_like(Af)
    for j in range(0, n, base):
        b = min(base, n - j)
        l11 = jnp.linalg.cholesky(Af[j:j + b, j:j + b])
        L = L.at[j:j + b, j:j + b].set(l11)
        if j + b < n:
            inv11 = jax.lax.linalg.triangular_solve(
                l11, jnp.eye(b, dtype=jnp.float32),
                left_side=True, lower=True)
            panel = jnp.matmul(Af[j + b:, j:j + b], inv11.T,
                               preferred_element_type=jnp.float32,
                               precision=_prec())
            L = L.at[j + b:, j:j + b].set(panel)
            Af = Af.at[j + b:, j + b:].add(
                -jnp.matmul(panel, panel.T,
                            preferred_element_type=jnp.float32,
                            precision=_prec()))
    return L.astype(A.dtype)


def trsm_tiles_gemm(L, Bs):
    """Batched B_i ← B_i·L⁻ᵀ with a SHARED factor L, as one inversion
    plus one wide matmul: Y = [B₁; B₂; …]·(L⁻¹)ᵀ. The inversion is
    amortized over the whole wave; the matmul runs at MXU speed where
    the wide triangular solve runs an order of magnitude slower."""
    nbatch, nb, _ = Bs.shape
    Linv = tri_inv_tile(L)
    wide = Bs.reshape(nbatch * nb, nb)
    Y = jnp.matmul(wide.astype(jnp.float32), Linv.T.astype(jnp.float32),
                   preferred_element_type=jnp.float32, precision=_prec())
    return Y.reshape(nbatch, nb, nb).astype(Bs.dtype)


def add_tile(A, B):
    return A + B


def scale_tile(A, alpha):
    return alpha * A


# ---- tiled-LU kernels (DPLASMA dgetrf_nopiv tile operations) -----------
# In-tile LU without pivoting: XLA has no unpivoted-LU primitive (and
# lax.linalg.lu's row permutation would have to flow through the whole
# block row), so the factorization is a Schur-complement recursion whose
# every flop above the tiny base case is a matmul or triangular solve —
# the same MXU-first reformulation as potrf_tile_blocked. Valid for the
# diagonally-dominant / well-conditioned regime tile LU targets (the
# no-pivot variant is the standard accelerator formulation; pivoted
# fallback = jax.lax.linalg.lu at user level).

def _lu_base(T):
    """Masked rank-1 eliminations as ONE fori_loop — a handful of traced
    ops regardless of the block size (an unrolled loop would put ~n ops
    per tile into the fused whole-DAG program). A rank-2 variant
    (second column's post-elimination state derived algebraically) was
    tried in round 5 and measured SLOWER in the full fused LU (53.9 vs
    56.9 TF/s at N=32768): the longer dependent-op body beat the saved
    loop iterations."""
    n = T.shape[0]
    idx = jnp.arange(n)

    def step(i, M):
        piv = M[i, i]
        col = jnp.where(idx > i, M[:, i] / piv, 0.0)   # multipliers
        row = jnp.where(idx > i, M[i, :], 0.0)         # U row, cols > i
        M = M - col[:, None] * row[None, :]
        return M.at[:, i].set(jnp.where(idx > i, col, M[:, i]))

    return jax.lax.fori_loop(0, n - 1, step, T)


def getrf_nopiv_tile(A, base: int = 64):
    """A ← packed LU (unit-lower L below the diagonal, U on/above)
    without pivoting, via blocked Schur recursion."""
    Af = jnp.asarray(A, jnp.float32)

    def rec(T):
        n = T.shape[0]
        if n <= base or n % 2:
            return _lu_base(T)
        h = n // 2
        A11 = rec(T[:h, :h])
        # A12 <- L11^-1 A12 (unit-lower), A21 <- A21 U11^-1
        A12 = jax.lax.linalg.triangular_solve(
            A11, T[:h, h:], left_side=True, lower=True,
            unit_diagonal=True)
        A21 = jax.lax.linalg.triangular_solve(
            A11, T[h:, :h], left_side=False, lower=False)
        S = T[h:, h:] - jnp.matmul(A21, A12,
                                   preferred_element_type=jnp.float32,
                                   precision=_prec())
        A22 = rec(S)
        top = jnp.concatenate([A11, A12], axis=1)
        return jnp.concatenate(
            [top, jnp.concatenate([A21, A22], axis=1)], axis=0)

    return rec(Af).astype(A.dtype)


def lu_inv_tile(A, base: int = 64):
    """``(packed LU, L⁻¹, U⁻¹)`` of a tile in ONE Schur recursion — the
    LU analog of :func:`chol_inv_tile` (the MAGMA diagonal-inversion
    trick applied to BOTH solve stages). With the child inverses in
    hand, the recursion's panel solves become matmuls
    (U12 = L11⁻¹·A12, L21 = A21·U11⁻¹ — plain dots against the
    already-computed inverses instead of triangular solves) and the
    inverses assemble from blocks the recursion already has
    (L⁻¹₂₁ = −L22⁻¹·L21·L11⁻¹, U⁻¹₁₂ = −U11⁻¹·U12·U22⁻¹), so every
    flop above the base case is a matmul. Consumed by the GETRF panel
    fuser under ``getrf.trsm_hook=gemm``: the step's two panel TRSMs
    run as MXU matmuls against the returned inverses, and the two
    standalone nb-sized ``tri_inv_tile`` recursions (each with its own
    internal triangular solves) disappear — their results fall out of
    the factorization recursion."""
    Af = jnp.asarray(A, jnp.float32)

    def mm(a, b):
        return jnp.matmul(a, b, preferred_element_type=jnp.float32,
                          precision=_prec())

    def rec(T):
        n = T.shape[0]
        if n <= base or n % 2:
            LU = _lu_base(T)
            eye = jnp.eye(n, dtype=jnp.float32)
            L = jnp.tril(LU, -1) + eye
            Li = jax.lax.linalg.triangular_solve(
                L, eye, left_side=True, lower=True, unit_diagonal=True)
            Ui = jax.lax.linalg.triangular_solve(
                jnp.triu(LU), eye, left_side=True, lower=False)
            return LU, Li, Ui
        h = n // 2
        LU11, Li11, Ui11 = rec(T[:h, :h])
        U12 = mm(Li11, T[:h, h:])
        L21 = mm(T[h:, :h], Ui11)
        S = T[h:, h:] - mm(L21, U12)
        LU22, Li22, Ui22 = rec(S)
        Li21 = -mm(Li22, mm(L21, Li11))
        Ui12 = -mm(Ui11, mm(U12, Ui22))
        Ztop = jnp.zeros((h, n - h), jnp.float32)
        Zbot = jnp.zeros((n - h, h), jnp.float32)
        LU = jnp.concatenate(
            [jnp.concatenate([LU11, U12], axis=1),
             jnp.concatenate([L21, LU22], axis=1)], axis=0)
        Li = jnp.concatenate(
            [jnp.concatenate([Li11, Ztop], axis=1),
             jnp.concatenate([Li21, Li22], axis=1)], axis=0)
        Ui = jnp.concatenate(
            [jnp.concatenate([Ui11, Ui12], axis=1),
             jnp.concatenate([Zbot, Ui22], axis=1)], axis=0)
        return LU, Li, Ui

    LU, Li, Ui = rec(Af)
    return LU.astype(A.dtype), Li.astype(A.dtype), Ui.astype(A.dtype)


def lu_split(LU):
    """Unpack (L unit-lower, U upper) from a packed LU tile."""
    L = jnp.tril(LU, -1) + jnp.eye(LU.shape[0], dtype=LU.dtype)
    return L, jnp.triu(LU)


def trsm_lower_unit(LU, C):
    """C ← L⁻¹·C with L the unit-lower factor of a packed LU tile (the
    dgetrf row-panel update, left solve)."""
    return jax.lax.linalg.triangular_solve(
        jnp.asarray(LU, jnp.float32), jnp.asarray(C, jnp.float32),
        left_side=True, lower=True, unit_diagonal=True).astype(C.dtype)


def trsm_upper_right(LU, C):
    """C ← C·U⁻¹ with U the upper factor of a packed LU tile (the
    dgetrf column-panel update, right solve)."""
    return jax.lax.linalg.triangular_solve(
        jnp.asarray(LU, jnp.float32), jnp.asarray(C, jnp.float32),
        left_side=False, lower=False).astype(C.dtype)


# ---- panel QR (whole block-column at once, MXU-formulated) -------------
# The compiled GEQRF path factors an entire (mk x nb) panel per step.
# XLA's blocked-Householder QR serializes badly on TPU (measured ~20 ms
# at 16384x1024 where the CholeskyQR2 pipeline below takes ~5 ms), so the
# panel kernel is CholeskyQR2 — two Gram+Cholesky orthogonalization
# rounds, everything but the nb-sized factorizations a matmul — followed
# by an exact orthogonal-completion reconstruction:
#
#     given the reduced factor Q_r (mk x nb) with top block Q1, set
#         V = Q_r - E1,   X = I - Q1
#     then  H = I - V X^-T V^T  satisfies  H E1 = Q_r  (exact algebra:
#     V^T E1 = (Q1 - I)^T = -X^T) and
#           H^T H = I + V X^-1 (Q_r^T Q_r - I) X^-T V^T
#
# i.e. H is orthogonal exactly when Q_r is orthonormal — CholeskyQR2's
# job — and the trailing update H^T C = C - V X^-T (V^T C) is two large
# matmuls. This is the Householder-reconstruction idea of Ballard et al.
# / Yamamoto (public algorithm), reformulated around an explicit nb x nb
# inverse instead of an unpivoted LU (X's diagonal is >= 1 after the
# sign fix below, the same conditioning argument). Reference analog: the
# GEQRT+TSQRT panel chain of dplasma's dgeqrf
# (reference parsec/data_dist/matrix/ + BASELINE.md dgeqrf config).

mca_param.register("ops.panel_qr", "cholqr2",
                   help="panel QR kernel for the fused GEQRF path: "
                        "cholqr2 (all-matmul, needs full column rank) | "
                        "xla (jnp.linalg.qr, slower, more robust)")
compile_cache.register_trace_knob("ops.panel_qr")


def panel_qr_tile(Pt):
    """Factor a panel given TRANSPOSED ``Pt`` (nb x mk, P = Ptᵀ).

    Returns ``(Vt, Xinv, R)`` with ``Vt`` (nb x mk) the transposed
    reconstruction factor, ``Xinv = X⁻¹`` (nb x nb), and ``R`` (nb x nb
    upper) such that ``H = I - Vtᵀ·Xinvᵀ·Vt`` is orthogonal,
    ``Hᵀ·P = [R; 0]`` and ``H·E1 = Q_r``. All heavy ops are matmuls at
    f32 accumulation.
    """
    nb = Pt.shape[0]
    Pt = Pt.astype(jnp.float32)
    if str(mca_param.get("ops.panel_qr", "cholqr2")) == "xla":
        Q, R = jnp.linalg.qr(Pt.T)      # reduced: (mk, nb), (nb, nb)
        Qt = Q.T
    else:
        # CholeskyQR2: Q1 = P L1^-T, Q = Q1 L2^-T, R = (L1 L2)^T.
        # Grams accumulate in f32; the nb-sized chol/solves are exact.
        G1 = jnp.matmul(Pt, Pt.T, preferred_element_type=jnp.float32,
                        precision=_prec())
        L1 = jnp.linalg.cholesky(G1)
        Q1t = jax.scipy.linalg.solve_triangular(L1, Pt, lower=True)
        G2 = jnp.matmul(Q1t, Q1t.T, preferred_element_type=jnp.float32,
                        precision=_prec())
        L2 = jnp.linalg.cholesky(G2)
        Qt = jax.scipy.linalg.solve_triangular(L2, Q1t, lower=True)
        # nb x nb product: always full f32 — R must match the H the
        # trailing update applies, and this matmul's cost is noise
        R = jnp.matmul(L1, L2, preferred_element_type=jnp.float32,
                       precision="highest").T
    # sign fix: scale columns of Q (rows of Qt) so diag(Q1) <= 0 and
    # X = I - Q1 has diagonal >= 1 (well-conditioned inverse); R's rows
    # absorb the signs, so Q·R is unchanged
    d = jnp.diagonal(Qt[:, :nb])
    s = jnp.where(d >= 0, -1.0, 1.0).astype(jnp.float32)
    Qt = s[:, None] * Qt
    R = s[:, None] * R
    Vt = Qt.at[:, :nb].add(-jnp.eye(nb, dtype=jnp.float32))
    X = jnp.eye(nb, dtype=jnp.float32) - Qt[:, :nb].T
    Xinv = jnp.linalg.inv(X)
    return Vt, Xinv, R


def panel_qr_apply(Vt, Xinv, Ct):
    """Trailing update in transposed storage: given ``Ct = Cᵀ``
    (ncols x mk), return ``(Hᵀ·C)ᵀ = Ct - (Ct·Vtᵀ)·Xinvᵀ·Vt`` — two
    large matmuls plus one small (ncols x nb)·(nb x nb)."""
    W = jnp.matmul(Ct, Vt.T, preferred_element_type=jnp.float32,
                   precision=_prec())
    W = jnp.matmul(W, Xinv.T, preferred_element_type=jnp.float32,
                   precision=_prec())
    return (Ct - jnp.matmul(W, Vt, preferred_element_type=jnp.float32,
                            precision=_prec())).astype(Ct.dtype)


# ---- tiled-QR kernels (DPLASMA dgeqrf tile operations) -----------------
# PLASMA's four, in compact-WY form: a tile's orthogonal factor is a
# product of nb/ib block reflectors Q = Q_1 ... Q_{nb/ib},
# Q_j = I - V_j T_j V_j^T, T_j upper triangular ib x ib, the T_j side by
# side in one ib x nb tile (core_zgeqrt's layout; ib = nb is one block).
# V stays where upstream leaves it: unit lower below R in the diagonal
# tile (GEQRT), the whole tile under it (TSQRT, V = [I; V2]).
#
# A column-by-column Householder loop is 2048 dependent trips a tile on
# this chip, so a panel is factored the way panel_qr_tile is: by
# Cholesky-QR on its Gram matrix and Householder reconstruction
# (Ballard et al.), every flop above a _QR_BASE-wide block a matmul.
#   TS case, [R; A] with R upper triangular: the thin factor's top block
#   Q1 = R R'^-1 is upper triangular, so with R' = -S chol(R^T R + A^T A)^T
#   (S the signs of diag R: diag Q1 <= 0) V2 = A (R - R')^-1, one solve.
#   Tall case (rows >= 2 x columns): Q from shifted Cholesky-QR, V = L of
#   the unpivoted LU of S - Q, the signs S chosen pivot by pivot (every
#   pivot >= 1). The one block of a diagonal tile that is nearly square
#   goes through XLA's Householder QR: a Gram matrix cannot carry it.
# T is then MADE from V: T^-1 = striu(V^T V) + diag(V^T V)/2, the
# identity that makes I - V T V^T orthogonal whatever V is, computed at
# full f32. So the stored (V, T) give an orthogonal Q to f32 rounding
# however the update matmuls are rounded (ops.matmul_precision), and what
# the Gram route loses (kappa(panel)^2 eps of R': the TS panels [R; A]
# and the tall panels of a matrix of full column rank have kappa under
# ~50) shows in A - QR beside the updates' own bf16 rounding, not in
# Q^T Q. A rank-deficient panel is not carried (NaN), as under
# ops.panel_qr=cholqr2.

_QR_BASE = 256      # widest panel factored without splitting its columns
_F32 = jnp.float32


def _mm(a, b):
    """An update's product, at the configured precision."""
    return jnp.matmul(a, b, preferred_element_type=_F32, precision=_prec())


def _mmh(a, b):
    """A product T or a panel's R is made of: always full float32."""
    return jnp.matmul(a, b, preferred_element_type=_F32,
                      precision="highest")


def _larft(G):
    """T of the block reflector I - V T V^T from G = V^T V."""
    b = G.shape[0]
    t_inv = jnp.triu(G) - 0.5 * jnp.diag(jnp.diagonal(G))
    return jax.lax.linalg.triangular_solve(
        t_inv, jnp.eye(b, dtype=_F32), left_side=True, lower=False)


def _merge_t(T1, T2, V1tV2):
    """T of Q1 Q2 from the halves' and V1^T V2."""
    T12 = -_mmh(T1, _mmh(V1tV2, T2))
    return jnp.concatenate(
        [jnp.concatenate([T1, T12], axis=1),
         jnp.concatenate([jnp.zeros(T12.T.shape, _F32), T2], axis=1)],
        axis=0)


def _lu_signed(W):
    """Unpivoted LU of S + W with S = diag(+-1) chosen as it goes, each
    sign its pivot's own, so that every pivot is at least 1 in size
    (the modified LU of Ballard et al.; signs fixed beforehand from
    diag W leave a square orthogonal block of the wrong determinant
    with a pivot of 0) -> (packed LU, the signs)."""
    n = W.shape[0]
    idx = jnp.arange(n)

    def step(i, carry):
        M, s = carry
        si = jnp.where(M[i, i] >= 0, 1.0, -1.0).astype(_F32)
        piv = M[i, i] + si
        col = jnp.where(idx > i, M[:, i] / piv, 0.0)
        row = jnp.where(idx > i, M[i, :], 0.0)
        M = M - col[:, None] * row[None, :]
        M = M.at[:, i].set(jnp.where(idx > i, col, M[:, i]))
        return M.at[i, i].set(piv), s.at[i].set(si)

    return jax.lax.fori_loop(0, n, step, (W, jnp.ones((n,), _F32)))


def _qr_householder(P):
    """:func:`_qr_base` of a panel under twice as tall as wide, by XLA's
    own Householder QR (LAPACK's ``geqrf``: V and the ``tau``): a square
    block of a random tile is singular to float32 once in a few thousand
    (kappa over 1e6: four in a run of the 32768-cell), and there a Gram
    matrix's Cholesky gives NaN whatever its shift. T from V and tau,
    ``T^-1 = striu(V^T V) + diag(1/tau)``, as ``(I + D S)^-1 D``: a
    ``tau`` of 0 (a column with nothing under its diagonal) is no 1/0."""
    b = P.shape[1]
    h, tau = jnp.linalg.qr(P, mode="raw")           # h: LAPACK's, transposed
    a = h.T
    eye = jnp.eye(b, dtype=_F32)
    V = jnp.tril(a, -1) + jnp.eye(*a.shape, dtype=_F32)
    S = jnp.triu(_mmh(V.T, V), 1)
    T = jax.lax.linalg.triangular_solve(
        eye + tau[:, None] * S, jnp.diag(tau), left_side=True, lower=False,
        unit_diagonal=True)
    return V, T, jnp.triu(a[:b])


def _qr_base(P):
    """Thin QR of P (m x b, m >= b) -> (V unit lower trapezoid, T, R)."""
    m, b = P.shape
    if m < 2 * b:
        return _qr_householder(P)
    eye = jnp.eye(b, dtype=_F32)
    Q, R = P, eye
    # a panel at least twice as tall as wide: two shifted rounds bring
    # any kappa float32 can tell from singular to where the two plain
    # ones end at rounding level (full column rank asked, as
    # ops.panel_qr=cholqr2 asks it)
    for shift in (2e-5, 2e-5, 0.0, 0.0):
        G = _mmh(Q.T, Q)
        if shift:
            G = G + (shift * jnp.trace(G)) * eye
        L = jnp.linalg.cholesky(G)
        Q = jax.lax.linalg.triangular_solve(
            L, Q, left_side=False, lower=True, transpose_a=True)
        R = _mmh(L.T, R)
    LU, s = _lu_signed(-Q[:b])
    V = jnp.tril(LU, -1) + eye
    if m > b:
        V = jnp.concatenate([V, jax.lax.linalg.triangular_solve(
            jnp.triu(LU), -Q[b:], left_side=False, lower=False)], axis=0)
    return V, _larft(_mmh(V.T, V)), jnp.triu(s[:, None] * R)


def _qr_rec(P):
    """:func:`_qr_base` of a panel of any width, its columns halved
    down to ``_QR_BASE`` (Elmroth-Gustavson)."""
    b = P.shape[1]
    if b <= _QR_BASE or b % 2:
        return _qr_base(P)
    h = b // 2
    V1, T1, R11 = _qr_rec(P[:, :h])
    C = P[:, h:]
    C = C - _mm(V1, _mm(T1.T, _mm(V1.T, C)))
    V2, T2, R22 = _qr_rec(C[h:])
    T = _merge_t(T1, T2, _mmh(V1[h:].T, V2))
    V = jnp.concatenate(
        [V1, jnp.concatenate([jnp.zeros((h, b - h), _F32), V2], axis=0)],
        axis=1)
    R = jnp.concatenate(
        [jnp.concatenate([R11, C[:h]], axis=1),
         jnp.concatenate([jnp.zeros((b - h, h), _F32), R22], axis=1)],
        axis=0)
    return V, T, R


def _tsqr_base(R, A):
    """[R; A] = Q [R'; 0], R upper triangular (b x b), A (m x b), with
    Q = I - [I; V2] T [I; V2]^T -> (V2, T, R')."""
    L = jnp.linalg.cholesky(_mmh(R.T, R) + _mmh(A.T, A))
    s = jnp.where(jnp.diagonal(R) >= 0, -1.0, 1.0).astype(_F32)
    Rn = jnp.triu(s[:, None] * L.T)
    V2 = jax.lax.linalg.triangular_solve(
        jnp.triu(R) - Rn, A, left_side=False, lower=False)
    G = jnp.eye(R.shape[0], dtype=_F32) + _mmh(V2.T, V2)
    return V2, _larft(G), Rn


def _tsqr_rec(R, A):
    b = R.shape[0]
    if b <= _QR_BASE or b % 2:
        return _tsqr_base(R, A)
    h = b // 2
    V1, T1, R11 = _tsqr_rec(R[:h, :h], A[:, :h])
    W = _mm(T1.T, R[:h, h:] + _mm(V1.T, A[:, h:]))
    V2, T2, R22 = _tsqr_rec(R[h:, h:], A[:, h:] - _mm(V1, W))
    T = _merge_t(T1, T2, _mmh(V1.T, V2))
    Rn = jnp.concatenate(
        [jnp.concatenate([R11, R[:h, h:] - W], axis=1),
         jnp.concatenate([jnp.zeros((b - h, h), _F32), R22], axis=1)],
        axis=0)
    return jnp.concatenate([V1, V2], axis=1), T, Rn


def geqrt_tile(A, ib: int):
    """GEQRT: A = Q R -> (the tile with R in its upper triangle and V,
    unit lower, under it; T, ib x nb)."""
    nb = A.shape[1]
    Af = jnp.asarray(A, _F32)
    cols, Ts = [], []
    for o in range(0, nb, ib):
        V, T, R = _qr_rec(Af[o:, o:o + ib])
        packed = jnp.tril(V, -1).at[:ib].add(R)
        cols.append(jnp.concatenate([Af[:o, o:o + ib], packed], axis=0))
        Ts.append(T)
        if o + ib < nb:
            C = Af[o:, o + ib:]
            Af = Af.at[o:, o + ib:].set(
                C - _mm(V, _mm(T.T, _mm(V.T, C))))
    return (jnp.concatenate(cols, axis=1).astype(A.dtype),
            jnp.concatenate(Ts, axis=1).astype(A.dtype))


def unmqr_tile(V, T, C):
    """UNMQR: C <- Q^T C, Q as :func:`geqrt_tile` left it (``V`` its
    tile: what lies on and above the diagonal is R and is not read)."""
    ib, nb = T.shape
    Cf = jnp.asarray(C, _F32)
    for o in range(0, nb, ib):
        Vj = jnp.tril(jnp.asarray(V[o:, o:o + ib], _F32), -1) + \
            jnp.eye(V.shape[0] - o, ib, dtype=_F32)
        Tj = jnp.asarray(T[:, o:o + ib], _F32)
        Cf = Cf.at[o:].add(-_mm(Vj, _mm(Tj.T, _mm(Vj.T, Cf[o:]))))
    return Cf.astype(C.dtype)


def tsqrt_tile(A1, A2, ib: int):
    """TSQRT: [R; A2] = Q [R'; 0] with R = triu(A1) -> (A1 with R' in
    its upper triangle, what lies under it kept; V2; T, ib x nb)."""
    nb = A1.shape[0]
    Rf, Af = jnp.triu(jnp.asarray(A1, _F32)), jnp.asarray(A2, _F32)
    Vs, Ts = [], []
    for o in range(0, nb, ib):
        J = slice(o, o + ib)
        V2, T, Rjj = _tsqr_rec(Rf[J, J], Af[:, J])
        Rf = Rf.at[J, J].set(Rjj)
        Vs.append(V2)
        Ts.append(T)
        if o + ib < nb:
            W = _mm(T.T, Rf[J, o + ib:] + _mm(V2.T, Af[:, o + ib:]))
            Rf = Rf.at[J, o + ib:].add(-W)
            Af = Af.at[:, o + ib:].add(-_mm(V2, W))
    return ((jnp.tril(jnp.asarray(A1, _F32), -1) + Rf).astype(A1.dtype),
            jnp.concatenate(Vs, axis=1).astype(A2.dtype),
            jnp.concatenate(Ts, axis=1).astype(A2.dtype))


def tsmqr_tile(V2, T, C1, C2):
    """TSMQR: [C1; C2] <- Q^T [C1; C2], Q as :func:`tsqrt_tile` left it."""
    ib, nb = T.shape
    C1f, C2f = jnp.asarray(C1, _F32), jnp.asarray(C2, _F32)
    for o in range(0, nb, ib):
        J = slice(o, o + ib)
        Vj, Tj = jnp.asarray(V2[:, J], _F32), jnp.asarray(T[:, J], _F32)
        W = _mm(Tj.T, C1f[J] + _mm(Vj.T, C2f))
        C1f = C1f.at[J].add(-W)
        C2f = C2f - _mm(Vj, W)
    return C1f.astype(C1.dtype), C2f.astype(C2.dtype)


# ---- tile LU by incremental pivoting (DPLASMA dgetrf_incpiv) -----------
# PLASMA's four core_blas kernels (zgetrf_incpiv, zgessm, ztstrf, zssssm)
# over a tile pair: pivots inside the diagonal tile, then pairwise
# between the diagonal tile's U and each tile under it, ``ib`` columns at
# a time. A block's factorization (the pivot search, the scaling by the
# pivot, the rank-1 updates) is partial pivoting at full float32: in
# TSTRF, where the stack [the block's ib rows of U; the lower tile's
# column block] is float32 with ib a multiple of 128 (the cell's 2176 x
# 128), a panel kernel of this module that keeps the stack in VMEM from
# the first pivot step to the last (``_lu_panel``, below); at any other
# shape, and for GETRF's whole tile, XLA's ``lax.linalg.lu``. Both give
# the same factors and the same interchanges (the lowest index among
# equal magnitudes); which one a block was traced through is counted in
# ``LU_BLOCKS_TRACED``. What is applied to the columns to the right
# (L11^-1 and the product with the multipliers) is full float32 too,
# WHATEVER ``ops.matmul_precision`` says: a pivoted
# LU's entries grow (about n^(2/3) under partial pivoting, more under
# pairwise pivoting), every rounding of an update is carried through the
# rest of the elimination, and a bf16 pass's 2^-9 times that growth leaves
# no digit: N = 32768 in 2048-tiles read a solve residual near 1 on the
# v5e at default precision (PERF.md section 6, PR 39), where the QR and
# Cholesky updates, which do not grow, read 1e-2. A device
# trace splits a kernel's time by three scopes: ``parsec:lu_pivot`` (the
# pivoted factorization of a block: serial), ``parsec:lu_swap`` (row
# exchanges: memory-bound) and ``parsec:lu_update`` (solve and product).
#
# Pivots are int32 tiles of 1 x nb. A diagonal tile's holds the
# permutation (row i of P·A is row ``perm[i]`` of A): in-tile interchanges
# go anywhere, and a permutation is applied in one gather. A pair's holds
# LAPACK's interchange indices block by block: at step j of block b the
# stack's rows j and ``ipiv[b·ib + j]`` were exchanged, the stack being
# [the block's ib rows of U; the nb rows of the lower tile]. A zero under
# U's diagonal never wins a search, so an index is j itself or ib + r,
# row r of the lower tile, and the block's exchanges come to one gather
# of ib rows and one scatter (``_pair_moves``): no loop over the steps.

_I32 = jnp.int32


def _unit_lower_solve(L, C, base: int = 256):
    """``L⁻¹·C`` for the unit lower triangle of ``L`` (what lies on and
    above its diagonal is not read): halves down to ``base``, the
    off-diagonal block a product."""
    n = L.shape[0]
    if n <= base or n % 2:
        return jax.lax.linalg.triangular_solve(
            L, C, left_side=True, lower=True, unit_diagonal=True)
    h = n // 2
    top = _unit_lower_solve(L[:h, :h], C[:h], base)
    return jnp.concatenate(
        [top, _unit_lower_solve(L[h:, h:], C[h:] - _mmh(L[h:, :h], top),
                                base)], axis=0)


def _pair_moves(piv, ib: int, nb: int):
    """One block's interchanges as moves: ``src[j]`` the stacked row the
    block's row j of U ends with (j: its own; under ib: another U row,
    swapped down earlier and now back; else ib + a lower row), ``dst[j]``
    the lower row U's row j ends in (nb: none, dropped by the scatter)."""
    j = jnp.arange(ib, dtype=_I32)
    low = piv >= ib
    same = (piv[:, None] == piv[None, :]) & low[:, None]
    before = same & (j[None, :] < j[:, None])
    prev = jnp.max(jnp.where(before, j[None, :], -1), axis=1)
    src = jnp.where(low, jnp.where(prev >= 0, prev, piv), j)
    last = ~jnp.any(same & (j[None, :] > j[:, None]), axis=1)
    return src, jnp.where(low & last, piv - ib, nb)


def _pair_swap(top, bot, piv):
    """Exchange rows between ``top`` (a block's ib rows of the upper
    operand) and ``bot`` (the lower tile) as the block's ``piv`` says."""
    ib, nb = top.shape[0], bot.shape[0]
    src, dst = _pair_moves(piv, ib, nb)
    own = src < ib
    new_top = jnp.where(own[:, None], top[jnp.where(own, src, 0)],
                        bot[jnp.where(own, 0, src - ib)])
    return new_top, bot.at[dst].set(top, mode="drop")


def getrf_incpiv_tile(A):
    """GETRF: P·A = L·U, partial pivoting inside the tile -> (the tile
    with U in its upper triangle and L, unit lower, under it; the
    permutation, 1 x nb int32)."""
    with jax.named_scope("parsec:lu_pivot"):
        lu, _, perm = jax.lax.linalg.lu(jnp.asarray(A, _F32))
    return lu.astype(A.dtype), perm.astype(_I32)[None, :]


def gessm_tile(L, P, C):
    """GESSM: C <- L⁻¹·P·C, L and P as :func:`getrf_incpiv_tile` left
    them (``L`` its tile: U is not read)."""
    with jax.named_scope("parsec:lu_swap"):
        Cf = jnp.asarray(C, _F32)[P[0]]
    with jax.named_scope("parsec:lu_update"):
        return _unit_lower_solve(jnp.asarray(L, _F32), Cf).astype(C.dtype)


def _unit_lower_inverse(L):
    """The inverse of a unit lower block, at full float32."""
    return jax.lax.linalg.triangular_solve(
        L, jnp.eye(L.shape[0], dtype=L.dtype), left_side=True, lower=True,
        unit_diagonal=True)


# ---- a block's stack factored in VMEM -----------------------------------
# XLA's ``LuDecompositionBlock`` takes 1.66 us a pivot step on a 2176 x
# 128 stack (0.22 us and 0.66 ns a row: PERF.md section 6, PR 41). The
# kernel below takes 0.57: it holds the stack TRANSPOSED, ``t[c, k, i]`` =
# entry (128 c + i, k), so that a column of the stack is a row over the
# lanes (a search is a few VPU operations and two rounds of reductions
# across the lanes, 80 ns each on a v5e whatever they reduce), a step's
# multipliers are broadcast over the sublanes for nothing, and the pivot
# row's entries, one to a column, are one lane of 128-column blocks: a
# lane gather each. No row moves while the steps run: a row keeps its
# place and ``pos`` says which position of LAPACK's interchanged stack
# it holds; the interchanges are applied once, to the factored stack in
# its natural layout, where a row is a sublane and an exchange two loads
# and two stores.

_LANES = 128
# position and row of a candidate in one number (the lowest position
# among equal magnitudes is one reduction): 12 bits each, so that a
# float32 holds it exactly (a v5e reduces floats across lanes in half
# the time it takes for integers) and has its sign bit left for the
# entry's
_PACK_BITS = 12
_PACK = 1 << _PACK_BITS
# rows x columns x 4 bytes the panel takes: input, output and the
# transposed copy are in VMEM at once (a v5e core has 128 MiB)
_PANEL_BYTES = 8 << 20

# how many blocks ``tstrf_tile`` was TRACED with through each
# factorization (the choice is made from the stack's shape at trace
# time, so these count traces, not launches)
LU_BLOCKS_TRACED = {"vmem_panel": 0, "xla_lu": 0}


def _tree(op, items):
    """``op`` over ``items`` pairwise: a chain log2(n) deep, not n."""
    items = list(items)
    while len(items) > 1:
        items = [op(*items[i:i + 2]) if i + 1 < len(items) else items[i]
                 for i in range(0, len(items), 2)]
    return items[0]


def _pallas():
    """Pallas and its TPU side, at first use: an import of a second on
    the chip's host that no other kernel of this module pays for."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl, pltpu


def _lu_panel_kernel(x_ref, lu_ref, piv_ref, t_ref, u_ref, pos_ref):
    pl, _ = _pallas()
    nblk, w, _ = t_ref.shape
    for c in range(nblk):
        t_ref[c] = x_ref[c * _LANES:(c + 1) * _LANES, :].T
    blk = jax.lax.broadcasted_iota(_I32, (nblk, 1, _LANES), 0)
    row = blk * _LANES + jax.lax.broadcasted_iota(
        _I32, (nblk, 1, _LANES), 2)
    col = jax.lax.broadcasted_iota(_I32, (w, _LANES), 0)
    pos_ref[...] = jnp.broadcast_to(row, pos_ref.shape)

    def over(op, reduce, v):
        """(nblk, 1, LANES) -> (1, 1, 1): across the blocks on the VPU,
        then ONE reduction across the lanes."""
        return reduce(_tree(op, [v[c] for c in range(nblk)]), axis=1,
                      keepdims=True)[None]

    def step(j, carry):
        pos = pos_ref[:, 0:1, :]
        c = t_ref[:, pl.ds(j, 1), :]             # column j of the stack
        active = pos >= j                        # not yet a pivot row
        size = jnp.where(active, jnp.abs(c), -1.0)
        top = over(jnp.maximum, jnp.max, size)
        # the first of the largest: position and row in one float32, the
        # greater the earlier the position, the entry's sign on it; a
        # maximum and a minimum across the lanes (issued together) say
        # which row it is and give the pivot its sign
        rank = float(_PACK * _PACK) - (pos * _PACK + row).astype(_F32)
        mark = jnp.where(size == top, jnp.where(c < 0, -rank, rank), 0.0)
        plus = over(jnp.maximum, jnp.max, mark)
        minus = -over(jnp.minimum, jnp.min, mark)
        pivot = jnp.where(minus > plus, -top, top)
        first = (float(_PACK * _PACK) - jnp.maximum(plus, minus)).astype(_I32)
        q, p = first >> _PACK_BITS, first & (_PACK - 1)   # position, row
        is_p = row == p
        low = active & ~is_p
        # a column of zeros keeps its zeros: no 0/0
        mult = jnp.where(low, c / jnp.where(pivot == 0, 1.0, pivot), 0.0)
        t_ref[:, pl.ds(j, 1), :] = jnp.where(low, mult, c)
        # the row at position j goes where the pivot row was
        pos_ref[:, 0:1, :] = jnp.where(is_p, j, jnp.where(pos == j, q, pos))
        piv_ref[0, j] = q[0, 0, 0]
        at = p[0, 0, 0]
        # the pivot row, an entry a column, every lane the same: the
        # rank-1 update's other factor
        u_ref[...] = jnp.where(col > j, jnp.take_along_axis(
            t_ref[at // _LANES], jnp.full((w, _LANES), at % _LANES, _I32),
            axis=1), 0.0)
        mults = jnp.broadcast_to(mult, (nblk, 8, _LANES))

        def update(r, carry):                    # columns 8r .. 8r + 7
            k = pl.ds(pl.multiple_of(r * 8, 8), 8)
            t_ref[:, k, :] = t_ref[:, k, :] - u_ref[k, :][None] * mults
            return carry

        return jax.lax.fori_loop(j // 8, w // 8, update, carry)

    jax.lax.fori_loop(0, w, step, 0)
    for c in range(nblk):
        lu_ref[c * _LANES:(c + 1) * _LANES, :] = t_ref[c].T

    def interchange(j, carry):
        q = piv_ref[0, j]
        top, low = lu_ref[pl.ds(j, 1), :], lu_ref[pl.ds(q, 1), :]
        lu_ref[pl.ds(j, 1), :] = low
        lu_ref[pl.ds(q, 1), :] = top
        return carry

    jax.lax.fori_loop(0, w, interchange, 0)


def _lu_panel_takes(rows: int, w: int, dtype) -> bool:
    """The shapes the VMEM panel is built for, read off the stack."""
    return (dtype == _F32 and w % _LANES == 0 and rows % _LANES == 0
            and rows <= _PACK and rows * w * 4 <= _PANEL_BYTES)


def _lu_panel(stack):
    """``lax.linalg.lu``'s first two results for a float32 stack the
    panel takes: the factored stack (U over the unit lower L) and
    LAPACK's interchange indices, 0-based int32: at step j rows j and
    ``piv[j]`` were exchanged, ``piv[j]`` the lowest index among the
    entries of largest magnitude in column j from row j on. Interpreted
    iff the run was started on the CPU platform
    (``ops/flash_attention.py``'s rule)."""
    from ..utils.jax_platform import cpu_requested
    return _lu_panel_call(stack, cpu_requested())


# jitted, so that a TSTRF's sixteen blocks are one kernel traced and
# lowered once (0.1 s each on the chip's host otherwise)
@functools.partial(jax.jit, static_argnums=1)
def _lu_panel_call(stack, interpret: bool):
    pl, pltpu = _pallas()
    rows, w = stack.shape
    nblk = rows // _LANES
    lu, piv = pl.pallas_call(
        _lu_panel_kernel,
        out_shape=(jax.ShapeDtypeStruct((rows, w), _F32),
                   jax.ShapeDtypeStruct((1, w), _I32)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.SMEM)),
        scratch_shapes=[pltpu.VMEM((nblk, w, _LANES), _F32),
                        pltpu.VMEM((w, _LANES), _F32),
                        pltpu.VMEM((nblk, 8, _LANES), _I32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=3 * rows * w * 4 + (8 << 20)),
        interpret=interpret, name="parsec_lu_panel")(stack)
    return lu, piv[0]


def _block_lu(stack):
    """A block's stack factored: the VMEM panel where it takes the
    shape, XLA's LU elsewhere; (factors, interchanges)."""
    if _lu_panel_takes(*stack.shape, stack.dtype):
        LU_BLOCKS_TRACED["vmem_panel"] += 1
        return _lu_panel(stack)
    LU_BLOCKS_TRACED["xla_lu"] += 1
    lu, piv, _ = jax.lax.linalg.lu(stack)
    return lu, piv


def tstrf_tile(U, A, ib: int):
    """TSTRF: the partial-pivoting LU of the stack [U; A], U the upper
    triangle of ``U``, ``ib`` columns at a time -> (U'; the multipliers
    L21 in A's place; L, ib x nb: the blocks' L11, unit lower, side by
    side; the interchanges, 1 x nb int32; W, ib x nb: the blocks' L11⁻¹
    side by side, which is no part of the factored form: the pair's
    SSSSMs apply it as a product where each would else invert L11's
    diagonal blocks for itself)."""
    nb = U.shape[0]
    Uf, Af = jnp.triu(jnp.asarray(U, _F32)), jnp.asarray(A, _F32)
    L21s, L11s, pivs, Ws = [], [], [], []
    for o in range(0, nb, ib):
        J = slice(o, o + ib)
        with jax.named_scope("parsec:lu_pivot"):
            lu, piv = _block_lu(
                jnp.concatenate([Uf[J, J], Af[:, J]], axis=0))
            L11 = jnp.tril(lu[:ib], -1) + jnp.eye(ib, dtype=_F32)
            W = _unit_lower_inverse(L11)
        piv = piv.astype(_I32)
        Uf = Uf.at[J, J].set(jnp.triu(lu[:ib]))
        L21s.append(lu[ib:])
        L11s.append(L11)
        pivs.append(piv)
        Ws.append(W)
        if o + ib < nb:
            with jax.named_scope("parsec:lu_swap"):
                top, bot = _pair_swap(Uf[J, o + ib:], Af[:, o + ib:], piv)
            with jax.named_scope("parsec:lu_update"):
                top = _mmh(W, top)
                Uf = Uf.at[J, o + ib:].set(top)
                Af = Af.at[:, o + ib:].set(bot - _mmh(lu[ib:], top))
    return (Uf.astype(U.dtype),
            jnp.concatenate(L21s, axis=1).astype(A.dtype),
            jnp.concatenate(L11s, axis=1).astype(A.dtype),
            jnp.concatenate(pivs)[None, :],
            jnp.concatenate(Ws, axis=1).astype(A.dtype))


# jitted, so that the members of a launch of four, and a process's launch
# sizes, are one function traced and lowered once: a second of a
# process's set-up on the chip's host, which is what Pallas's import
# costs TSTRF's program there (PERF.md section 6, PR 41); XLA inlines
# the calls, a lone SSSSM's program is the parent's to the instruction
@jax.jit
def ssssm_tile(A1, A2, W, L21, P):
    """SSSSM: [A1; A2] <- the pair's transformation applied, W (the
    blocks' L11⁻¹), L21 and P as :func:`tstrf_tile` left them: block by
    block, the interchanges between A1's ib rows and A2, then A1's rows
    <- L11⁻¹·them and A2 <- A2 - L21·them."""
    ib, nb = W.shape
    A1f, A2f = jnp.asarray(A1, _F32), jnp.asarray(A2, _F32)
    Wf, L21f = jnp.asarray(W, _F32), jnp.asarray(L21, _F32)
    tops = []
    for o in range(0, nb, ib):
        J = slice(o, o + ib)
        with jax.named_scope("parsec:lu_swap"):
            top, A2f = _pair_swap(A1f[J], A2f, P[0, J])
        with jax.named_scope("parsec:lu_update"):
            top = _mmh(Wf[:, J], top)
            A2f = A2f - _mmh(L21f[:, J], top)
        tops.append(top)
    return (jnp.concatenate(tops, axis=0).astype(A1.dtype),
            A2f.astype(A2.dtype))


# ---- LU with partial pivoting over a whole panel (dgetrf_1d) ------------
# The panel task factors the STACK of a block column's tiles: the pivot
# of every column is the largest entry of the whole remaining column,
# whichever tile holds it, so every multiplier is at most 1 over the
# whole column and the factored form is LAPACK's dgetrf's. The pivots
# are LAPACK's interchange indices, 0-based from the panel's first row:
# at step j rows j and ipiv[j] >= j of the panel were exchanged. The
# same three scopes split a kernel's device time as above.

# XLA's ``LuDecompositionBlock`` factors a stack in scoped VMEM and takes
# two to three times the stack's bytes of it: 48 MiB for the 32768 x 128
# of a 16-tile panel, where the compiler's default allows 16 (a v5e core
# has 128 MiB). A program that factors such a stack is compiled with this
# (``Chore.compiler_options``; tests/test_panel_partition.py compiles the
# tallest for a described v5e).
PANEL_COMPILER_OPTIONS = {"xla_tpu_scoped_vmem_limit_kib": 65536}


def _swap_moves(piv, n: int):
    """LAPACK's interchanges j <-> ``piv[j]`` (j = 0..n-1 in turn,
    ``piv[j]`` >= j, rows of the stack they act on) as row moves, without
    a loop over the steps: ``(top, low, frm)`` with row j of the result
    (j < n) the stack's row ``top[j]``, and its row ``low[j]`` the
    stack's row ``frm[j]`` where ``low[j]`` >= 0 (-1: no move; a row
    under the first n that is exchanged several times is written by the
    last). ``frm`` < n always: what goes down comes from the first n
    rows. The row that lies at position j when step j begins came there
    by a chain of earlier exchanges with positions under n; the chains
    are followed by pointer doubling."""
    j = jnp.arange(n, dtype=_I32)
    earlier = j[None, :] < j[:, None]
    # the last step before j that exchanged position j with its own
    came = jnp.max(jnp.where((piv[None, :] == j[:, None]) & earlier,
                             j[None, :], -1), axis=1)
    lies = jnp.where(came >= 0, came, j)
    for _ in range(max(n - 1, 1).bit_length()):
        lies = lies[lies]
    same = piv[None, :] == piv[:, None]
    prev = jnp.max(jnp.where(same & earlier, j[None, :], -1), axis=1)
    top = jnp.where(prev >= 0, lies[jnp.maximum(prev, 0)], piv)
    last = ~jnp.any(same & (j[None, :] > j[:, None]), axis=1)
    return top, jnp.where((piv >= n) & last, piv, -1), lies


def _stack_swap(S, piv, o, n: int):
    """The interchanges ``piv`` (relative to row ``o``, which may be
    traced) applied to the rows of the stack ``S`` from ``o`` on: 2n rows
    move, the stack stays where it lies."""
    top, low, frm = _swap_moves(piv, n)
    head = jax.lax.dynamic_slice_in_dim(S, o, n, axis=0)
    new_head = S[o + top]
    S = S.at[jnp.where(low >= 0, o + low, S.shape[0])].set(
        head[frm], mode="drop")
    return jax.lax.dynamic_update_slice_in_dim(S, new_head, o, axis=0)


def getrf_panel_tiles(tiles, ib: int):
    """GETRF of dgetrf_1d: P·[tiles stacked] = L·U by partial pivoting
    over the whole stack, ``ib`` columns at a time -> (the tiles with U
    in the first one's upper triangle and the multipliers, each at most
    1 in size, under it and in the others; LAPACK's interchange indices,
    1 x nb int32, 0-based from the stack's first row). A block's
    pivoted factorization is :func:`_block_lu`'s: the VMEM panel where
    the stack fits it, XLA's ``lax.linalg.lu`` on the tall ones.

    The inner blocks are ONE rolled loop whose body masks what the
    blocks before have finished (the block column is handed to the
    factorization with its finished rows zeroed and last: a zero never
    wins a search and stays zero; the trailing product runs over the
    whole stack with the finished rows and columns zeroed): a sixteenth
    of the program text of sixteen blocks unrolled with their own shapes
    (8 MiB against 73 for a 16-tile panel, one such program a list
    length) and a quarter of the compile time, for twice the panel's
    own products, which are a twentieth of the factorization's (PERF.md
    section 6, PR 43)."""
    nb, n = tiles[0].shape[0], len(tiles)
    S = jnp.concatenate([jnp.asarray(t, _F32) for t in tiles], axis=0) \
        if n > 1 else jnp.asarray(tiles[0], _F32)
    r = jnp.arange(S.shape[0], dtype=_I32)[:, None]
    c = jnp.arange(nb, dtype=_I32)[None, :]

    def block(b, carry):
        S, pivs = carry
        o = b * ib
        with jax.named_scope("parsec:lu_pivot"):
            col = jax.lax.dynamic_slice_in_dim(S, o, ib, axis=1)
            lu, piv = _block_lu(jnp.roll(jnp.where(r >= o, col, 0.0), -o,
                                         axis=0))
            piv = piv.astype(_I32)
        pivs = jax.lax.dynamic_update_slice(pivs, piv + o, (o,))
        with jax.named_scope("parsec:lu_swap"):
            S = _stack_swap(S, piv, o, ib)
        back = jnp.roll(lu, o, axis=0)
        S = jax.lax.dynamic_update_slice_in_dim(
            S, jnp.where(r >= o, back, jax.lax.dynamic_slice_in_dim(
                S, o, ib, axis=1)), o, axis=1)
        with jax.named_scope("parsec:lu_update"):
            right = c >= o + ib
            top = jax.lax.dynamic_slice_in_dim(S, o, ib, axis=0)
            u12 = jnp.where(right, _unit_lower_solve(lu[:ib], top), 0.0)
            S = jax.lax.dynamic_update_slice_in_dim(
                S, jnp.where(right, u12, top), o, axis=0)
            S = S - _mmh(jnp.where(r >= o + ib, back, 0.0), u12)
        return S, pivs

    S, pivs = jax.lax.fori_loop(0, nb // ib, block,
                                (S, jnp.zeros((nb,), _I32)))
    return ([S[t * nb:(t + 1) * nb].astype(tiles[t].dtype)
             for t in range(n)], pivs[None, :])


def laswp_tiles(tiles, ipiv):
    """LAPACK's ``dlaswp`` over the stack of ``tiles``: the interchanges
    ``ipiv`` (1 x nb int32, as :func:`getrf_panel_tiles` left them)
    applied to the stack's rows (:func:`_stack_swap`: the nb rows that
    come to the first tile are gathered from wherever they lie, the rows
    that leave it, all from the first tile, are scattered to where they
    go), and the stack cut into its tiles again."""
    nb, r = tiles[0].shape[0], len(tiles)
    with jax.named_scope("parsec:lu_swap"):
        S = _stack_swap(jnp.concatenate(tiles, axis=0) if r > 1
                        else tiles[0], ipiv[0].astype(_I32), 0, nb)
        return [S[t * nb:(t + 1) * nb] for t in range(r)]


@jax.jit
def swptrsm_tiles(L, ipiv, tiles):
    """SWPTRSM of dgetrf_1d: the panel's interchanges applied to a block
    column's ``tiles``, then its first tile <- L⁻¹ · it for the unit
    lower triangle of ``L`` (the panel's diagonal tile)."""
    out = laswp_tiles(tiles, ipiv)
    with jax.named_scope("parsec:lu_update"):
        out[0] = _unit_lower_solve(
            jnp.asarray(L, _F32), jnp.asarray(out[0], _F32)).astype(
                tiles[0].dtype)
    return out


@jax.jit
def gemm_full_tile(A, B, C):
    """C − A·B at full float32 whatever ``ops.matmul_precision`` says:
    a pivoted LU's trailing update (see the note above on growth)."""
    with jax.named_scope("parsec:lu_update"):
        return (jnp.asarray(C, _F32) - _mmh(
            jnp.asarray(A, _F32), jnp.asarray(B, _F32))).astype(C.dtype)
