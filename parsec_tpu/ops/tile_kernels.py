"""Tile-level kernels (jnp; MXU-friendly shapes).

These are the FLOP-carrying bodies of the shipped linear-algebra
taskpools — the role CUDA kernels in user .jdf BODY sections play in the
reference (e.g. DPLASMA's dpotrf/dgemm tiles). All operate on full
(mb × nb) tiles; ``preferred_element_type=float32`` keeps MXU accumulation
in f32 even for bf16 tiles.
"""

from __future__ import annotations

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


# ---- tiled-QR kernels (DPLASMA dgeqrf tile operations) -----------------
# Functional variant: the reference's Householder kernels (GEQRT/TSQRT/
# UNMQR/TSMQR with compact V+T storage) are re-expressed with explicit
# per-tile orthogonal factors — Q values flow between tasks as tiles,
# which is what XLA can batch; compact-V storage is a memory optimization
# tied to in-place BLAS that functional dataflow doesn't need.

def geqrt_tile(A):
    """Diagonal-tile QR: A = Q·R → (Q, R)."""
    Q, R = jnp.linalg.qr(A.astype(jnp.float32), mode="complete")
    return Q.astype(A.dtype), R.astype(A.dtype)


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


def unmqr_tile(Q, C):
    """C ← Qᵀ·C (apply a diagonal-tile factor to a row-panel tile)."""
    out = jnp.matmul(Q.T, C, preferred_element_type=jnp.float32,
                     precision=_prec())
    return out.astype(C.dtype)


def tsqrt_tile(R, A):
    """Triangular-on-top-of-square QR: [R; A] = Q₂·R' → (Q₂, R').
    Q₂ is the full (2nb × 2nb) factor; R' the updated nb × nb triangle."""
    nb = R.shape[0]
    S = jnp.concatenate([R, A], axis=0).astype(jnp.float32)
    Q2, Rfull = jnp.linalg.qr(S, mode="complete")
    return Q2.astype(R.dtype), Rfull[:nb].astype(R.dtype)


def tsmqr_tile(Q2, C1, C2):
    """Apply a TSQRT factor to a stacked pair: [C1; C2] ← Q₂ᵀ·[C1; C2]."""
    nb = C1.shape[0]
    S = jnp.concatenate([C1, C2], axis=0)
    out = jnp.matmul(Q2.T, S, preferred_element_type=jnp.float32,
                     precision=_prec()).astype(C1.dtype)
    return out[:nb], out[nb:]
