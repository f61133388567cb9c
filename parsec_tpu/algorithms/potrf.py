"""Tiled Cholesky factorization (right-looking, lower) as a PTG taskpool.

The DPLASMA dpotrf_L equivalent — the reference's headline workload class
(BASELINE.md: "DPLASMA-style tiled Cholesky ≥65% of peak"). Task classes
and dataflow mirror the classic dpotrf JDF:

    POTRF(k):  T = chol(A[k,k] after k SYRK updates)
    TRSM(m,k): C = A[m,k] · T^{-T}
    SYRK(m,k): diag update A[m,m] -= C·Cᵀ            (k-th update)
    GEMM(m,n,k): A[m,n] -= A[m,k]·A[n,k]ᵀ            (k-th update)

Every flow carries its logical tile (FlowSpec.tile), so the taskpool runs
on the host runtime AND on the compiled wavefront/SPMD executors.

The factorization runs in the matrix's own storage, as upstream's does
(a task updates its tile's data copy in place): every class writes its
result to its tile of ``A``, the updates of SYRK and GEMM too. A jax.Array
cannot be overwritten, so on the host runtime an update that only
travelled on to the next task would leave the collection holding the
version before it, and every trailing tile would be on the chip twice
until its TRSM or POTRF wrote the factor back (1.7 times the matrix at
N=49152 in 2048-tiles, and not the same twice: PERF.md section 6, PR 27).
And every body names its RW flow as the last reading of its version
(``Chore.donates``), so a chip module's program writes the update into
the buffer the tile lies in: in place on the device too, not a new
buffer that replaces the old one at the task's release (PR 36). The
tile of the collection is a deleted array from the update's launch to
its write-back: read the factor from ``A`` after the pool, never from
arrays kept aside.
"""

from __future__ import annotations

from ..compiled.panels import (PanelPartition, SegRead, SegStep, SegWrite,
                               bucket_tiles, register_panel_kernel)
from ..dsl import dtd, ptg
from ..data.matrix import TiledMatrix
from ..ops.tile_kernels import (gemm_tile, potrf_tile, potrf_tile_blocked,
                                syrk_tile, trsm_tile,
                                trsm_tiles_gemm, trsm_tiles_wide)
from ..utils import compile_cache, mca_param

# The compiled path's batched kernels. "solve" (default) is the exact
# wide triangular solve — reference numerics (dplasma TRSM). "gemm"
# inverts the shared diagonal factor once per wave and runs every solve
# as an MXU matmul (MAGMA-style; measured ~5-8x the wide-solve
# throughput at nb=2048) at the cost of squaring the factor's
# condition-number contribution — fine for the well-conditioned
# dense-LA regime DPLASMA targets, and what the benchmark's POTRF
# configurations state (benchmark/configs/dpotrf_*.json; measured bound
# at N=40960 bf16: residual 4.1e-6 gemm vs the solve+highest variant's
# 4.5e-7; see PARITY.md divergence notes).
# Default "solve": a library default must not silently diverge from
# reference numerics for ill-conditioned inputs.
mca_param.register("potrf.trsm_hook", "solve",
                   help="compiled-path TRSM wave kernel: solve (exact, "
                        "reference numerics) | gemm (inverted-triangle "
                        "MXU multiply, ~5-8x faster, squares the "
                        "condition-number contribution)")
mca_param.register("potrf.blocked_tile_chol", 1,
                   help="use the matmul-rich blocked in-tile Cholesky in "
                        "the compiled path (0 = XLA cholesky)")
# both knobs pick the kernels traced into compiled programs — every
# shared/persistent compile-cache key must cover their values
compile_cache.register_trace_knob("potrf.trsm_hook")
compile_cache.register_trace_knob("potrf.blocked_tile_chol")


def _potrf_stacked(Ts):
    """The stacked form of POTRF, of the PTG class and of the DTD body.
    The diagonal tiles of a factorization never meet, and that is what
    declaring it is for: a body without a stacked form gets its unrolled
    programs of every size at first sight, and 8 + 4 tile Choleskys that
    never run cost 0.2 GiB of HBM beside the matrix at 2048-tiles
    (PERF.md section 6, PR 31); a stacked form is built when a group
    first forms."""
    import jax
    if mca_param.get("potrf.blocked_tile_chol", 1):
        return jax.vmap(potrf_tile_blocked)(Ts) if Ts.shape[0] > 1 \
            else potrf_tile_blocked(Ts[0])[None]
    return jax.vmap(potrf_tile)(Ts)


def _trsm_stacked(Ls, Cs):
    """The stacked form of TRSM, of the PTG class and of the DTD body:
    every TRSM(m, k) of a group shares the factor L = POTRF(k), so the
    group is one inversion and one wide matmul (or one wide-RHS solve)."""
    if mca_param.get("potrf.trsm_hook", "solve") == "gemm":
        return trsm_tiles_gemm(Ls[0], Cs)
    return trsm_tiles_wide(Ls[0], Cs)


def build_potrf(A: TiledMatrix) -> ptg.Taskpool:
    """Build the POTRF taskpool over tiled matrix ``A`` (lower)."""
    NT = A.nt
    if A.mt != A.nt:
        raise ValueError("POTRF needs a square tile grid")
    if A.mb != A.nb:
        # the wave fusers index the transposed store with nb-granular
        # row panels and mb-granular columns interchangeably — non-
        # square tiles would silently produce wrong slices
        raise ValueError("POTRF needs square tiles (mb == nb)")
    tp = ptg.Taskpool("potrf", A=A, NT=NT)

    POTRF = tp.task_class(
        "POTRF", params=("k",),
        space=lambda g: ((k,) for k in range(g.NT)),
        affinity=lambda g, k: (g.A, (k, k)),
        priority=lambda g, k: 3 * (g.NT - k) ** 2,
        flows=[ptg.FlowSpec(
            "T", ptg.RW,
            tile=lambda g, k: (g.A, (k, k)),
            ins=[ptg.In(data=lambda g, k: (g.A, (k, k)),
                        guard=lambda g, k: k == 0),
                 ptg.In(src=("SYRK", lambda g, k: (k, k - 1), "C"),
                        guard=lambda g, k: k > 0)],
            outs=[ptg.Out(dst=("TRSM",
                               lambda g, k: [(m, k) for m in range(k + 1, g.NT)],
                               "L")),
                  ptg.Out(data=lambda g, k: (g.A, (k, k)))])])

    TRSM = tp.task_class(
        "TRSM", params=("m", "k"),
        space=lambda g: ((m, k) for k in range(g.NT)
                         for m in range(k + 1, g.NT)),
        affinity=lambda g, m, k: (g.A, (m, k)),
        priority=lambda g, m, k: 2 * (g.NT - k) ** 2 - m,
        flows=[
            ptg.FlowSpec(
                "L", ptg.READ,
                tile=lambda g, m, k: (g.A, (k, k)),
                ins=[ptg.In(src=("POTRF", lambda g, m, k: (k,), "T"))]),
            ptg.FlowSpec(
                "C", ptg.RW,
                tile=lambda g, m, k: (g.A, (m, k)),
                ins=[ptg.In(data=lambda g, m, k: (g.A, (m, k)),
                            guard=lambda g, m, k: k == 0),
                     ptg.In(src=("GEMM", lambda g, m, k: (m, k, k - 1), "C"),
                            guard=lambda g, m, k: k > 0)],
                outs=[
                    ptg.Out(dst=("SYRK", lambda g, m, k: (m, k), "A")),
                    # row operand of the GEMMs updating row m
                    ptg.Out(dst=("GEMM",
                                 lambda g, m, k: [(m, n, k)
                                                  for n in range(k + 1, m)],
                                 "A")),
                    # transposed operand of the GEMMs updating column m
                    ptg.Out(dst=("GEMM",
                                 lambda g, m, k: [(i, m, k)
                                                  for i in range(m + 1, g.NT)],
                                 "B")),
                    ptg.Out(data=lambda g, m, k: (g.A, (m, k)))])])

    SYRK = tp.task_class(
        "SYRK", params=("m", "k"),
        space=lambda g: ((m, k) for m in range(1, g.NT)
                         for k in range(m)),
        affinity=lambda g, m, k: (g.A, (m, m)),
        priority=lambda g, m, k: 2 * (g.NT - k) ** 2 - m,
        flows=[
            ptg.FlowSpec(
                "A", ptg.READ,
                tile=lambda g, m, k: (g.A, (m, k)),
                ins=[ptg.In(src=("TRSM", lambda g, m, k: (m, k), "C"))]),
            ptg.FlowSpec(
                "C", ptg.RW,
                tile=lambda g, m, k: (g.A, (m, m)),
                ins=[ptg.In(data=lambda g, m, k: (g.A, (m, m)),
                            guard=lambda g, m, k: k == 0),
                     ptg.In(src=("SYRK", lambda g, m, k: (m, k - 1), "C"),
                            guard=lambda g, m, k: k > 0)],
                outs=[ptg.Out(dst=("SYRK", lambda g, m, k: (m, k + 1), "C"),
                              guard=lambda g, m, k: k < m - 1),
                      ptg.Out(dst=("POTRF", lambda g, m, k: (m,), "T"),
                              guard=lambda g, m, k: k == m - 1),
                      # in place: the tile's last version is freed
                      ptg.Out(data=lambda g, m, k: (g.A, (m, m)))])])

    GEMM = tp.task_class(
        "GEMM", params=("m", "n", "k"),
        space=lambda g: ((m, n, k) for m in range(2, g.NT)
                         for n in range(1, m) for k in range(n)),
        affinity=lambda g, m, n, k: (g.A, (m, n)),
        priority=lambda g, m, n, k: (g.NT - k) ** 2 - m - n,
        flows=[
            ptg.FlowSpec(
                "A", ptg.READ,
                tile=lambda g, m, n, k: (g.A, (m, k)),
                ins=[ptg.In(src=("TRSM", lambda g, m, n, k: (m, k), "C"))]),
            ptg.FlowSpec(
                "B", ptg.READ,
                tile=lambda g, m, n, k: (g.A, (n, k)),
                ins=[ptg.In(src=("TRSM", lambda g, m, n, k: (n, k), "C"))]),
            ptg.FlowSpec(
                "C", ptg.RW,
                tile=lambda g, m, n, k: (g.A, (m, n)),
                ins=[ptg.In(data=lambda g, m, n, k: (g.A, (m, n)),
                            guard=lambda g, m, n, k: k == 0),
                     ptg.In(src=("GEMM",
                                 lambda g, m, n, k: (m, n, k - 1), "C"),
                            guard=lambda g, m, n, k: k > 0)],
                outs=[ptg.Out(dst=("GEMM",
                                   lambda g, m, n, k: (m, n, k + 1), "C"),
                              guard=lambda g, m, n, k: k < n - 1),
                      ptg.Out(dst=("TRSM", lambda g, m, n, k: (m, n), "C"),
                              guard=lambda g, m, n, k: k == n - 1),
                      # in place: the tile's last version is freed
                      ptg.Out(data=lambda g, m, n, k: (g.A, (m, n)))])])

    # Every class updates its tile where it lies (``donates``): the
    # version of A(m,n) a GEMM, a SYRK, a TRSM or a POTRF takes in has no
    # other reader (one chain a tile: the k-th update's only successor
    # is the (k+1)-th, the last one's the tile's TRSM or POTRF, and the
    # tile of A it came from is the one the task writes), so a chip
    # module hands its buffer to the program for the flow's output, as
    # upstream's kernels update C in place: a launch still queued holds
    # nothing new, and the module may have the next one queued behind
    # it. Whether a stacked form's program really writes there is the
    # module's to read off the program. An executor that lowers the
    # whole pool places its buffers itself and does not read this.
    @POTRF.body(batch_hook=_potrf_stacked, donates=("T",))
    def potrf_body(task, T):
        return potrf_tile(T)

    # the batched form (the executor and the chip module verify the
    # shared-L grouping per wave and per group)
    @TRSM.body(batch_hook=_trsm_stacked, batch_hook_shared=("L",),
               donates=("C",))
    def trsm_body(task, L, C):
        return trsm_tile(C, L)

    @SYRK.body(donates=("C",))
    def syrk_body(task, A_, C):
        return syrk_tile(C, A_, alpha=-1.0, beta=1.0)

    @GEMM.body(donates=("C",))
    def gemm_body(task, A_, B_, C):
        return gemm_tile(C, A_, B_, alpha=-1.0, beta=1.0, tb=True)

    tp.wave_fuser = _potrf_wave_fuser
    return tp


def _fuser_helpers(geom):
    import jax.numpy as jnp
    from ..ops.tile_kernels import matmul_precision

    prec = matmul_precision()

    def mm(a, b):
        return jnp.matmul(a, b, preferred_element_type=jnp.float32,
                          precision=prec)

    def tile_chol(blk):
        if mca_param.get("potrf.blocked_tile_chol", 1):
            return potrf_tile_blocked(blk)
        return potrf_tile(blk)

    return jnp, mm, tile_chol


def _potrf_wave_fuser(wave, geoms):
    """Lower one right-looking POTRF wave to Aᵀ-dense ops
    (compiled.panels contract).

    ASAP leveling makes every wave one of three shapes per step k —
    [POTRF(k)], [TRSM(·,k)], [SYRK(·,k) (+GEMM(·,·,k))]. In the
    transposed store, block-column panels of A are leading-dim row
    slices, so the TRSM panel solve and every trailing strip are
    contiguous reads/writes. The shapes are verified from the actual
    task lists (never wave-index arithmetic); unrecognized waves return
    None.
    """
    (geom,) = geoms.values()      # single-collection DAG
    jnp, mm, tile_chol = _fuser_helpers(geom)
    names = sorted(g.tc.name for g in wave)
    mb, nb = geom.mb, geom.nb

    if names == ["POTRF"]:
        (grp,) = wave
        if len(grp.tasks) != 1:
            return None
        (k,) = grp.tasks[0]

        def do_potrf(st, k=k):
            D = st[geom.name]
            r, c = geom.rows(k), geom.cols(k)
            # diag tile of Aᵀ = (A[k,k])ᵀ, symmetric → chol directly;
            # store Lᵀ (upper) back
            st[geom.name] = D.at[c, r].set(tile_chol(D[c, r]).T)
            return st

        return do_potrf

    if names == ["TRSM"]:
        (grp,) = wave
        ks = {t[1] for t in grp.tasks}
        if len(ks) != 1:
            return None
        k = ks.pop()
        ms = sorted(t[0] for t in grp.tasks)
        if ms != list(range(ms[0], ms[0] + len(ms))):
            return None        # rows must be one contiguous panel

        solve_mode = mca_param.get("potrf.trsm_hook", "solve") == "solve"

        def do_trsm(st, k=k, lo=ms[0], hi=ms[-1] + 1):
            import jax
            from ..ops.tile_kernels import tri_inv_tile
            D = st[geom.name]
            c = geom.cols(k)
            # Lᵀ[k,k] stored upper → recover L
            L = D[c, geom.rows(k)].T
            rest = D[c, lo * mb:hi * mb]
            if solve_mode:        # exact wide solve, no inversion
                solved = jax.scipy.linalg.solve_triangular(
                    L.astype(jnp.float32), rest.astype(jnp.float32),
                    lower=True).astype(D.dtype)
            else:                 # invert once per wave, solve as matmul
                solved = mm(tri_inv_tile(L), rest).astype(D.dtype)
            # C ← C·L⁻ᵀ transposed: Cᵀ ← L⁻¹·Cᵀ, one contiguous row panel
            st[geom.name] = D.at[c, lo * mb:hi * mb].set(solved)
            return st

        return do_trsm

    if names in (["SYRK"], ["GEMM", "SYRK"]):
        syrk = next(g for g in wave if g.tc.name == "SYRK")
        ks = {t[1] for t in syrk.tasks}
        gemm = next((g for g in wave if g.tc.name == "GEMM"), None)
        if gemm is not None:
            ks |= {t[2] for t in gemm.tasks}
        if len(ks) != 1:
            return None
        k = ks.pop()
        rows = sorted(t[0] for t in syrk.tasks)
        lo, hi = rows[0], rows[-1] + 1
        if rows != list(range(lo, hi)):
            return None
        want = {(m, n) for m in range(lo, hi) for n in range(lo, m)}
        have = {(m, n) for (m, n, _k) in (gemm.tasks if gemm else [])}
        if want != have:
            return None        # trailing block-triangle must be complete

        def do_trailing(st, k=k, lo=lo, hi=hi):
            # strip j updates A[j.., j] — in Aᵀ: row panel j, trailing
            # columns; SYRK (diag tile) + GEMM (below) together, never
            # touching strictly-upper tiles
            D = st[geom.name]
            Pt = D[geom.cols(k), lo * mb:hi * mb]     # (nb, R) = panelᵀ
            for j in range(lo, hi):
                pj = Pt[:, (j - lo) * mb:(j - lo + 1) * mb]
                old = D[geom.cols(j), j * mb:hi * mb]
                D = D.at[geom.cols(j), j * mb:hi * mb].set(
                    old - mm(pj.T, Pt[:, (j - lo) * mb:]))
            st[geom.name] = D
            return st

        return do_trailing

    return None


# -- the same factorization by task insertion ------------------------------
# Module-level bodies (stable identity: the pure-body caches are keyed by
# fn, so every pool in the process shares one compile) over the tile
# kernels build_potrf's classes use, argument for argument.

def _potrf_dtd_potrf(t):
    return potrf_tile(t)


def _potrf_dtd_trsm(l, c):
    return trsm_tile(c, l)


def _potrf_dtd_syrk(a, c):
    return syrk_tile(c, a, alpha=-1.0, beta=1.0)


def _potrf_dtd_gemm(a, b, c):
    return gemm_tile(c, a, b, alpha=-1.0, beta=1.0, tb=True)


# The priorities ``testing_zpotrf_dtd.c`` inserts with (zpotrf_L.jdf's
# expressions; the GEMM that updates A(n, m), n > m, puts its indices
# where the JDF's has its own, so its middle term is negative).
POTRF_DTD_PRIORITY = {
    "POTRF": lambda nt, k: (nt - k) ** 3,
    "TRSM": lambda nt, m, k:
        (nt - m) ** 3 + 3 * (2 * nt - k - m - 1) * (m - k),
    "SYRK": lambda nt, m, k: (nt - m) ** 3 + 3 * (m - k),
    "GEMM": lambda nt, n, m, k:
        (nt - m) ** 3 + 3 * (2 * nt - m - n - 3) * (m - n) + 6 * (m - k),
}


def insert_potrf_dtd(tp: "dtd.Taskpool", A: TiledMatrix) -> None:
    """Insert the tiled Cholesky factorization of ``A`` (lower) into a
    DTD taskpool the way DPLASMA's ``tests/testing_zpotrf_dtd.c`` does:
    one sequential loop in the tester's program order, its priorities
    (``POTRF_DTD_PRIORITY``), a ``flush_tile`` of every tile the loop
    has finished with (A(k, k) once its TRSMs are inserted, A(m, k) once
    row m's updates are) and one ``flush_all`` at the end; the caller
    waits for the pool. Dependencies are discovered from the tiles'
    access modes while the tasks inserted before already run.

    The TRSMs of a column and the GEMMs of a row of the trailing update
    go in one ``insert_tasks`` call each, POTRF and SYRK in an
    ``insert_task``. The TRSM body declares its stacked form with L
    shared, so TRSMs that leave as one launch are one inversion and one
    wide matmul under ``potrf.trsm_hook=gemm``, as the PTG class's are;
    the POTRF body declares the PTG class's too (``_potrf_stacked``).
    Every update is written to its tile of ``A`` at its task's
    completion: the factorization runs in the matrix's own storage."""
    nt = A.nt
    if A.mt != nt or A.mb != A.nb:
        raise ValueError("POTRF needs a square grid of square tiles")
    T, IN, INOUT = dtd.TileArg, dtd.INPUT, dtd.INOUT
    p_potrf, p_trsm, p_syrk, p_gemm = (
        POTRF_DTD_PRIORITY[c] for c in ("POTRF", "TRSM", "SYRK", "GEMM"))
    for k in range(nt):
        tp.insert_task(_potrf_dtd_potrf, T(A, (k, k), INOUT, affinity=True),
                       priority=p_potrf(nt, k), pure=True,
                       stacked=(_potrf_stacked, ()))
        below = range(k + 1, nt)
        if not below:
            break
        tp.insert_tasks(
            _potrf_dtd_trsm,
            [(T(A, (k, k), IN), T(A, (m, k), INOUT, affinity=True))
             for m in below],
            priorities=[p_trsm(nt, m, k) for m in below],
            pure=True, stacked=(_trsm_stacked, (0,)))
        tp.flush_tile(A, (k, k))
        for m in below:
            tp.insert_task(_potrf_dtd_syrk, T(A, (m, k), IN),
                           T(A, (m, m), INOUT, affinity=True),
                           priority=p_syrk(nt, m, k), pure=True)
            right = range(m + 1, nt)
            if right:
                tp.insert_tasks(
                    _potrf_dtd_gemm,
                    [(T(A, (n, k), IN), T(A, (m, k), IN),
                      T(A, (n, m), INOUT, affinity=True)) for n in right],
                    priorities=[p_gemm(nt, n, m, k) for n in right],
                    pure=True)
            tp.flush_tile(A, (m, k))
    tp.flush_all(A)


def potrf_flops(n: int) -> float:
    """Useful FLOPs of an n×n Cholesky (LAPACK count)."""
    return n ** 3 / 3.0 + n ** 2 / 2.0 + n / 6.0


# -- the panel executor's own check: input generated on device in its
# Aᵀ-dense layout + a random-probe residual of the factor. Used by
# chip_smoke.py's panel phases, row-parametric so a caller never holds
# a second N×N array next to the factor.

def panel_spd_row(key, i: int, n: int, nb: int):
    """Block-row ``i`` of the Aᵀ-dense diagonally-dominant SPD input,
    generated on device from a per-row key, so that the residual check
    can regenerate one row at a time."""
    import jax
    import jax.numpy as jnp
    Ri = jax.random.normal(jax.random.fold_in(key, i), (nb, n),
                           dtype=jnp.float32)
    return Ri.at[:, i * nb:(i + 1) * nb].add(
        2.0 * n * jnp.eye(nb, dtype=jnp.float32))


def panel_spd_state(key, n: int, nb: int):
    """The whole input, entirely on device. Only the upper triangle of
    D (= lower of A) plus the averaged diagonal blocks are read by the
    DAG — the fuser symmetrizes diag blocks 0.5·(B+Bᵀ) at their point
    of use, and :func:`panel_potrf_residual` models exactly that
    matrix."""
    import jax.numpy as jnp
    return {"A": jnp.concatenate(
        [panel_spd_row(key, i, n, nb) for i in range(n // nb)], axis=0)}


def panel_potrf_residual(Lt, key, n: int, nb: int):
    """Random-probe residual ‖(LLᵀ−A₀)x‖/‖A₀x‖ of the factor ``Lt``
    (Lᵀ in the upper block triangle), where A₀ is EXACTLY the matrix
    the DAG factors: strict-lower blocks read from the stored triangle
    (upper of D), diagonal blocks symmetrized as the fuser does.
    Computed block-row-wise from regenerated rows — no N×N temporaries.
    Trace it under ``jax.default_matmul_precision("highest")``: the
    probe measures the factor, so its own dots must not add bf16
    noise."""
    import jax
    import jax.numpy as jnp
    nt = n // nb
    s = 8
    x = jax.random.normal(jax.random.fold_in(key, nt + 1), (n, s),
                          jnp.float32)

    def blk(i):
        return slice(i * nb, (i + 1) * nb)

    # y = A0 @ x, accumulated per regenerated block-row j of D0: diag
    # averaged, strict-lower blocks Dj[:, i>j]ᵀ plus their
    # mirrored-upper contribution
    y = jnp.zeros((n, s), jnp.float32)
    for j in range(nt):
        Dj = panel_spd_row(key, j, n, nb)
        d = Dj[:, blk(j)]
        yj = 0.5 * (d + d.T) @ x[blk(j)]
        if j < nt - 1:
            tail = Dj[:, (j + 1) * nb:]
            yj = yj + tail @ x[(j + 1) * nb:]
            y = y.at[(j + 1) * nb:].add(tail.T @ x[blk(j)])
        y = y.at[blk(j)].add(yj)

    # z = Lᵀ x ; y2 = L z — Lt's diag blocks are exactly upper-
    # triangular (chol zeroes the strict lower), and only the upper
    # block triangle of Lt is ever read
    z = jnp.concatenate(
        [Lt[blk(j), j * nb:] @ x[j * nb:] for j in range(nt)], axis=0)
    y2 = jnp.concatenate(
        [Lt[0:(i + 1) * nb, blk(i)].T @ z[0:(i + 1) * nb]
         for i in range(nt)], axis=0)
    return jnp.linalg.norm(y2 - y) / jnp.linalg.norm(y)


def build_potrf_left(A: TiledMatrix) -> ptg.Taskpool:
    """Left-looking tiled Cholesky (LAPACK-style blocked ``potrf``).

    The right-looking :func:`build_potrf` spreads a tile's updates over
    k-indexed SYRK/GEMM chains; this variant concentrates them: each
    tile receives ALL its k<j contributions in a single ``UPDATE`` task
    that CTL-gathers its producer TRSMs (the reference's CTL-gather
    fan-in, tests/dsl/ptg/controlgather/ctlgat.jdf) and reads their
    written-back tiles from the collection inside the body — the same
    direct-memory pattern reference JDF bodies use for gathered
    operands. ASAP leveling then yields exactly three waves per step k
    ([UPDATE(·,k)], [POTRF(k)], [TRSM(·,k)]), and the panel fuser turns
    each UPDATE wave into dense matmuls over all previously factored
    panels, one a column run of the row panel, their results subtracted
    where the POTRF and TRSM waves consume them — measured on a v5e at
    N=40960, NB=1024 (PERF.md §6, PR 40): 138 TF/s/chip for the
    factorization, the products alone at 173; 107 while the
    product and its subtraction were one fusion as wide as the row panel
    (PRs 22-39), ~68 for the fused right-looking form.

    Distribution: UPDATE's gathered operands are resolved with the
    direct-memory pattern of reference JDF bodies — local tiles read
    from the collection, remote tiles through the comm engine's
    one-sided :meth:`~..comm.engine.CommEngine.fetch_tile` (the
    rendezvous-GET analog, remote_dep_mpi.c:1594-1729). The CTL-gather
    guarantees every gathered TRSM wrote its tile back on its owner
    before UPDATE runs, so the fetch is race-free; the same taskpool
    runs single-process panel-fused AND multi-rank.
    """
    NT = A.nt
    if A.mt != A.nt:
        raise ValueError("POTRF needs a square tile grid")
    if A.mb != A.nb:
        raise ValueError("POTRF needs square tiles (mb == nb)")
    tp = ptg.Taskpool("potrf_left", A=A, NT=NT)

    def _gathered(g, m, k):
        """Producer TRSMs whose tiles UPDATE(m, k) reads: row m and
        row k, all columns j < k."""
        seen = []
        for row in (m, k):
            for j in range(k):
                if (row, j) not in seen:
                    seen.append((row, j))
        return seen

    UPDATE = tp.task_class(
        "UPDATE", params=("m", "k"),
        space=lambda g: ((m, k) for k in range(1, g.NT)
                         for m in range(k, g.NT)),
        affinity=lambda g, m, k: (g.A, (m, k)),
        priority=lambda g, m, k: 2 * (g.NT - k) ** 2 - m + 1,
        flows=[
            ptg.FlowSpec(
                "G", ptg.CTL,
                ins=[ptg.In(src=("TRSM", _gathered, "G"), gather=True)]),
            ptg.FlowSpec(
                "C", ptg.RW,
                tile=lambda g, m, k: (g.A, (m, k)),
                ins=[ptg.In(data=lambda g, m, k: (g.A, (m, k)))],
                outs=[ptg.Out(dst=("POTRF", lambda g, m, k: (k,), "T"),
                              guard=lambda g, m, k: m == k),
                      ptg.Out(dst=("TRSM", lambda g, m, k: (m, k), "C"),
                              guard=lambda g, m, k: m > k)])])

    POTRF = tp.task_class(
        "POTRF", params=("k",),
        space=lambda g: ((k,) for k in range(g.NT)),
        affinity=lambda g, k: (g.A, (k, k)),
        priority=lambda g, k: 3 * (g.NT - k) ** 2,
        flows=[ptg.FlowSpec(
            "T", ptg.RW,
            tile=lambda g, k: (g.A, (k, k)),
            ins=[ptg.In(data=lambda g, k: (g.A, (k, k)),
                        guard=lambda g, k: k == 0),
                 ptg.In(src=("UPDATE", lambda g, k: (k, k), "C"),
                        guard=lambda g, k: k > 0)],
            outs=[ptg.Out(dst=("TRSM",
                               lambda g, k: [(m, k)
                                             for m in range(k + 1, g.NT)],
                               "L")),
                  ptg.Out(data=lambda g, k: (g.A, (k, k)))])])

    TRSM = tp.task_class(
        "TRSM", params=("m", "k"),
        space=lambda g: ((m, k) for k in range(g.NT)
                         for m in range(k + 1, g.NT)),
        affinity=lambda g, m, k: (g.A, (m, k)),
        priority=lambda g, m, k: 2 * (g.NT - k) ** 2 - m,
        flows=[
            ptg.FlowSpec(
                "L", ptg.READ,
                tile=lambda g, m, k: (g.A, (k, k)),
                ins=[ptg.In(src=("POTRF", lambda g, m, k: (k,), "T"))]),
            ptg.FlowSpec(
                "C", ptg.RW,
                tile=lambda g, m, k: (g.A, (m, k)),
                ins=[ptg.In(data=lambda g, m, k: (g.A, (m, k)),
                            guard=lambda g, m, k: k == 0),
                     ptg.In(src=("UPDATE", lambda g, m, k: (m, k), "C"),
                            guard=lambda g, m, k: k > 0)],
                outs=[ptg.Out(data=lambda g, m, k: (g.A, (m, k)))]),
            ptg.FlowSpec(
                "G", ptg.CTL,
                outs=[ptg.Out(
                    dst=("UPDATE",
                         lambda g, m, k: sorted(
                             {(m, kk) for kk in range(k + 1, m + 1)} |
                             {(m2, m) for m2 in range(m, g.NT)}),
                         "G"))])])

    # the CTL-gather contract guarantees every gathered TRSM has written
    # its tile back (on its owner rank) before the UPDATE body runs, so
    # direct local reads / remote one-sided fetches are race-free.
    # Fetched tiles are FINAL for the taskpool's lifetime (column j is
    # never rewritten after step j), so remote fetches cache per rank on
    # the taskpool — each remote tile crosses the wire once, not once
    # per consuming UPDATE.
    tp._fetch_cache = {}

    @UPDATE.body(batchable=False)
    def update_body(task, C):
        import numpy as np
        from ..comm.engine import resolve_column_tiles
        g = task.taskpool.g
        ctx = task.taskpool.context
        cache = task.taskpool._fetch_cache
        m, k = task.locals
        remote = ctx is not None and ctx.nb_ranks > 1
        my = ctx.my_rank if remote else 0
        # resolve the two gathered rows up front: local reads inline,
        # uncached remote tiles in ONE concurrent batch fetch (a
        # sequential fetch per tile would serialize ~2k link RTTs)
        keys = []
        for row in (m, k) if m != k else (m,):
            for j in range(k):
                key = (row, j)
                if remote and g.A.rank_of(key) != my \
                        and key not in cache:
                    keys.append(key)
        if keys:
            for key, v in zip(keys,
                              resolve_column_tiles(task, g.A, keys)):
                cache[key] = v          # benign race: idempotent value

        def tile(row, j):
            hit = cache.get((row, j))
            if hit is not None:
                return hit
            return np.asarray(g.A.data_of((row, j)), dtype=np.float32)

        acc = np.asarray(C, dtype=np.float32).copy()
        for j in range(k):
            acc -= tile(m, j) @ tile(k, j).T
        return acc.astype(np.asarray(C).dtype)

    @POTRF.body
    def potrf_body(task, T):
        return potrf_tile(T)

    @TRSM.body(batchable=False)
    def trsm_body(task, L, C):
        return {"C": trsm_tile(C, L)}

    tp.wave_fuser = _potrf_left_wave_fuser
    tp.mesh_wave_fuser = _potrf_left_mesh_wave_fuser
    tp.panel_segment_fuser = _potrf_left_segment_fuser
    tp.requires_fuser = True     # compiled per-tile executors can't feed
    #                              the UPDATE body's collection reads
    return tp


def _potrf_left_wave_fuser(wave, geoms):
    """Lower one left-looking POTRF wave to Aᵀ-dense ops on one chip, as
    :func:`_potrf_left_mesh_wave_fuser` lowers it for a row panel nobody
    sends.

    Wave shapes per step k: [UPDATE(·,k)] → the products alone,
    (Lᵀ[:k, k])ᵀ · Lᵀ[:k, run], one matmul a column run of row panel k
    (``PanelPartition.chunks``' rule for a panel without senders: the
    fewest equal runs of whole tiles within ``PANEL_CHUNK_BYTES``),
    carried in ``st["_products"]``: nothing is subtracted and nothing
    written in this wave. [POTRF(k)] → the diagonal tile less the head
    of the first run's product, chol (inverse stashed in the carry).
    [TRSM(·,k)] → a run at a time, row panel k less the run's product,
    solved via the stashed inverse, one contiguous write a run; the
    diagonal tile's Lᵀ goes with the first run's, so row panel k is
    written once. Every product of a step reads the state as the step
    found it (one ``optimization_barrier`` ahead of the writes says so).

    The form before PR 40 subtracted inside the matmul's fusion, over the
    whole remaining row panel at once. On a v5e at N = 40960 (PERF.md
    §6, PR 40) XLA:TPU's fusion of a product with the subtraction behind
    it ran at about 128 TF/s; the product alone runs at 171, in runs of
    ``PANEL_CHUNK_BYTES`` at 173 with shorter writes behind it (0.2135 →
    0.1720 → 0.1657 s a factorization), and in runs without the barrier
    the compiler copies the state: the barrier is not an ornament."""
    import jax
    from jax import lax
    from ..ops.tile_kernels import tri_inv_tile
    (geom,) = geoms.values()      # single-collection DAG
    jnp, mm, tile_chol = _fuser_helpers(geom)
    if len(wave) != 1:
        return None
    (grp,) = wave
    kind = grp.tc.name
    mb, nb, name = geom.mb, geom.nb, geom.name
    f32 = jnp.float32
    solve_mode = mca_param.get("potrf.trsm_hook", "solve") == "solve"

    ks = {t[-1] for t in grp.tasks}
    if kind not in ("UPDATE", "POTRF", "TRSM") or len(ks) != 1:
        return None
    k = ks.pop()
    ms = sorted(t[0] for t in grp.tasks)
    lo, hi = ms[0], ms[-1] + 1
    if ms != list(range(lo, hi)) or lo != (k + 1 if kind == "TRSM" else k):
        return None
    rows, diag = geom.cols(k), geom.rows(k)   # row panel k, its diagonal tile
    # the column runs of row panel k: one chip is a panel nobody sends
    runs = PanelPartition("", 1).chunks(k, hi, nb * mb * 4, 0)

    if kind == "UPDATE":

        def do_update(st):
            D = st[name]
            with jax.named_scope("parsec:panel_update"):
                st["_products"] = [
                    mm(D[:k * nb, diag].T, D[:k * nb, t0 * mb:t1 * mb])
                    for t0, t1 in runs]
            return st

        # the lowering's own account (PanelExecutor.lowering_report)
        do_update.account = {
            "update_runs": len(runs),
            "update_ops": 2 * k * nb * nb * (hi - k) * mb}
        return do_update

    if kind == "POTRF":
        if len(grp.tasks) != 1:
            return None

        def do_potrf(st):
            D = st[name]
            d = D[rows, diag].astype(f32)
            if "_products" in st:     # no UPDATE wave precedes step 0
                d = d - st["_products"][0][:, :nb]
            # symmetrize (identity for symmetric input; elementwise triu
            # masking here measurably breaks XLA's in-place scheduling —
            # the average form fuses cleanly)
            L = tile_chol(0.5 * (d + d.T))
            if k == geom.nt - 1:
                # no TRSM wave follows: this step's single write is ours
                st.pop("_products", None)
                st[name] = D.at[rows, diag].set(L.T.astype(D.dtype))
                return st
            # defer the write — the TRSM wave writes Lᵀ with its first
            # run as ONE contiguous DUS; split writes double the panel's
            # HBM liveness
            st["_potrf_L"] = L
            if not solve_mode:
                # chol-then-invert, NOT ops.chol_inv_tile: measured
                # identical in-program runtime (105-107 TF/s both ways
                # at N=40960 — the fused kernel's standalone win is
                # dispatch overhead, absent inside one XLA program) and
                # the fused program deserializes 2-4x slower from the
                # persistent cache
                st["_potrf_inv"] = tri_inv_tile(L)
            return st

        return do_potrf

    def do_trsm(st):
        L = st.pop("_potrf_L")
        inv = None if solve_mode else st.pop("_potrf_inv")
        # the step's writes wait for its products: reads before in-place
        # writes is the order XLA keeps without a copy of the state, and
        # it looks for that order no further than a data dependence
        D, products = lax.optimization_barrier(
            (st[name], st.pop("_products", None)))     # None at k = 0
        # the update's runs, less the diagonal tile at the first's head
        for i, (t0, t1) in enumerate(runs):
            skip = 0 if i else mb
            c0, c1 = t0 * mb + skip, t1 * mb
            out = [] if i else [L.T.astype(D.dtype)]
            if c0 < c1:
                rest = D[rows, c0:c1].astype(f32)
                if products:
                    rest = rest - products[i][:, skip:]
                if solve_mode:
                    # exact wide triangular solve (potrf.trsm_hook=solve):
                    # no inversion, no condition-number squaring
                    rest = jax.scipy.linalg.solve_triangular(
                        L.astype(f32), rest, lower=True)
                else:
                    rest = mm(inv, rest)
                out.append(rest.astype(D.dtype))
            # one contiguous write a run, Lᵀ with the first
            st[name] = D = D.at[rows, t0 * mb:c1].set(
                jnp.concatenate(out, axis=1))
        return st

    return do_trsm


def _potrf_left_mesh_wave_fuser(wave, geoms, part):
    """Lower one left-looking POTRF wave for ONE shard of Aᵀ split by
    rows over a mesh axis (compiled.panels mesh contract): owner-computes
    over the collection's distribution, written by the runtime.

    The update of step k contracts over the factored rows ``< k·nb``,
    the SPLIT axis. Each chip multiplies the factored rows it already
    holds — all of its panels (chips before row panel k's owner), the
    first ``k mod panels-a-chip`` of them (the owner), none (chips after
    it): static shapes under a ``lax.switch`` on the chip's index,
    because k and so the owner are static — and every chip before the
    owner sends it its partial product, point to point
    (``part.senders``; the first chip's own panels need nobody's). The
    owner alone adds what arrives to its own product, sender by sender
    in the order of the chips, subtracts, factors the diagonal tile,
    inverts or solves and writes row panel k, in f32 with the one-chip
    fuser's precisions; every other chip skips that as a loop of no
    trips, which unlike a conditional leaves its shard where it is. Row
    panel k is never read by another chip, so nothing else travels and
    nothing travels back.

    A step runs in column chunks (``part.chunks``). No product of step k
    reads row panel k — a sender's rows are final, the owner contracts
    the panels before it — so the UPDATE wave multiplies and sends every
    chunk from the state as the step found it, and the POTRF and TRSM
    waves consume them: a chunk's transfer runs under its sender's next
    product, and a step exposes its last chunk's alone. Two
    ``optimization_barrier``s say so to XLA. The state the step writes is
    one every product has read: reads before in-place writes is the
    order XLA keeps without a copy of the shard, and it looks for that
    order no further than a data dependence. And the owner's chain waits
    for every chunk but the last: left to consume them one by one, the
    scheduler counts the owner's solves as work to hide a send under and
    starts the send after the product that should have hidden it (on a
    sender those solves are loops of no trips). A step never reads ahead
    of the one before it.

    The diagonal tile is factored by ``chol_inv_tile``, the loop form of
    the one-chip fuser's ``potrf_tile_blocked`` + ``tri_inv_tile``: a
    third of their program text, which a chip holds in HBM 64 times."""
    import jax
    from jax import lax
    from ..ops.tile_kernels import chol_inv_tile
    (geom,) = geoms.values()      # single-collection DAG
    jnp, mm, _ = _fuser_helpers(geom)
    if len(wave) != 1:
        return None
    (grp,) = wave
    kind = grp.tc.name
    mb, nb, name = geom.mb, geom.nb, geom.name
    axis = part.axis
    f32 = jnp.float32
    tile_bytes = nb * mb * 4          # of an f32 partial sum
    solve_mode = mca_param.get("potrf.trsm_hook", "solve") == "solve"

    ks = {t[-1] for t in grp.tasks}
    if kind not in ("UPDATE", "POTRF", "TRSM") or len(ks) != 1:
        return None
    k = ks.pop()
    ms = sorted(t[0] for t in grp.tasks)
    lo, hi = ms[0], ms[-1] + 1
    if ms != list(range(lo, hi)) or lo != (k + 1 if kind == "TRSM" else k):
        return None
    owner, kl = part.owner(geom, k)
    held = geom.nt // part.shards * nb        # rows of Aᵀ a chip holds
    rows = slice(kl * nb, (kl + 1) * nb)      # row panel k in its shard
    diag = geom.rows(k)

    def mine():
        return lax.axis_index(axis) == owner

    def owner_alone(write, D):
        """``write(D)`` on row panel k's owner, ``D`` on every other chip,
        in place on both: a loop of one trip or none."""
        return lax.fori_loop(0, mine().astype(jnp.int32),
                             lambda _, D: write(D), D)

    def less(cur, parts, c0=0, c1=None):
        """``cur`` less columns ``[c0, c1)`` of a chunk's products, the
        owner's own and then each sender's in the order of the chips:
        one order, so one result to the bit. Taken off ``cur`` one by
        one and not summed first: a sum of what arrived would not hang
        on the owner's loop, and XLA would lift it out for every chip
        to compute."""
        cur = cur.astype(f32)
        for arrived in parts:
            cur = cur - arrived[:, c0:c1]
        return cur

    if kind == "UPDATE":
        senders = part.senders(owner)
        chunks = part.chunks(k, hi, tile_bytes, owner)
        for i, (t0, t1) in enumerate(chunks):
            w = (t1 - t0) * mb
            for shard in senders:
                part.count_send(shard, nb * w * 4, last=i == len(chunks) - 1)
                part.ops[shard] += 2 * held * nb * w
            part.ops[owner] += 2 * kl * nb * nb * w

        def products(D):
            """Per chunk, the products the owner sums: (Lᵀ[:k, k])ᵀ ·
            Lᵀ[:k, chunk] over the factored rows it holds itself, then
            over each sender's as they arrive (zeros on any other
            chip)."""
            me = lax.axis_index(axis)
            # 0: a chip before the owner, 1: the owner, 2: one after it
            role = (me >= owner).astype(jnp.int32) + \
                (me > owner).astype(jnp.int32)
            out = []
            for t0, t1 in chunks:
                c0, c1 = t0 * mb, t1 * mb

                def whole(D, c0=c0, c1=c1):
                    return mm(D[:, diag].T, D[:, c0:c1])

                def head(D, c0=c0, c1=c1):
                    return mm(D[:kl * nb, diag].T, D[:kl * nb, c0:c1])

                def nothing(D, w=c1 - c0):
                    return jnp.zeros((nb, w), f32)

                own = head if kl else nothing
                partial = lax.switch(role, (whole, own, nothing), D)
                with jax.named_scope("parsec:panel_reduce"):
                    out.append((partial,) + tuple(
                        lax.ppermute(partial, axis, perm=[(shard, owner)])
                        for shard in senders))
            return out

        def do_update(st):
            found = products(st[name])
            last = found[-1][1:]
            # the step's writes wait for its products, and the owner's
            # chain for all that is sent but the last chunk
            st[name], own, sent = lax.optimization_barrier(
                (st[name], [p[0] for p in found],
                 [p[1:] for p in found[:-1]]))
            st["_products"] = [(o,) + s for o, s in zip(own, sent + [last])]
            return st

        return do_update

    if kind == "POTRF":
        if len(grp.tasks) != 1:
            return None
        part.ops[owner] += 2 * nb ** 3 // 3

        def do_potrf(st):
            D = st[name]
            # chunk 0 of the update's products: the diagonal tile's at
            # their head (no UPDATE wave precedes step 0)
            parts = st["_products"][0] if "_products" in st else ()

            def factor(cur, *parts):
                d = less(cur, parts)
                # symmetrized as the one-chip fuser does
                L, inv = chol_inv_tile(0.5 * (d + d.T))
                return L.T.astype(D.dtype), L if solve_mode else inv

            def keep(cur, *parts):
                return cur, jnp.zeros((nb, nb), f32)

            Lt, inv = lax.cond(mine(), factor, keep, D[rows, diag],
                               *(p[:, :nb] for p in parts))
            # the one tile every chip writes, its own back where it is
            # not the owner's: a tile of Lᵀ is L in the other layout, and
            # written inside owner_alone's loop it may talk XLA into
            # keeping the whole shard that way round (two copies of it)
            st[name] = D.at[rows, diag].set(Lt)
            if k == geom.nt - 1:       # no TRSM wave follows
                st.pop("_products", None)
            else:
                st["_potrf_inv"] = inv   # L itself under trsm_hook=solve
            return st

        return do_potrf

    part.ops[owner] += 2 * nb * nb * (hi - lo) * mb

    def do_trsm(st):
        D = st[name]
        products = st.pop("_products", None)       # None at k = 0
        inv = st.pop("_potrf_inv")
        # the update's chunks, less the diagonal tile at the first's head
        for i, (t0, t1) in enumerate(part.chunks(k, hi, tile_bytes, owner)):
            skip = 0 if i else mb
            c0, c1 = t0 * mb + skip, t1 * mb
            if c0 == c1:
                continue

            def write(D, c0=c0, c1=c1, skip=skip,
                      parts=products[i] if products else ()):
                rest = less(D[rows, c0:c1], parts, skip)
                if solve_mode:
                    # exact wide triangular solve: no inversion
                    rest = jax.scipy.linalg.solve_triangular(
                        inv, rest, lower=True)
                else:
                    rest = mm(inv, rest)
                # one contiguous write a chunk
                return D.at[rows, c0:c1].set(rest.astype(D.dtype))

            D = owner_alone(write, D)
        st[name] = D
        return st

    return do_trsm


# ---------------------------------------------------------------------------
# segmented panel lowering (compile-once serving)
# ---------------------------------------------------------------------------
# The monolith fusers above bake k into static slices of the full Aᵀ
# array: the whole-DAG program is specific to N and its compile time is
# linear in waves. The segment lowering expresses the SAME math as
# named kernels over extracted panels whose shapes are rounded up to
# the bucket lattice (compiled.panels.bucket_tiles) — each kernel is
# keyed by (NB, bucket, dtype, trsm_hook/chol knobs), INDEPENDENT of N,
# so a new problem size at a served NB re-uses every compiled bucket
# and the persistent store makes the second process compile nothing.
# Padding is exact: extraction zero-masks past the true extents (zero
# rows contribute nothing to the update matmul; zero RHS columns solve
# to zero) and write-back masks to the true window.

def _seg_mm():
    import jax.numpy as jnp
    from ..ops.tile_kernels import matmul_precision
    prec = matmul_precision()

    def mm(a, b):
        return jnp.matmul(a, b, preferred_element_type=jnp.float32,
                          precision=prec)

    return jnp, mm


@register_panel_kernel("potrf_left.update")
def _seg_update_kernel(in_sds, static):
    """(U (Kb,nb), S (Kb,Wb), Drow (nb,Wb)) → rowk = Drow − UᵀS."""
    del in_sds, static
    jnp, mm = _seg_mm()

    def fn(U, S, Drow):
        return Drow - mm(U.T, S)

    return fn


@register_panel_kernel("potrf_left.diag")
def _seg_diag_kernel(in_sds, static):
    """(rowk (nb, Wb)) → (Lᵀ write, L carry[, L⁻¹ carry]): symmetrized
    diag chol of the panel head; the inverse carry exists only under
    potrf.trsm_hook=gemm (key covered by the trace-knob snapshot)."""
    del static
    (rowk_sds,) = in_sds
    nb = rowk_sds.shape[0]
    jnp, mm = _seg_mm()
    solve_mode = mca_param.get("potrf.trsm_hook", "solve") == "solve"

    def tile_chol(blk):
        if mca_param.get("potrf.blocked_tile_chol", 1):
            return potrf_tile_blocked(blk)
        return potrf_tile(blk)

    def fn(rowk):
        from ..ops.tile_kernels import tri_inv_tile
        diag = rowk[:, :nb]
        diag = 0.5 * (diag + diag.T)
        L = tile_chol(diag.astype(jnp.float32))
        if solve_mode:
            return L.T.astype(rowk.dtype), L
        return L.T.astype(rowk.dtype), L, tri_inv_tile(L)

    return fn


@register_panel_kernel("potrf_left.trsm")
def _seg_trsm_kernel(in_sds, static):
    """(L (nb,nb)[, inv], rest-or-rowk (nb, W)) → solved panel. static
    ``skip``: 1 when the panel input is the rowk carry (diag in its
    first nb columns, skipped), 0 when it is the k=0 state read."""
    (skip,) = static
    nb = in_sds[0].shape[0]
    jnp, mm = _seg_mm()
    solve_mode = mca_param.get("potrf.trsm_hook", "solve") == "solve"

    if solve_mode:
        def fn(L, panel):
            import jax
            rest = panel[:, nb:] if skip else panel
            return jax.scipy.linalg.solve_triangular(
                L.astype(jnp.float32), rest.astype(jnp.float32),
                lower=True)
    else:
        def fn(L, inv, panel):
            del L
            rest = panel[:, nb:] if skip else panel
            return mm(inv, rest)

    return fn


def _potrf_left_segment_fuser(wave, geoms):
    """Lower one left-looking POTRF wave to bucketed SegSteps
    (compiled.panels segmented contract). Wave-shape recognition is
    identical to the monolith fuser; the emitted steps express the same
    math over bucketed panels with masked reads/writes."""
    (geom,) = geoms.values()      # single-collection DAG
    names = sorted(g.tc.name for g in wave)
    nb, NT = geom.nb, geom.nt
    name = geom.name
    solve_mode = mca_param.get("potrf.trsm_hook", "solve") == "solve"

    def wb(tiles):               # bucketed element width of `tiles`
        return bucket_tiles(tiles, NT) * nb

    if names == ["UPDATE"]:
        (grp,) = wave
        ks = {t[1] for t in grp.tasks}
        if len(ks) != 1:
            return None
        k = ks.pop()
        ms = sorted(t[0] for t in grp.tasks)
        lo, hi = ms[0], ms[-1] + 1
        if ms != list(range(lo, hi)) or lo != k or hi != NT:
            return None
        r0, W = k * nb, (NT - k) * nb
        Kb, Wb = wb(k), wb(NT - k)
        return [SegStep(
            kernel="potrf_left.update",
            reads=(SegRead("state", name, 0, r0, r0, nb, Kb, nb),
                   SegRead("state", name, 0, r0, r0, W, Kb, Wb),
                   SegRead("state", name, r0, r0, nb, W, nb, Wb)),
            writes=(SegWrite("carry", "_rowk"),))]

    if names == ["POTRF"]:
        (grp,) = wave
        if len(grp.tasks) != 1:
            return None
        (k,) = grp.tasks[0]
        r0 = k * nb
        carries = (SegWrite("carry", "_L"),) if solve_mode else \
            (SegWrite("carry", "_L"), SegWrite("carry", "_inv"))
        if k == 0:
            reads = (SegRead("state", name, 0, 0, nb, nb, nb, nb),)
        else:
            reads = (SegRead("carry", "_rowk"),)
        return [SegStep(
            kernel="potrf_left.diag", reads=reads,
            writes=(SegWrite("state", name, r0, r0, nb, nb),) + carries)]

    if names == ["TRSM"]:
        (grp,) = wave
        ks = {t[1] for t in grp.tasks}
        if len(ks) != 1:
            return None
        k = ks.pop()
        ms = sorted(t[0] for t in grp.tasks)
        lo, hi = ms[0], ms[-1] + 1
        if ms != list(range(lo, hi)) or lo != k + 1 or hi != NT:
            return None
        r0 = k * nb
        rest_w = (NT - k - 1) * nb
        if k == 0:
            panel = SegRead("state", name, 0, nb, nb, rest_w,
                            nb, wb(NT - 1))
            skip = 0
        else:
            panel = SegRead("carry", "_rowk")
            skip = 1
        reads = (SegRead("carry", "_L"), panel) if solve_mode else \
            (SegRead("carry", "_L"), SegRead("carry", "_inv"), panel)
        return [SegStep(
            kernel="potrf_left.trsm", reads=reads, static=(skip,),
            writes=(SegWrite("state", name, r0, (k + 1) * nb,
                             nb, rest_w),))]

    return None
