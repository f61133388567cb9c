"""Operation and byte counts of the algorithms the cells run.

The benchmark's own arithmetic: every rate and roofline share divides by
these, so they live here and not in the program (``parsec_tpu`` has
``potrf_flops``/``gemm_flops`` of its own; a change there moves nothing
here). Counts are what the ALGORITHM needs, not what a given schedule
executes: recomputed or padded work does not count.
"""

from __future__ import annotations


def potrf_ops(n: int) -> float:
    """Floating-point operations of an n×n Cholesky factorization: the
    LAPACK working note 41 count n³/3 + n²/2 + n/6."""
    return n ** 3 / 3.0 + n ** 2 / 2.0 + n / 6.0


def potrf_min_bytes(n: int, itemsize: int) -> float:
    """Least memory traffic of an in-place n×n factorization: the stored
    triangle (with its diagonal) read once and written once."""
    return 2.0 * itemsize * n * (n + 1) / 2.0


def gemm_ops(m: int, n: int, k: int) -> float:
    """C(m×n) ← A(m×k)·B(k×n) + C: one multiply and one add per term."""
    return 2.0 * m * n * k


def tiled_gemm_tasks(m: int, n: int, k: int, nb: int) -> int:
    """Tasks of the tiled GEMM DAG: one per (C tile, k block)."""
    return (m // nb) * (n // nb) * (k // nb)


def tiled_gemm_min_bytes(m: int, n: int, k: int, nb: int,
                         itemsize: int) -> float:
    """Least memory traffic of the TILED algorithm, one kernel launch per
    task: each task reads an A, a B and a C tile and writes a C tile.
    (A fused GEMM would move less; this is the bound of the tile kernel
    the dynamic path launches, which is what its roofline share is
    measured against.)"""
    return tiled_gemm_tasks(m, n, k, nb) * 4.0 * nb * nb * itemsize


def roofline_seconds(ops: float, nbytes: float, peak_flops: float,
                     peak_bytes_per_s: float):
    """``(seconds, bound)``: the least time one chip could take for
    ``ops`` operations and ``nbytes`` bytes, and which peak sets it."""
    t_ops = ops / peak_flops
    t_mem = nbytes / peak_bytes_per_s
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
