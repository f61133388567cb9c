"""Operation and byte counts of the tile LU with partial pivoting over
whole panels the ``dgetrf_1d_ptg_host`` cell runs (``ops.py``'s rules:
what the ALGORITHM needs; the solves inside a panel's inner blocks do not
count beyond the panel's own LU).
"""

from __future__ import annotations

from benchmark.ops_getrf import getrf_ops  # noqa: F401  (the whole: 2n³/3 − n²/2 − n/6)


def getrf_1d_stored_bytes(n: int, itemsize: int, int_size: int = 4) -> int:
    """Bytes of the factored form: A and IPIV (n integers)."""
    return itemsize * n * n + int_size * n


def getrf_1d_min_bytes(n: int, itemsize: int, int_size: int = 4) -> float:
    """Least memory traffic of an in-place factorization: A read once and
    written once, IPIV written once."""
    return float(2 * itemsize * n * n + int_size * n)


def getrf_1d_tasks(nt: int) -> dict:
    """Tasks of zgetrf_1d.jdf's four classes over an nt×nt grid."""
    pairs = nt * (nt - 1) // 2
    return {
        "GETRF": nt,
        "SWPTRSM": pairs,
        "GEMM": sum((nt - k - 1) ** 2 for k in range(nt)),
        "SWPBACK": pairs,
    }


def panel_ops(rows: int, nb: int) -> float:
    """Operations of GETRF(k): the LU of a ``rows`` × nb panel,
    rows·nb² − nb³/3."""
    return float(rows) * nb * nb - nb ** 3 / 3.0


def getrf_1d_kernels(nt: int, nb: int, itemsize: int,
                     int_size: int = 4) -> dict:
    """``{class: (operations, least bytes)}`` of one task of each kernel.
    A GETRF's panel is (nt − k) tiles tall, so its figures are the MEAN
    over k: the panel read and written once, rows·nb² − nb³/3 operations
    (the roofline of the mean is at most the mean of the rooflines, so
    the share a reader makes of it errs low). SWPTRSM: nb³ (the unit-lower
    solve); L and the pivots read, its own tile read and written, nb rows
    read from the tiles below and nb written there. GEMM: 2nb³; three
    tiles read, one written. SWPBACK: no operation; 2·nb rows read and
    2·nb written, and the pivots."""
    tile, piv = nb * nb * itemsize, nb * int_size
    heights = [nt - k for k in range(nt)]
    return {
        "GETRF": (sum(panel_ops(r * nb, nb) for r in heights) / nt,
                  sum(2 * r * tile + piv for r in heights) / nt),
        "SWPTRSM": (1.0 * nb ** 3, 5 * tile + piv),
        "GEMM": (2.0 * nb ** 3, 4 * tile),
        "SWPBACK": (0.0, 4 * tile + piv),
    }
