"""Operation and byte counts of the tiled QR factorization the
``dgeqrf_ptg_host`` cells run (``ops.py``'s rules: what the ALGORITHM
needs; the extra work of a finite inner block ``ib``, of a Gram-based
panel or of a triangular T applied as a full product does not count).
"""

from __future__ import annotations


def geqrf_ops(m: int, n: int) -> float:
    """Floating-point operations of the QR factorization of an m×n
    matrix, m ≥ n: LAPACK working note 41's count for ``dgeqrf``."""
    return 2.0 * m * n * n - 2.0 * n ** 3 / 3.0 + m * n + n * n / 2.0


def geqrf_t_tiles(mt: int, nt: int) -> int:
    """Tiles of descT the factorization writes: one beside every tile
    of A on and under the diagonal."""
    return sum(mt - k for k in range(nt))


def geqrf_min_bytes(m: int, n: int, nb: int, ib: int,
                    itemsize: int) -> float:
    """Least memory traffic of an in-place factorization: A read once
    and written once, T (``ib`` × nb beside every tile on and under the
    diagonal) written once."""
    return itemsize * (2.0 * m * n
                       + geqrf_t_tiles(m // nb, n // nb) * ib * nb)


def geqrf_tasks(mt: int, nt: int) -> dict:
    """Tasks of zgeqrf.jdf's four classes over an mt×nt tile grid."""
    return {
        "GEQRT": nt,
        "UNMQR": sum(nt - k - 1 for k in range(nt)),
        "TSQRT": sum(mt - k - 1 for k in range(nt)),
        "TSMQR": sum((mt - k - 1) * (nt - k - 1) for k in range(nt)),
    }


def geqrf_kernels(nb: int, ib: int, itemsize: int) -> dict:
    """``{class: (operations, least bytes)}`` of one task of each tile
    kernel. Operations: the leading term of the kernel's LAPACK count
    (GEQRT 4nb³/3, UNMQR 2nb³, TSQRT 2nb³, TSMQR 4nb³); together over
    the grid they come to ``geqrf_ops``'s leading term. Bytes: every
    tile the kernel reads and every tile it writes, once."""
    tile, t = nb * nb * itemsize, ib * nb * itemsize
    return {
        "GEQRT": (4.0 * nb ** 3 / 3.0, 2 * tile + t),       # A; A, T
        "UNMQR": (2.0 * nb ** 3, 3 * tile + t),             # V, T, C; C
        "TSQRT": (2.0 * nb ** 3, 4 * tile + t),     # R, A; R', V2, T
        "TSMQR": (4.0 * nb ** 3, 5 * tile + t),     # V2, T, C1, C2; C1, C2
    }
