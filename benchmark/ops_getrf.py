"""Operation and byte counts of the tile LU by incremental pivoting the
``dgetrf_incpiv_ptg_host`` cells run (``ops.py``'s rules: what the
ALGORITHM needs; the extra triangular solves of a finite inner block
``ib`` and the row exchanges do not count).
"""

from __future__ import annotations


def getrf_ops(n: int) -> float:
    """Floating-point operations of the LU factorization of an n×n
    matrix: LAPACK working note 41's count for ``dgetrf`` (n³/3 − n/3
    multiplications, n³/3 − n²/2 + n/6 additions)."""
    return 2.0 * n ** 3 / 3.0 - n * n / 2.0 - n / 6.0


def getrf_l_tiles(nt: int) -> int:
    """Tiles of descL the factorization writes: one beside every tile of
    A under the diagonal."""
    return nt * (nt - 1) // 2


def getrf_ipiv_tiles(nt: int) -> int:
    """Tiles of descIPIV it writes: one beside every tile of A on and
    under the diagonal."""
    return nt * (nt + 1) // 2


def getrf_stored_bytes(n: int, nb: int, ib: int, itemsize: int,
                       int_size: int = 4) -> int:
    """Bytes of the factored form: A, the written tiles of L (``ib`` ×
    nb) and of IPIV (nb integers)."""
    nt = n // nb
    return itemsize * (n * n + getrf_l_tiles(nt) * ib * nb) + \
        int_size * getrf_ipiv_tiles(nt) * nb


def getrf_min_bytes(n: int, nb: int, ib: int, itemsize: int,
                    int_size: int = 4) -> float:
    """Least memory traffic of an in-place factorization: A read once
    and written once, L and IPIV written once."""
    return float(getrf_stored_bytes(n, nb, ib, itemsize, int_size)
                 + itemsize * n * n)


def getrf_tasks(nt: int) -> dict:
    """Tasks of zgetrf_incpiv.jdf's four classes over an nt×nt grid."""
    pairs = nt * (nt - 1) // 2
    return {
        "GETRF": nt,
        "GESSM": pairs,
        "TSTRF": pairs,
        "SSSSM": sum((nt - k - 1) ** 2 for k in range(nt)),
    }


def getrf_kernels(nb: int, ib: int, itemsize: int,
                  int_size: int = 4) -> dict:
    """``{class: (operations, least bytes)}`` of one task of each tile
    kernel. Operations: the leading term of the kernel's count (GETRF
    2nb³/3, GESSM nb³, TSTRF nb³, SSSSM 2nb³); together over the grid
    they come to ``getrf_ops``'s leading term. Bytes: every tile the
    kernel reads and every tile it writes, once."""
    tile, low, piv = nb * nb * itemsize, ib * nb * itemsize, nb * int_size
    return {
        "GETRF": (2.0 * nb ** 3 / 3.0, 3 * tile + piv),     # A; A, U, IPIV
        "GESSM": (1.0 * nb ** 3, 3 * tile + piv),           # L, P, C; C
        "TSTRF": (1.0 * nb ** 3, 4 * tile + low + piv),
        #                                       U, A; U, L21, L, IPIV
        "SSSSM": (2.0 * nb ** 3, 5 * tile + low + piv),
        #                                       L21, L, P, A1, A2; A1, A2
    }
