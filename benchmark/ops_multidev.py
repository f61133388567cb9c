"""Counts of a tiled Cholesky whose tiles lie 2D-cyclically over a grid of
chips, each task on the chip of the tile it writes (``zpotrf_L.jdf`` with
``dplasma_advise_data_on_device``'s 2D advice, ``testing_dpotrf -g <n>``).

The benchmark's own arithmetic, as ``ops.py``: what the ALGORITHM needs on
that layout, whatever the program does. The graph is the JDF's: POTRF(k)
writes tile (k, k); TRSM(m, k) reads it and writes (m, k); SYRK(m, k)
reads (m, k) and writes (m, m); GEMM(m, n, k) reads (m, k) and (n, k) and
writes (m, n). A tile's updates are a chain on the tile's own chip, so
what has to cross between chips is a FINAL tile read by a task of another
chip, and it has to cross once for each such chip.
"""

from __future__ import annotations

from typing import Dict, Sequence, Set


def chip_of(m: int, n: int, grid: Sequence[int]) -> int:
    """The chip tile (m, n) is advised to, of ``rows x cols`` chips."""
    rows, cols = grid
    return (m % rows) * cols + n % cols


def potrf_tasks_by_chip(nt: int, grid: Sequence[int]) -> Dict[int, int]:
    """Tasks of one factorization by the chip of the tile each writes:
    POTRF(k) writes (k, k), TRSM(m, k) (m, k), SYRK(m, k) (m, m),
    GEMM(m, n, k) (m, n)."""
    return {chip: sum(by_class.values()) for chip, by_class in
            potrf_tasks_by_chip_class(nt, grid).items()}


def potrf_tasks_by_chip_class(nt: int, grid: Sequence[int]
                              ) -> Dict[int, Dict[str, int]]:
    """The same by task class: ``{chip: {class: tasks}}``."""
    tasks = {c: dict.fromkeys(("POTRF", "TRSM", "SYRK", "GEMM"), 0)
             for c in range(grid[0] * grid[1])}
    for k in range(nt):
        tasks[chip_of(k, k, grid)]["POTRF"] += 1
        for m in range(k + 1, nt):
            tasks[chip_of(m, k, grid)]["TRSM"] += 1
            tasks[chip_of(m, m, grid)]["SYRK"] += 1
            for n in range(k + 1, m):
                tasks[chip_of(m, n, grid)]["GEMM"] += 1
    return tasks


def potrf_remote_readers(nt: int, grid: Sequence[int]
                         ) -> Dict[tuple, Set[int]]:
    """``{tile: the chips other than its own that run a reader of its
    final version}``, for every tile with such a reader."""
    out = {}
    for k in range(nt):
        # L(k, k): the TRSMs of column k, each on its tile's chip
        readers = {chip_of(m, k, grid) for m in range(k + 1, nt)}
        out[k, k] = readers - {chip_of(k, k, grid)}
        for m in range(k + 1, nt):
            # L(m, k): SYRK(m, k) on (m, m)'s chip; the GEMMs of row m
            # (m, n, k), k < n < m; the GEMMs of column m (i, m, k), i > m
            readers = {chip_of(m, m, grid)}
            readers |= {chip_of(m, n, grid) for n in range(k + 1, m)}
            readers |= {chip_of(i, m, grid) for i in range(m + 1, nt)}
            out[m, k] = readers - {chip_of(m, k, grid)}
    return {tile: chips for tile, chips in out.items() if chips}


def potrf_min_remote_bytes(nt: int, nb: int, itemsize: int,
                           grid: Sequence[int]) -> int:
    """Least bytes that cross between chips in one factorization: for
    every tile, the chips other than its owner's that run a reader of it,
    each counted once."""
    copies = sum(len(chips) for chips in
                 potrf_remote_readers(nt, grid).values())
    return copies * nb * nb * itemsize


def potrf_min_remote_bytes_into(nt: int, nb: int, itemsize: int,
                                grid: Sequence[int]) -> Dict[int, int]:
    """The same by the chip the bytes cross INTO."""
    into = dict.fromkeys(range(grid[0] * grid[1]), 0)
    for chips in potrf_remote_readers(nt, grid).values():
        for chip in chips:
            into[chip] += nb * nb * itemsize
    return into
