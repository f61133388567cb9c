"""Plain reference of ``dgetrf_1d_ptg_host``: what a correct factored
form is.

The configuration factors A₀ by tile LU with partial pivoting over whole
panels and leaves LAPACK's ``dgetrf`` form: P·A₀ = L·U with U in the upper
triangle of the tiled collection A (diagonal included), the unit lower L
strictly under it with EVERY interchange applied to its rows (what
``dgetrs`` reads), and in tile (k, 0) of IPIV (1 × nb int32) panel k's
interchange indices as LAPACK writes them, 0-based from the panel's first
row: at step j of panel k the rows k·nb + j and k·nb + ipiv[j] of the
matrix were exchanged, j ≤ ipiv[j] < (nt − k)·nb.

Everything here is straightforward ``jax.numpy`` in float32 with no
kernel of the program; trace it under
``jax.default_matmul_precision("highest")``. :func:`apply_p` is LAPACK's
``dlaswp``, one interchange at a time in the order they were made;
:func:`apply_l` / :func:`solve_l` and :func:`apply_u` / :func:`solve_u`
multiply by and solve with the triangles tile by tile (tile indices may
be traced: one program serves every tile). A₀ is rebuilt from the seed a
block row at a time (``generate.tile``, uniform in [−0.5, 0.5) as
``dplasma_dplrnt`` makes it; tile (i, j) has index ``i·nt + j``: the
``dgetrf_incpiv_ptg_host`` cell's matrix for the same seed).

At test sizes :func:`dense_a0` gives A₀ whole and :func:`factor_plain`
is ``dgetf2`` in plain numpy loops, one column at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import generate

PROBES = 8


def probe_vectors(key, n: int):
    return jax.random.normal(jax.random.fold_in(key, 1 << 20), (n, PROBES),
                             jnp.float32)


def input_row(i, key, nt: int, nb: int):
    """Block row ``i`` of A₀ (nb × nt·nb); ``i`` may be traced."""
    tiles = jax.vmap(lambda j: generate.tile(key, i * nt + j, nb))(
        jnp.arange(nt))
    return tiles.transpose(1, 0, 2).reshape(nb, nt * nb)


def _rows(x, i, nb: int):
    return lax.dynamic_slice(x, (i * nb, 0), (nb, x.shape[1]))


def _put(x, i, rows):
    return lax.dynamic_update_slice(x, rows, (i * rows.shape[0], 0))


def probe_input_row(i, key, x, y, sq, *, nt: int, nb: int):
    """Block ``i`` of ``y = A₀·x``, and ``sq`` plus the block row's
    share of ‖A₀‖_F²."""
    row = input_row(i, key, nt, nb)
    return _put(y, i, row @ x), sq + jnp.sum(row * row)


# -- P: the interchanges, as dlaswp applies them ---------------------------

def swap_panel(k, ipiv, y, *, inverse: bool = False):
    """Panel ``k``'s interchanges on the rows of ``y``, one at a time in
    the order they were made (``inverse``: undone, last first)."""
    nb = ipiv.shape[1]
    piv = ipiv[0]

    def one(t, y):
        j = nb - 1 - t if inverse else t
        a, b = k * nb + j, k * nb + piv[j]
        ra, rb = y[a], y[b]
        return y.at[a].set(rb).at[b].set(ra)

    return lax.fori_loop(0, nb, one, y)


_swap_panel = jax.jit(swap_panel, static_argnames="inverse")


def apply_p(p_tile, y, nt: int):
    """``P·y``: every panel's interchanges, first panel first."""
    for k in range(nt):
        y = _swap_panel(k, p_tile(k), y)
    return y


def apply_pt(p_tile, y, nt: int):
    """``Pᵀ·y``: the interchanges undone, last first."""
    for k in reversed(range(nt)):
        y = _swap_panel(k, p_tile(k), y, inverse=True)
    return y


# -- L and U, tile by tile ---------------------------------------------------

def _unit_lower(t):
    n = t.shape[0]
    return jnp.tril(t.astype(jnp.float32), -1) + jnp.eye(n, dtype=jnp.float32)


def _l_part(i, j, tile):
    """Tile (i, j)'s part of L, i ≥ j."""
    return jnp.where(i == j, _unit_lower(tile), tile.astype(jnp.float32))


def _u_part(i, j, tile):
    """Tile (i, j)'s part of U, i ≤ j."""
    t = tile.astype(jnp.float32)
    return jnp.where(i == j, jnp.triu(t), t)


@jax.jit
def probe_l(i, j, tile, x, y):
    """Add tile (i, j)'s share, i ≥ j, to ``y = L·x``."""
    nb = tile.shape[0]
    return _put(y, i, _rows(y, i, nb) + _l_part(i, j, tile) @ _rows(x, j, nb))


@jax.jit
def probe_u(i, j, tile, x, y):
    """Add tile (i, j)'s share, i ≤ j, to ``y = U·x``."""
    nb = tile.shape[0]
    return _put(y, i, _rows(y, i, nb) + _u_part(i, j, tile) @ _rows(x, j, nb))


@jax.jit
def _off(i, j, tile, z, x):
    """``z_i ← z_i − T_ij·x_j`` for an off-diagonal tile."""
    nb = tile.shape[0]
    return _put(z, i, _rows(z, i, nb) -
                tile.astype(jnp.float32) @ _rows(x, j, nb))


@jax.jit
def _solve_l_row(i, diag, z, x):
    nb = diag.shape[0]
    return _put(x, i, lax.linalg.triangular_solve(
        _unit_lower(diag), _rows(z, i, nb), left_side=True, lower=True,
        unit_diagonal=True))


@jax.jit
def _solve_u_row(i, diag, z, x):
    nb = diag.shape[0]
    return _put(x, i, lax.linalg.triangular_solve(
        jnp.triu(diag.astype(jnp.float32)), _rows(z, i, nb),
        left_side=True, lower=False))


def apply_l(a_tile, x, nt: int):
    """``L·x`` from the tiles of A (``a_tile(i, j)``)."""
    y = jnp.zeros_like(x)
    for i in range(nt):
        for j in range(i + 1):
            y = probe_l(i, j, a_tile(i, j), x, y)
    return y


def apply_u(a_tile, x, nt: int):
    """``U·x`` from the tiles of A."""
    y = jnp.zeros_like(x)
    for i in range(nt):
        for j in range(i, nt):
            y = probe_u(i, j, a_tile(i, j), x, y)
    return y


def solve_l(a_tile, z, nt: int):
    """``L⁻¹·z`` by block forward substitution."""
    x = jnp.zeros_like(z)
    for i in range(nt):
        for j in range(i):
            z = _off(i, j, a_tile(i, j), z, x)
        x = _solve_l_row(i, a_tile(i, i), z, x)
    return x


def solve_u(a_tile, z, nt: int):
    """``U⁻¹·z`` by block back substitution."""
    x = jnp.zeros_like(z)
    for i in reversed(range(nt)):
        for j in range(i + 1, nt):
            z = _off(i, j, a_tile(i, j), z, x)
        x = _solve_u_row(i, a_tile(i, i), z, x)
    return x


def norm(x) -> float:
    return float(jnp.linalg.norm(x))


# -- what the stored factors have to be, tile by tile ----------------------

@jax.jit
def multipliers(i, j, tile):
    """max |L_ij| over tile (i, j)'s part of L's STRICT lower triangle,
    i ≥ j: the whole tile under the diagonal, a diagonal tile's entries
    under its own. Partial pivoting over the whole column leaves none
    over 1, in any tile; a search that stopped at the panel's first tile
    (or pairwise pivoting) leaves larger ones in the tiles below."""
    t = jnp.abs(tile.astype(jnp.float32))
    return jnp.max(jnp.where(i == j, jnp.tril(t, -1), t))


@jax.jit
def pivots_valid(k, ipiv, nt):
    """Panel ``k``'s IPIV: ``j ≤ ipiv[j] < (nt − k)·nb``."""
    nb = ipiv.shape[1]
    j = jnp.arange(nb, dtype=ipiv.dtype)
    return jnp.all((ipiv[0] >= j) & (ipiv[0] < (nt - k) * nb))


@jax.jit
def low_bits_share(tile):
    """The share of a float32 tile's entries (those that are neither 0
    nor ±1) whose low 16 bits are all zero, that is, which a bfloat16
    holds exactly: about 2⁻¹⁶ of what float32 arithmetic left, all of a
    tile that was computed, rounded or stored below float32."""
    t = tile.astype(jnp.float32)
    counted = (t != 0) & (jnp.abs(t) != 1)
    coarse = (lax.bitcast_convert_type(t, jnp.uint32) & 0xFFFF) == 0
    return jnp.sum(counted & coarse) / jnp.maximum(jnp.sum(counted), 1)


# -- test sizes ------------------------------------------------------------

def dense_a0(key, nt: int, nb: int):
    """A₀ whole (nt·nb square), as float32 numpy — test sizes only."""
    import numpy as np
    return np.concatenate([np.asarray(input_row(i, key, nt, nb), np.float32)
                           for i in range(nt)], axis=0)


def factor_plain(a0):
    """LAPACK's ``dgetf2`` on the dense ``a0`` in plain numpy loops, one
    column at a time in float32 -> ``(LU, ipiv)``, ``ipiv`` 0-based from
    the MATRIX's first row (``scipy.linalg.lu_factor``'s convention).
    Test sizes only."""
    import numpy as np
    a = np.array(a0, np.float32)
    n = a.shape[0]
    ipiv = np.zeros(n, np.int32)
    for j in range(n):
        p = j + int(np.argmax(np.abs(a[j:, j])))
        ipiv[j] = p
        a[[j, p]] = a[[p, j]]
        a[j + 1:, j] /= a[j, j]
        a[j + 1:, j + 1:] -= np.outer(a[j + 1:, j], a[j, j + 1:]).astype(
            np.float32)
    return a, ipiv
