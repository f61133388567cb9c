"""Plain reference of ``dgetrf_incpiv_ptg_host``: what a correct factored
form is.

The configuration factors A₀ by tile LU with incremental pivoting and
leaves, as ``dgetrf_incpiv`` does, U in the upper triangle of the tiled
collection A and the transformation that took A₀ to it, in task order, in
three places:

* tile (k, k) of A, strictly under its diagonal: the unit lower L_kk of
  GETRF(k), with tile (k, k) of IPIV the permutation of its pivoting:
  ``P_k·X = X[perm]`` (1 × nb int32), ``P_k·A_kk = L_kk·U_kk``;
* tile (m, k) of A, m > k: the multipliers L21 of TSTRF(k, m), nb × nb,
  block b the columns ``c_b = [b·ib, (b+1)·ib)``; tile (m, k) of L (ib × nb):
  the blocks' L11_b, unit lower, side by side; tile (m, k) of IPIV: the
  blocks' interchanges as LAPACK writes them, ``ipiv[b·ib + j] = p``: at
  step j of block b the rows j and p of the stack [the rows c_b of the
  upper operand; the nb rows of the lower one] were exchanged (p = j:
  none; p ≥ ib: row p − ib of the lower operand).

The transformation, applied to a block of vectors y cut into block rows:

    for k = 0 .. nt−1:
        y_k ← L_kk⁻¹ · y_k[perm_kk]
        for m = k+1 .. nt−1, for b = 0 .. nb/ib − 1:
            the block's interchanges between y_k[c_b] and y_m, in order
            y_k[c_b] ← L11_b⁻¹ · y_k[c_b]
            y_m ← y_m − L21_b · y_k[c_b]

:func:`apply_l` does that and :func:`apply_l_inverse` undoes it (M, with
A₀ = M·U), tile by tile in float32 with no kernel of the program: the
interchanges one at a time in a loop, as they are written. Trace them
under ``jax.default_matmul_precision("highest")``. A₀ is rebuilt from the
seed a block row at a time (``generate.tile``, uniform in [−0.5, 0.5) as
``dplasma_dplrnt`` makes it; tile (i, j) has index ``i·nt + j``). Tile
indices may be traced: one program serves every tile.

At test sizes :func:`factor_plain` is the whole tile algorithm in plain
loops (numpy float32, one column and one interchange at a time), and
:func:`dense_a0` gives A₀ whole.
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


def probe_u(i, j, tile, x, y):
    """Add tile (i, j)'s share, i ≤ j, to ``y = U·x``: what a diagonal
    tile holds under its diagonal is L and is not read."""
    nb = tile.shape[0]
    r = jnp.arange(nb)[:, None]
    c = jnp.arange(nb)[None, :]
    t = jnp.where((i < j) | (r <= c), tile.astype(jnp.float32),
                  jnp.float32(0))
    return _put(y, i, _rows(y, i, nb) + t @ _rows(x, j, nb))


def solve_u_row(i, diag, z, x):
    """Block ``i`` of ``x = U⁻¹·z`` once the blocks under it are in
    ``x`` and their products have been taken off ``z``
    (:func:`solve_u_off`): ``x_i = U_ii⁻¹·z_i``."""
    nb = diag.shape[0]
    xi = lax.linalg.triangular_solve(
        jnp.triu(diag.astype(jnp.float32)), _rows(z, i, nb),
        left_side=True, lower=False)
    return _put(x, i, xi)


def solve_u_off(i, j, tile, z, x):
    """``z_i ← z_i − U_ij·x_j``, i < j."""
    nb = tile.shape[0]
    return _put(z, i, _rows(z, i, nb) -
                tile.astype(jnp.float32) @ _rows(x, j, nb))


def _unit_lower(t):
    n = t.shape[0]
    return jnp.tril(t.astype(jnp.float32), -1) + jnp.eye(n, dtype=jnp.float32)


def apply_diagonal(k, tile, perm, y, *, inverse: bool):
    """``y_k ← L_kk⁻¹·P_k·y_k`` (``inverse``: ``P_kᵀ·L_kk·y_k``)."""
    nb = tile.shape[0]
    low, yk, perm = _unit_lower(tile), _rows(y, k, nb), perm[0]
    if inverse:
        yk = jnp.zeros_like(yk).at[perm].set(low @ yk)
    else:
        yk = lax.linalg.triangular_solve(
            low, yk[perm], left_side=True, lower=True, unit_diagonal=True)
    return _put(y, k, yk)


def _interchanges(top, bot, piv, *, inverse: bool):
    """The block's interchanges between ``top`` (ib rows) and ``bot``,
    one at a time in the order they were made (``inverse``: undone, last
    first)."""
    ib = top.shape[0]

    def one(t, stack):
        j = ib - 1 - t if inverse else t
        rj, rp = stack[j], stack[piv[j]]
        return stack.at[j].set(rp).at[piv[j]].set(rj)

    stack = lax.fori_loop(0, ib, one, jnp.concatenate([top, bot], axis=0))
    return stack[:ib], stack[ib:]


def apply_pair(k, m, l21, l_tile, ipiv, y, *, inverse: bool):
    """TSTRF(k, m)'s transformation on block rows k and m of ``y``
    (``inverse``: undone)."""
    ib, nb = l_tile.shape
    yk, ym = _rows(y, k, nb), _rows(y, m, nb)
    l21, l_tile, ipiv = l21.astype(jnp.float32), \
        l_tile.astype(jnp.float32), ipiv[0]
    starts = range(0, nb, ib)
    for o in (reversed(starts) if inverse else starts):
        J = slice(o, o + ib)
        low = _unit_lower(l_tile[:, J])
        if inverse:
            ym = ym + l21[:, J] @ yk[J]
            top, ym = _interchanges(low @ yk[J], ym, ipiv[J], inverse=True)
        else:
            top, ym = _interchanges(yk[J], ym, ipiv[J], inverse=False)
            top = lax.linalg.triangular_solve(
                low, top, left_side=True, lower=True, unit_diagonal=True)
            ym = ym - l21[:, J] @ top
        yk = yk.at[J].set(top)
    return _put(_put(y, k, yk), m, ym)


_diagonal = jax.jit(apply_diagonal, static_argnames="inverse")
_pair = jax.jit(apply_pair, static_argnames="inverse")
_solve_row, _solve_off = jax.jit(solve_u_row), jax.jit(solve_u_off)


def apply_l(a_tile, l_tile, p_tile, y, nt: int):
    """The stored transformation on ``y`` (what ``dgetrs_incpiv`` does to
    a right-hand side before its solve with U): ``a_tile(i, j)``,
    ``l_tile(i, j)`` and ``p_tile(i, j)`` give the tiles of A, L and
    IPIV."""
    for k in range(nt):
        y = _diagonal(k, a_tile(k, k), p_tile(k, k), y, inverse=False)
        for m in range(k + 1, nt):
            y = _pair(k, m, a_tile(m, k), l_tile(m, k), p_tile(m, k), y,
                      inverse=False)
    return y


def apply_l_inverse(a_tile, l_tile, p_tile, y, nt: int):
    """``M·y``, M the inverse of the stored transformation: A₀ = M·U."""
    for k in reversed(range(nt)):
        for m in reversed(range(k + 1, nt)):
            y = _pair(k, m, a_tile(m, k), l_tile(m, k), p_tile(m, k), y,
                      inverse=True)
        y = _diagonal(k, a_tile(k, k), p_tile(k, k), y, inverse=True)
    return y


def solve_u(a_tile, z, nt: int):
    """``U⁻¹·z`` by block back substitution on the upper tiles of A."""
    x = jnp.zeros_like(z)
    for i in reversed(range(nt)):
        for j in range(i + 1, nt):
            z = _solve_off(i, j, a_tile(i, j), z, x)
        x = _solve_row(i, a_tile(i, i), z, x)
    return x


def norm(x) -> float:
    return float(jnp.linalg.norm(x))


# -- what the stored factors have to be, tile by tile ----------------------

@jax.jit
def multipliers_diagonal(tile):
    """max |L_kk| under the diagonal."""
    return jnp.max(jnp.abs(jnp.tril(tile.astype(jnp.float32), -1)))


@jax.jit
def multipliers_pair(l21, l_tile):
    """max of |L21| and of |L11_b − I| over the blocks (an L11 that is
    not unit lower shows here too)."""
    ib, nb = l_tile.shape
    eye = jnp.tile(jnp.eye(ib, dtype=jnp.float32), (1, nb // ib))
    return jnp.maximum(jnp.max(jnp.abs(l21.astype(jnp.float32))),
                       jnp.max(jnp.abs(l_tile.astype(jnp.float32) - eye)))


@jax.jit
def permutation_valid(perm):
    """A diagonal tile's IPIV: every index of 0 .. nb−1 once."""
    nb = perm.shape[1]
    return jnp.all(jnp.sort(perm[0]) == jnp.arange(nb, dtype=perm.dtype))


def interchanges_valid(ipiv, ib: int):
    """A pair's IPIV: step j of a block exchanges row j with itself or
    with a row of the stack under it, ``j ≤ p < ib + nb``."""
    nb = ipiv.shape[1]
    j = jnp.arange(nb, dtype=ipiv.dtype) % ib
    return jnp.all((ipiv[0] >= j) & (ipiv[0] < ib + nb))


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


# -- test sizes: the whole algorithm in plain loops ------------------------

def dense_a0(key, nt: int, nb: int):
    """A₀ whole (nt·nb square), as float32 numpy — test sizes only."""
    import numpy as np
    return np.concatenate([np.asarray(input_row(i, key, nt, nb), np.float32)
                           for i in range(nt)], axis=0)


def factor_plain(a0, nb: int, ib: int):
    """The tile algorithm on the dense ``a0`` (numpy), one column and one
    interchange at a time in float32 -> ``(A, L, IPIV)`` as dicts of
    tiles in the format above. Test sizes only."""
    import numpy as np
    n = a0.shape[0]
    nt = n // nb
    a = {(i, j): np.array(a0[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb],
                          np.float32) for i in range(nt) for j in range(nt)}
    low, piv = {}, {}

    def eliminate(stack, ncols):
        """Partial pivoting on the first ``ncols`` columns of ``stack``,
        in place, every column to the right updated with them: the
        interchange indices, step j's search from row j on."""
        out = []
        for j in range(ncols):
            p = j + int(np.argmax(np.abs(stack[j:, j])))
            out.append(p)
            stack[[j, p]] = stack[[p, j]]
            stack[j + 1:, j] /= stack[j, j]
            stack[j + 1:, j + 1:] -= np.outer(
                stack[j + 1:, j], stack[j, j + 1:]).astype(np.float32)
        return out

    def swap(stack, interchanges):
        for j, p in enumerate(interchanges):
            stack[[j, p]] = stack[[p, j]]

    for k in range(nt):
        # GETRF(k): LAPACK's interchanges, kept as the permutation
        t = a[k, k]
        perm = np.arange(nb)
        for j, p in enumerate(eliminate(t, nb)):
            perm[[j, p]] = perm[[p, j]]
        piv[k, k] = perm[None, :].astype(np.int32)
        lkk = np.tril(t, -1) + np.eye(nb, dtype=np.float32)
        for j in range(k + 1, nt):      # GESSM(k, j)
            a[k, j] = np.linalg.solve(
                lkk.astype(np.float64),
                a[k, j][perm].astype(np.float64)).astype(np.float32)
        u = np.triu(t)
        for m in range(k + 1, nt):      # TSTRF(k, m), then its SSSSMs
            low[m, k] = np.zeros((ib, nb), np.float32)
            piv[m, k] = np.zeros((1, nb), np.int32)
            for o in range(0, nb, ib):
                # the block's columns and those to their right, of the
                # block's rows of U over the lower tile: what lies to the
                # left (earlier blocks' multipliers) is not exchanged
                work = np.concatenate([u[o:o + ib, o:], a[m, k][:, o:]],
                                      axis=0)
                piv[m, k][0, o:o + ib] = eliminate(work, ib)
                low[m, k][:, o:o + ib] = np.tril(work[:ib, :ib], -1) + \
                    np.eye(ib, dtype=np.float32)
                u[o:o + ib, o:o + ib] = np.triu(work[:ib, :ib])
                u[o:o + ib, o + ib:] = work[:ib, ib:]
                a[m, k][:, o:] = work[ib:]
            for j in range(k + 1, nt):  # SSSSM(k, m, j)
                for o in range(0, nb, ib):
                    stack = np.concatenate([a[k, j][o:o + ib], a[m, j]],
                                           axis=0)
                    swap(stack, piv[m, k][0, o:o + ib])
                    top = np.linalg.solve(
                        low[m, k][:, o:o + ib].astype(np.float64),
                        stack[:ib].astype(np.float64)).astype(np.float32)
                    a[k, j][o:o + ib] = top
                    a[m, j] = stack[ib:] - \
                        (a[m, k][:, o:o + ib] @ top).astype(np.float32)
        a[k, k] = np.tril(t, -1) + u
    return a, low, piv
