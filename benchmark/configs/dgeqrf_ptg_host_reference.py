"""Plain reference of ``dgeqrf_ptg_host``: what a correct factored form is.

The configuration factors A₀ = Q·R and leaves, as ``dgeqrf`` does, R in the
upper triangle of the tiled collection A, the Householder vectors V under
it, and the block reflectors' triangular factors in the collection T:

* tile (k, k) of A: R on and above its diagonal; below it the unit lower
  V of GEQRT(k), nb/ib block reflectors ``Q_j = I − V_j·T_j·V_jᵀ``, block j
  the columns ``J = [j·ib, (j+1)·ib)`` and the rows from ``j·ib`` on, the
  ``T_j`` (upper triangular, ib × ib) side by side in tile (k, k) of T;
* tile (m, k), m > k: V₂ of TSQRT(m, k): block j is ``V_j = [e_J; V₂[:, J]]``
  over block rows k and m, its ``T_j`` in tile (m, k) of T;
* Q = Π_k ( Q_kk · Π_{m>k} Q_mk ), k ascending, m ascending, each tile's
  factor ``Q_1 ⋯ Q_{nb/ib}``.

:func:`apply_q` and :func:`apply_qt` follow that format tile by tile on a
block of vectors, in float32 with no kernel of the program; trace them
under ``jax.default_matmul_precision("highest")``. A₀ is rebuilt from the
seed a block row at a time (``generate.tile``, uniform in [−0.5, 0.5) as
``dplasma_dplrnt`` makes it; tile (i, j) has index ``i·nt + j``), so
neither A₀ nor a dense Q ever exists. Tile indices may be traced: one
program serves every tile.

At test sizes :func:`dense_a0` and :func:`dense_q` give both whole.
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


def probe_input_row(i, key, x, y, *, nt: int, nb: int):
    """Block ``i`` of ``y = A₀·x``."""
    return _put(y, i, input_row(i, key, nt, nb) @ x)


def probe_r(i, j, tile, x, y):
    """Add tile (i, j)'s share, i ≤ j, to ``y = R·x``: what a diagonal
    tile holds under its diagonal is V and is not read."""
    nb = tile.shape[0]
    r = jnp.arange(nb)[:, None]
    c = jnp.arange(nb)[None, :]
    t = jnp.where((i < j) | (r <= c), tile.astype(jnp.float32),
                  jnp.float32(0))
    return _put(y, i, _rows(y, i, nb) + t @ _rows(x, j, nb))


def _blocks(nb: int, ib: int, trans: bool):
    """Q = Q_1 ⋯ Q_p: Q·x takes the last block first, Qᵀ·x the first."""
    starts = list(range(0, nb, ib))
    return starts if trans else starts[::-1]


def apply_diagonal(k, tile, t_tile, x, *, trans: bool):
    """``x ← Q_kk·x`` (``trans``: ``Q_kkᵀ·x``): block row k of ``x``."""
    ib, nb = t_tile.shape
    xk = _rows(x, k, nb)
    tile, t_tile = tile.astype(jnp.float32), t_tile.astype(jnp.float32)
    for o in _blocks(nb, ib, trans):
        v = jnp.tril(tile[o:, o:o + ib], -1) + jnp.eye(nb - o, ib,
                                                        dtype=jnp.float32)
        t = t_tile[:, o:o + ib]
        w = (t.T if trans else t) @ (v.T @ xk[o:])
        xk = xk.at[o:].add(-v @ w)
    return _put(x, k, xk)


def apply_below(k, m, v2, t_tile, x, *, trans: bool):
    """``x ← Q_mk·x`` (``trans``: ``Q_mkᵀ·x``): block rows k and m."""
    ib, nb = t_tile.shape
    xk, xm = _rows(x, k, nb), _rows(x, m, nb)
    v2, t_tile = v2.astype(jnp.float32), t_tile.astype(jnp.float32)
    for o in _blocks(nb, ib, trans):
        v, t = v2[:, o:o + ib], t_tile[:, o:o + ib]
        w = (t.T if trans else t) @ (xk[o:o + ib] + v.T @ xm)
        xk = xk.at[o:o + ib].add(-w)
        xm = xm - v @ w
    return _put(_put(x, k, xk), m, xm)


_diagonal = jax.jit(apply_diagonal, static_argnames="trans")
_below = jax.jit(apply_below, static_argnames="trans")


def apply_qt(a_tile, t_tile, x, mt: int, nt: int):
    """``Qᵀ·x`` from the factored form: ``a_tile(i, j)`` and
    ``t_tile(i, j)`` give the tiles of A and of T."""
    for k in range(nt):
        x = _diagonal(k, a_tile(k, k), t_tile(k, k), x, trans=True)
        for m in range(k + 1, mt):
            x = _below(k, m, a_tile(m, k), t_tile(m, k), x, trans=True)
    return x


def apply_q(a_tile, t_tile, x, mt: int, nt: int):
    """``Q·x`` from the factored form."""
    for k in reversed(range(nt)):
        for m in reversed(range(k + 1, mt)):
            x = _below(k, m, a_tile(m, k), t_tile(m, k), x, trans=False)
        x = _diagonal(k, a_tile(k, k), t_tile(k, k), x, trans=False)
    return x


def norm(x) -> float:
    return float(jnp.linalg.norm(x))


def dense_a0(key, mt: int, nt: int, nb: int):
    """A₀ whole (mt·nb × nt·nb), as float64 numpy — test sizes only."""
    import numpy as np
    return np.concatenate([np.asarray(input_row(i, key, nt, nb), np.float64)
                           for i in range(mt)], axis=0)


def dense_q(a_tile, t_tile, mt: int, nt: int, nb: int):
    """Q whole (mt·nb square), as float64 numpy — test sizes only."""
    import numpy as np
    with jax.default_matmul_precision("highest"):
        eye = jnp.eye(mt * nb, dtype=jnp.float32)
        return np.asarray(apply_q(a_tile, t_tile, eye, mt, nt), np.float64)
