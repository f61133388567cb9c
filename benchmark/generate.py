"""The one generator of every cell's inputs: on the device, from ``--seed``.

A traffic mix is a data file (``workloads/<cell>.json``) of sizes; this
module turns sizes and a seed into arrays. Values are uniform in
[-0.5, 0.5) as DPLASMA's own generators (``dplasma_dplrnt``/``dplgsy``)
make them; the SPD input adds ``n`` on the diagonal (``dplgsy``'s bump),
which makes it strictly diagonally dominant, so no factorization fails.
Everything is keyed so that one block row or one tile can be made again
alone: the reference check regenerates its inputs piecewise and never
holds a second copy of a matrix.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def step_key(seed: int, step: int):
    """The key of step ``step`` of a run started with ``--seed seed``."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), step)


def _uniform(key, shape):
    return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)


def spd_row(key, j, n: int, nb: int):
    """Block row ``j`` (``nb`` × ``n``) of the dense symmetric positive
    definite input; ``j`` may be traced."""
    row = _uniform(jax.random.fold_in(key, j), (nb, n))
    r = jnp.arange(nb)[:, None]
    c = jnp.arange(n)[None, :]
    return row + jnp.where(c == j * nb + r, jnp.float32(n), jnp.float32(0))


def spd_matrix(key, n: int, nb: int):
    """All ``n // nb`` block rows as one ``n`` × ``n`` array, made in one
    fused program (jit this; give it ``out_shardings`` to make each
    chip's rows on that chip)."""
    rows = jax.vmap(lambda j: spd_row(key, j, n, nb))(jnp.arange(n // nb))
    return rows.reshape(n, n)


def tile(key, index, nb: int):
    """Tile ``index`` (``nb`` × ``nb``) of a general matrix; ``index`` may
    be traced, so one compiled program makes every tile."""
    return _uniform(jax.random.fold_in(key, index), (nb, nb))
