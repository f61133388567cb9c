"""Plain reference of ``dpotrf_ptg_multidev``: what a correct lower factor is,
when its tiles lie on several chips.

The configuration factors A₀ = L·Lᵀ and leaves L in the lower tiles
(i ≥ j) of a tiled collection that stores that triangle alone. A₀ is the
matrix ``dpotrf_panel`` and ``dpotrf_ptg_host`` factor for the same seed
(this file is the latter's reference, copied: a configuration's reference
is its own; :func:`on_probe_chip` is what this one adds): with D the dense array
whose block row j is ``generate.spd_row``, the strictly-lower block (i, j)
of A₀ is the transpose of D's block (j, i), and a diagonal block is the
symmetrized ½(d + dᵀ). This module rebuilds A₀ from the seed and measures

    ‖A₀·x − L·(Lᵀ·x)‖_F / ‖A₀·x‖_F      for 8 random probe vectors x

a block row of D (:func:`probe_input_row`) and a tile of L
(:func:`probe_factor_t`, then :func:`probe_factor`) at a time, so that
neither A₀ nor a second copy of the factor ever exists. Trace them under
``jax.default_matmul_precision("highest")``: the probe measures the
factor and must add no bf16 noise of its own. Tile and row indices may be
traced, so one program serves every tile.

The factor's tiles lie where the deployment advised them, 2D-cyclically
over the chips, and the whole triangle fits no one chip: the probe runs
on ONE chip and takes each tile there as it comes to it
(:func:`on_probe_chip`), so that at most one tile of another chip is on
the probe's chip at a time. It knows nothing of the program under test: a
tile is a ``jax.Array``, wherever it is.

At test sizes :func:`dense_a0` gives A₀ whole, for ``numpy.linalg.cholesky``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import generate

PROBES = 8


def on_probe_chip(tile, device):
    """``tile`` on ``device``, the chip the probe runs on: as it is where
    it already lies there, else a copy the caller drops after one use."""
    return tile if tile.devices() == {device} else \
        jax.device_put(tile, device)


def probe_vectors(key, n: int):
    return jax.random.normal(jax.random.fold_in(key, 1 << 20), (n, PROBES),
                             jnp.float32)


def probe_input_row(j, key, x, y, *, n: int, nb: int):
    """Add to ``y = A₀·x`` what block row ``j`` of D holds of A₀: the
    diagonal block (j, j) and, right of it, the blocks (j, c) = (c, j)ᵀ."""
    c = jnp.arange(n)[None, :]
    d_row = generate.spd_row(key, j, n, nb)
    tail = jnp.where(c >= (j + 1) * nb, d_row, jnp.float32(0))
    xj = lax.dynamic_slice(x, (j * nb, 0), (nb, PROBES))
    d = lax.dynamic_slice(d_row, (0, j * nb), (nb, nb))
    y = y + tail.T @ xj                     # the lower blocks (c, j), c > j
    yj = lax.dynamic_slice(y, (j * nb, 0), (nb, PROBES))
    yj = yj + 0.5 * (d + d.T) @ xj + tail @ x
    return lax.dynamic_update_slice(y, yj, (j * nb, 0))


def _lower(i, j, tile):
    """Tile (i, j) of L as the factor reads it, in float32: whatever a
    diagonal tile holds above its diagonal is ignored."""
    nb = tile.shape[0]
    r = jnp.arange(nb)[:, None]
    c = jnp.arange(nb)[None, :]
    return jnp.where((i > j) | (r >= c), tile.astype(jnp.float32),
                     jnp.float32(0))


def probe_factor_t(i, j, tile, x, z):
    """Add tile (i, j)'s share to ``z = Lᵀ·x`` (block j of z)."""
    nb = tile.shape[0]
    xi = lax.dynamic_slice(x, (i * nb, 0), (nb, PROBES))
    zj = lax.dynamic_slice(z, (j * nb, 0), (nb, PROBES))
    return lax.dynamic_update_slice(
        z, zj + _lower(i, j, tile).T @ xi, (j * nb, 0))


def probe_factor(i, j, tile, z, y2):
    """Add tile (i, j)'s share to ``y2 = L·z`` (block i of y2)."""
    nb = tile.shape[0]
    zj = lax.dynamic_slice(z, (j * nb, 0), (nb, PROBES))
    yi = lax.dynamic_slice(y2, (i * nb, 0), (nb, PROBES))
    return lax.dynamic_update_slice(
        y2, yi + _lower(i, j, tile) @ zj, (i * nb, 0))


def residual(y, y2) -> float:
    return float(jnp.linalg.norm(y2 - y) / jnp.linalg.norm(y))


def dense_a0(key, n: int, nb: int):
    """A₀ whole, as float64 numpy — test sizes only."""
    import numpy as np
    d = np.asarray(generate.spd_matrix(key, n, nb), np.float64)
    a0 = np.zeros_like(d)
    for j in range(n // nb):
        blk = slice(j * nb, (j + 1) * nb)
        a0[blk, blk] = 0.5 * (d[blk, blk] + d[blk, blk].T)
        upper = d[blk, (j + 1) * nb:]
        a0[blk, (j + 1) * nb:] = upper
        a0[(j + 1) * nb:, blk] = upper.T
    return a0
