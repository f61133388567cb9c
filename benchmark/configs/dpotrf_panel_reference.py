"""Plain reference of ``dpotrf_panel``: what a correct factor satisfies.

The configuration factors A₀ = L·Lᵀ. The system under test stores the
input as the dense array D (block row j of D made by
``generate.spd_row``), reads the strictly-upper blocks of D as the
strictly-lower blocks of A₀ (transposed) and the symmetrized diagonal
blocks ½(d + dᵀ), and returns Lᵀ in the upper triangle of an array of
the same shape. This module rebuilds A₀ from the seed and measures

    ‖A₀·x − L·(Lᵀ·x)‖_F / ‖A₀·x‖_F      for 8 random probe vectors x

one block row at a time, so that neither A₀ nor a second copy of the
factor ever exists: N = 65536 cannot be gathered onto one chip. Trace
:func:`probe_row` under ``jax.default_matmul_precision("highest")`` —
the probe measures the factor and must not add bf16 noise of its own.

At test sizes :func:`dense_a0` gives A₀ whole, for ``numpy.linalg.cholesky``.
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


def probe_row(j, factor_row, key, x, y, y2, *, n: int, nb: int):
    """Add block row ``j``'s share to ``y = A₀·x`` and ``y2 = L·(Lᵀ·x)``.
    ``factor_row`` is block row ``j`` (``nb`` × ``n``) of the returned
    array; whatever it holds below the diagonal is ignored."""
    r = jnp.arange(nb)[:, None]
    c = jnp.arange(n)[None, :]
    zero = jnp.float32(0)
    # factor: block row j of Lᵀ is upper triangular from column j*nb + r
    u = jnp.where(c >= j * nb + r, factor_row, zero)
    y2 = y2 + u.T @ (u @ x)
    # input: strictly-upper blocks of D are A₀'s strictly-lower blocks
    d_row = generate.spd_row(key, j, n, nb)
    tail = jnp.where(c >= (j + 1) * nb, d_row, zero)
    xj = lax.dynamic_slice(x, (j * nb, 0), (nb, PROBES))
    d = lax.dynamic_slice(d_row, (0, j * nb), (nb, nb))
    yj = 0.5 * (d + d.T) @ xj + tail @ x
    y = y + tail.T @ xj
    yj = yj + lax.dynamic_slice(y, (j * nb, 0), (nb, PROBES))
    return lax.dynamic_update_slice(y, yj, (j * nb, 0)), y2


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
