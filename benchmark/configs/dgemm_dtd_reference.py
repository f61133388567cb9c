"""Plain reference of ``dgemm_dtd``: C ← A·B + C₀ as one matmul.

The system under test computes the product tile by tile through the
dynamic runtime; the reference concatenates the same tiles and multiplies
once, under ``jax.default_matmul_precision("highest")``, one block row of
C at a time (a block row of the reference is ``nb`` × ``n``; the whole
would be a fourth matrix on the chip).
"""

from __future__ import annotations

import jax.numpy as jnp


def row_error(a_row, b_full, c0_row, c_row):
    """``(‖got − ref‖², ‖ref‖²)`` of one block row: ``a_row``, ``c0_row``
    and ``c_row`` are that row's tiles in column order, ``b_full`` is B
    whole."""
    ref = jnp.concatenate(a_row, axis=1) @ b_full + \
        jnp.concatenate(c0_row, axis=1)
    got = jnp.concatenate(c_row, axis=1)
    return jnp.sum((got - ref) ** 2), jnp.sum(ref ** 2)


def concat_tiles(tiles, mt: int, nt: int):
    """The ``mt`` × ``nt`` grid of tiles (row-major list) as one array."""
    return jnp.concatenate(
        [jnp.concatenate(tiles[i * nt:(i + 1) * nt], axis=1)
         for i in range(mt)], axis=0)
