"""The yardstick's own arithmetic: operation and byte counts, the
percentile rule, the generator and the plain references."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import generate, ops, stats  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402

MAN = Manifest(ROOT)


# -- operation and byte counts ------------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 7, 64])
def test_potrf_ops_is_the_count_of_the_unblocked_algorithm(n):
    """Column j of a Cholesky: one square root, n-1-j divisions, and a
    multiply and an add per entry of the trailing update."""
    # the trailing update of column j touches the lower triangle of an
    # (n-1-j) square: (n-1-j)(n-j)/2 entries, a multiply and an add each
    count = sum(1 + (n - 1 - j) + (n - 1 - j) * (n - j) for j in range(n))
    assert ops.potrf_ops(n) == pytest.approx(count)


def test_potrf_ops_at_the_cells_sizes():
    assert ops.potrf_ops(40960) == pytest.approx(40960 ** 3 / 3, rel=1e-4)
    assert ops.potrf_ops(65536) == pytest.approx(9.38e13, rel=1e-3)


def test_gemm_counts():
    assert ops.gemm_ops(32768, 32768, 4096) == 2 * 32768 ** 2 * 4096
    assert ops.tiled_gemm_tasks(32768, 32768, 4096, 1024) == 4096
    assert ops.tiled_gemm_tasks(32768, 32768, 4096, 4096) == 64
    # a task reads A, B, C tiles and writes C
    assert ops.tiled_gemm_min_bytes(64, 64, 64, 32, 4) == 8 * 4 * 32 * 32 * 4
    assert ops.potrf_min_bytes(4, 4) == 2 * 4 * 10


@pytest.mark.parametrize("nb,bound", [(1024, "memory"), (4096, "compute")])
def test_roofline_says_which_peak_binds_the_tile_gemm(nb, bound):
    """On a v5e a 1024-tile f32 GEMM moves 16.8 MB for 2.1 GFLOP and is
    bound by memory; a 4096-tile is bound by the MXU."""
    v5e = MAN.peaks("TPU v5 lite")
    seconds, which = ops.roofline_seconds(
        ops.gemm_ops(nb, nb, nb), ops.tiled_gemm_min_bytes(nb, nb, nb, nb, 4),
        v5e["bf16_flops_per_s"], v5e["hbm_bytes_per_s"])
    assert which == bound
    assert seconds == pytest.approx(
        max(2 * nb ** 3 / 197e12, 16 * nb * nb / 819e9))


# -- the percentile rule ------------------------------------------------------

@pytest.mark.parametrize("n,want", [
    (5, None), (39, None), (40, 75.0), (99, 75.0), (100, 90.0),
    (199, 90.0), (200, 95.0), (1000, 99.0), (10000, 99.9)])
def test_highest_percentile_keeps_ten_samples_beyond_it(n, want):
    assert stats.highest_percentile(n) == want
    if want is not None:
        assert n * (1000 - round(10 * want)) >= 1000 * stats.BEYOND


@pytest.mark.parametrize("p", [0.0, 25.0, 50.0, 75.0, 90.0, 99.9, 100.0])
def test_percentile_agrees_with_numpy(p):
    xs = list(np.random.default_rng(0).exponential(size=137))
    assert stats.percentile(xs, p) == pytest.approx(np.percentile(xs, p))


def test_median_and_tail_of_a_run():
    xs = [0.2] * 45 + [0.9] * 5
    assert stats.median(xs) == 0.2
    assert stats.tail(xs) == (75.0, 0.2)
    assert stats.tail(xs[:10]) is None
    with pytest.raises(ValueError):
        stats.median([])


# -- the generator ------------------------------------------------------------

def test_same_seed_same_inputs_and_rows_can_be_made_alone():
    import jax
    key = generate.step_key(7, 3)
    a = np.asarray(generate.spd_matrix(key, 96, 32))
    assert np.array_equal(a, np.asarray(
        generate.spd_matrix(generate.step_key(7, 3), 96, 32)))
    for j in range(3):
        assert np.array_equal(a[32 * j:32 * (j + 1)], np.asarray(
            jax.jit(lambda j: generate.spd_row(key, j, 96, 32))(j)))
    assert not np.array_equal(a, np.asarray(
        generate.spd_matrix(generate.step_key(7, 4), 96, 32)))
    assert not np.array_equal(a, np.asarray(
        generate.spd_matrix(generate.step_key(8, 3), 96, 32)))


def test_spd_input_is_strictly_diagonally_dominant():
    a = np.asarray(generate.spd_matrix(generate.step_key(0, 0), 128, 32),
                   np.float64)
    off = np.abs(a).sum(axis=1) - np.abs(np.diag(a))
    assert (np.diag(a) > off).all()
    assert np.abs(a - np.diag(np.diag(a))).max() <= 0.5


def test_tiles_are_keyed_by_index():
    key = generate.step_key(1, 0)
    t0, t1 = (np.asarray(generate.tile(key, i, 16)) for i in (0, 1))
    assert t0.shape == (16, 16) and t0.dtype == np.float32
    assert not np.array_equal(t0, t1)
    assert np.array_equal(t0, np.asarray(generate.tile(key, 0, 16)))
    assert np.abs(t0).max() <= 0.5


# -- the plain references -----------------------------------------------------

def _probe(ref, factor, key, n, nb):
    import jax
    import jax.numpy as jnp
    x = ref.probe_vectors(key, n)
    y, y2 = jnp.zeros_like(x), jnp.zeros_like(x)
    with jax.default_matmul_precision("highest"):
        for j in range(n // nb):
            y, y2 = ref.probe_row(j, factor[j * nb:(j + 1) * nb], key, x,
                                  y, y2, n=n, nb=nb)
    return ref.residual(y, y2)


def test_potrf_probe_accepts_numpys_factor_and_refuses_a_wrong_one():
    ref = MAN.reference("dpotrf_panel_reference")
    n, nb = 128, 32
    key = generate.step_key(5, 1)
    a0 = ref.dense_a0(key, n, nb)
    assert np.allclose(a0, a0.T)
    lt = np.linalg.cholesky(a0).T.astype(np.float32)
    assert _probe(ref, lt, key, n, nb) < 1e-6
    # what sits below the diagonal is ignored
    junk = lt + np.tril(np.full((n, n), 7.0, np.float32), -1)
    assert _probe(ref, junk, key, n, nb) < 1e-6
    # a factor rounded to bfloat16 is a different result, not a fast one
    import jax.numpy as jnp
    rounded = np.asarray(jnp.asarray(lt).astype(jnp.bfloat16)
                         .astype(jnp.float32))
    limit = MAN.config("dpotrf_panel")["correct"]["limit"]
    assert _probe(ref, rounded, key, n, nb) > limit
    assert _probe(ref, lt, generate.step_key(5, 2), n, nb) > limit


def test_gemm_reference_row_error():
    ref = MAN.reference("dgemm_dtd_reference")
    rng = np.random.default_rng(1)
    a = [rng.standard_normal((8, 8)).astype(np.float32) for _ in range(2)]
    b = [rng.standard_normal((8, 8)).astype(np.float32) for _ in range(6)]
    c0 = [rng.standard_normal((8, 8)).astype(np.float32) for _ in range(3)]
    b_full = np.asarray(ref.concat_tiles(b, 2, 3))
    assert b_full.shape == (16, 24)
    assert np.array_equal(b_full[8:, 16:], b[5])
    want = np.concatenate(a, 1) @ b_full + np.concatenate(c0, 1)
    got = [want[:, 8 * i:8 * (i + 1)] for i in range(3)]
    err, norm = ref.row_error(a, b_full, c0, got)
    assert float(err) < 1e-8 * float(norm)
    got[1] = got[1] + 1.0
    err, norm = ref.row_error(a, b_full, c0, got)
    assert float(err) == pytest.approx(64.0, rel=1e-3)
