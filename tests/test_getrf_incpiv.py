"""Tile LU by incremental pivoting (DPLASMA ``dgetrf_incpiv``): the four
kernels against the benchmark's plain reference on seeded matrices (U, the
applied solution and the invariants, not pivot indices bit for bit), the
region of a tile as a dependency carries it, and the whole taskpool
through the host scheduler on the CPU device and through ``TPUDevice`` on
the CPU backend."""

import os
import sys

import numpy as np
import pytest

import parsec_tpu as parsec
from parsec_tpu.algorithms.getrf import (build_getrf, build_getrf_incpiv,
                                         getrf_flops, getrf_ipiv_collection,
                                         getrf_l_collection)
from parsec_tpu.core.reshape import UPPER_TILE
from parsec_tpu.data import TiledMatrix
from parsec_tpu.dsl import ptg
from parsec_tpu.ops import tile_kernels
from parsec_tpu.ops.tile_kernels import (_lu_panel, _pair_swap, gessm_tile,
                                         getrf_incpiv_tile, ssssm_tile,
                                         tstrf_tile)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

NB = 16
IBS = (4, 8, 16)            # every ib that divides nb, among three
# (nb, ib) of a pair's kernels: the small ones through XLA's LU, ib = 128
# through the VMEM panel (interpreted here)
PAIRS = [(NB, ib) for ib in IBS] + [(128, 128), (256, 128)]


@pytest.fixture(scope="module")
def ref():
    """The benchmark's plain reference of the factored form."""
    from benchmark.manifest import Manifest
    return Manifest(ROOT).reference("dgetrf_incpiv_ptg_host_reference")


@pytest.fixture(autouse=True)
def highest():
    """The comparisons are of float32 arithmetic, not of bf16 passes."""
    import jax
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture
def make_ctx():
    """Contexts with one chip module; ``on``: the module the bodies run
    on, ``cpu`` (the inline CPU device) or ``tpu`` (``TPUDevice`` on the
    CPU backend: the chip's path)."""
    from parsec_tpu.utils import mca_param
    made = []
    mca_param.set("device.tpu.max_devices", 1)

    def make(on="tpu", nb_cores=2):
        ctx = parsec.init(nb_cores=nb_cores)
        ctx.start()
        made.append(ctx)
        for d in ctx.devices.devices:
            if d.name.startswith("tpu" if on == "cpu" else "cpu"):
                d.weight = 1e-6
        return ctx

    yield make
    for ctx in made:
        parsec.fini(ctx)
    mca_param.unset("device.tpu.max_devices")


def _seeded(shape, seed=7):
    return np.random.default_rng(seed).uniform(
        -0.5, 0.5, shape).astype(np.float32)


def _chip(ctx):
    return next(d for d in ctx.devices.devices if d.name.startswith("tpu"))


def _programs(dev, tc):
    """Every program the module built for the task class ``tc``."""
    return [p for chore in tc.incarnations
            for slot, programs in dev._table.get(id(chore), {}).items()
            if slot != "pinned" for p in programs.values()]


def _factor(ctx, a0, nb, ib):
    A = TiledMatrix.from_array(a0.copy(), nb, nb)
    L, P = getrf_l_collection(A, ib), getrf_ipiv_collection(A)
    tp = build_getrf_incpiv(A, L, P)
    ctx.add_taskpool(tp)
    ctx.wait()
    return tp, A, L, P


def _tiles(*collections):
    import jax.numpy as jnp
    return [lambda i, j, dc=dc: jnp.asarray(dc.data_of((i, j)))
            for dc in collections]


def _solve(ref, a, low, piv, b, nt):
    """x with A0 x = b, as dgetrs_incpiv: the stored transformation, then
    the solve with U."""
    import jax.numpy as jnp
    return np.asarray(ref.solve_u(a, ref.apply_l(
        a, low, piv, jnp.asarray(b, jnp.float32), nt), nt))


# -- the kernels ------

def test_getrf_and_gessm_against_their_equations():
    a, c = _seeded((NB, NB), 1), _seeded((NB, NB), 2)
    lu, perm = getrf_incpiv_tile(a)
    lu, perm = np.asarray(lu), np.asarray(perm)
    assert perm.shape == (1, NB) and perm.dtype == np.int32
    assert sorted(perm[0]) == list(range(NB))
    low = np.tril(lu, -1) + np.eye(NB, dtype=np.float32)
    np.testing.assert_allclose(low @ np.triu(lu), a[perm[0]], atol=2e-6)
    assert np.abs(np.tril(lu, -1)).max() <= 1.0
    got = np.asarray(gessm_tile(lu, perm, c))
    np.testing.assert_allclose(
        got, np.linalg.solve(low.astype(np.float64), c[perm[0]]), atol=2e-5)


@pytest.mark.parametrize("nb,ib", PAIRS)
def test_tstrf_and_ssssm_against_the_plain_reference(ref, nb, ib):
    """The pair's kernels against the reference's reading of what they
    store: the stored transformation takes the stack [U; A] to [U'; 0]
    and the pair [A1; A2] to what SSSSM returns; U' and the multipliers
    against the plain loops' own."""
    import jax.numpy as jnp
    u = np.triu(_seeded((nb, nb), 3))
    a, a1, a2 = (_seeded((nb, nb), s) for s in (4, 5, 6))
    u2, l21, low, piv, w = tstrf_tile(u, a, ib)
    assert low.shape == (ib, nb) and piv.shape == (1, nb)
    assert piv.dtype == jnp.int32
    assert bool(ref.interchanges_valid(piv, ib))
    assert float(ref.multipliers_pair(l21, low)) <= 1.0
    np.testing.assert_array_equal(np.tril(np.asarray(u2), -1), 0)
    # float32's rounding of what an elimination of nb columns carries
    atol = 5e-6 * nb / NB
    stack = jnp.asarray(np.concatenate([u, a], axis=0))
    out = np.asarray(ref.apply_pair(0, 1, l21, low, piv, stack,
                                    inverse=False))
    np.testing.assert_allclose(out[:nb], np.asarray(u2), atol=atol)
    np.testing.assert_allclose(out[nb:], 0, atol=atol)
    back = ref.apply_pair(0, 1, l21, low, piv, jnp.asarray(out),
                          inverse=True)
    np.testing.assert_allclose(np.asarray(back), stack, atol=atol)
    # the plain loops on the same stack: a 2 x 1 grid's column, by hand
    wide = np.concatenate([np.concatenate([u, a1], 1),
                           np.concatenate([a, a2], 1)], 0)
    pair = np.asarray(ref.apply_pair(
        0, 1, l21, low, piv, jnp.asarray(wide[:, nb:]), inverse=False))
    c1, c2 = ssssm_tile(a1, a2, w, l21, piv)
    np.testing.assert_allclose(np.asarray(c1), pair[:nb], atol=atol)
    np.testing.assert_allclose(np.asarray(c2), pair[nb:], atol=atol)


# -- a block's stack factored in VMEM (interpreted here) ------

def _stack(nb, seed, ib=128):
    """A TSTRF block's stack: [upper-triangular ib x ib; nb x ib]."""
    return np.concatenate([np.triu(_seeded((ib, ib), seed)),
                           _seeded((nb, ib), seed + 100)], axis=0)


def _xla_lu(stack):
    import jax
    lu, piv, _ = jax.lax.linalg.lu(stack)
    return np.asarray(lu), np.asarray(piv)


@pytest.mark.parametrize("seed", [21, 22, 23])
@pytest.mark.parametrize("nb,ib", [(128, 128), (256, 128), (4096, 128),
                                   (8064, 128), (4224, 384)])
def test_the_vmem_panel_against_xlas_lu(nb, ib, seed):
    """The same interchanges, no multiplier over 1, and factors as close
    to the float64 elimination with those interchanges as XLA's are (a
    different order of the same float32 operations: on the CPU, LAPACK's
    blocked one; 128 steps leave both some 30 ulp from it, 384 some
    80); 4224 and 8192 rows take the tall kernel, and 4608 x 384 too,
    factored at 10240 rows (its cap's 10922 cut to whole vregs of
    blocks)."""
    stack = _stack(nb, seed, ib)
    lu, piv = (np.asarray(x) for x in _lu_panel(stack))
    xla, xla_piv = _xla_lu(stack)
    assert piv.dtype == np.int32 and piv.shape == (ib,)
    np.testing.assert_array_equal(piv, xla_piv)
    assert np.abs(np.tril(lu, -1)).max() <= 1.0
    exact = stack.astype(np.float64)
    for j, p in enumerate(piv):
        exact[[j, p]] = exact[[p, j]]
        exact[j + 1:, j] /= exact[j, j]
        exact[j + 1:, j + 1:] -= np.outer(exact[j + 1:, j],
                                          exact[j, j + 1:])
    scale = np.maximum(np.abs(exact).max(axis=1, keepdims=True), 1.0)
    ulp = np.finfo(np.float32).eps
    off, xla_off = ((np.abs(x - exact) / scale).max() for x in (lu, xla))
    # the rounding grows with the steps: 64 ulp a 128 of them
    assert off <= max(2 * xla_off, 8 * ulp) and off <= ib // 2 * ulp


@pytest.mark.parametrize("nb", [128, 4096])
def test_the_vmem_panel_takes_row_j_where_nothing_under_it_is_larger(nb):
    """A column that is all zero under U's diagonal gives index j (no
    interchange): with a diagonal entry, and with a zero there too (a
    column of zeros keeps its zeros and makes no NaN); in both kernels
    (4224 rows: the tall one)."""
    stack = _stack(nb, 24)
    # nothing over U's diagonal either, so that no update fills the column
    stack[:5, 5] = stack[128:, 5] = 0.0       # U(5,5) alone
    stack[:10, 9] = stack[128:, 9] = 0.0      # nothing at all
    lu, piv = (np.asarray(x) for x in _lu_panel(stack))
    want, want_piv = _xla_lu(stack)
    assert piv[5] == 5
    assert np.isfinite(lu).all()
    # up to the column of zeros XLA's are the same factors
    np.testing.assert_array_equal(piv[:9], want_piv[:9])
    np.testing.assert_allclose(lu[:, :9], want[:, :9], atol=1e-5)
    assert piv[9] == 9 and not lu[10:, 9].any()


def test_the_vmem_panel_takes_the_lowest_index_among_equals():
    """Equal magnitudes, of either sign, in a column: the first of them
    in the stack as the interchanges before it left it, which is not the
    order the rows came in."""
    stack = _stack(128, 25)
    stack[:, 0] = 0.0
    stack[[130, 140, 150], 0] = [0.75, -0.75, 0.75]     # step 0: row 130
    lu, piv = (np.asarray(x) for x in _lu_panel(stack))
    want, want_piv = _xla_lu(stack)
    assert piv[0] == 130
    np.testing.assert_array_equal(piv, want_piv)
    # a tie at step 1 between row 0, which step 0 moved down to position
    # 130, and row 128: the lowest POSITION wins, not the lowest row
    stack = _stack(128, 26)
    stack[:, 0] = 0.0
    stack[130, 0] = 0.75
    stack[1:, 1] = 0.0
    stack[[0, 128], 1] = 0.5
    lu, piv = (np.asarray(x) for x in _lu_panel(stack))
    want, want_piv = _xla_lu(stack)
    assert list(piv[:2]) == [130, 128]
    np.testing.assert_array_equal(piv, want_piv)
    # the tall kernel (4224 rows), the same tie beyond position 4095:
    # row 0, moved to position 4100 by step 0, against row 4000 at
    # position 4000; position 4000 wins, where the lowest row would be
    # 0 and positions cut to 12 bits would compare 4 with 4000
    stack = _stack(4096, 27)
    stack[:, 0] = 0.0
    stack[4100, 0] = 0.75
    stack[:, 1] = 0.0
    stack[[0, 4000], 1] = [0.5, -0.5]
    assert tile_kernels._lu_panel_tall(stack.shape[0])
    lu, piv = (np.asarray(x) for x in _lu_panel(stack))
    want, want_piv = _xla_lu(stack)
    assert list(piv[:2]) == [4100, 4000]
    np.testing.assert_array_equal(piv, want_piv)
    assert lu[1, 1] == -0.5


def test_the_vmem_panel_vmapped_over_tall_stacks_takes_one_at_a_time():
    """A batch of tall stacks (``tstrf_tile``'s stacked form at tiles of
    4096) goes through the exported kernel's batching rule, as on the
    chip: each stack's factors and interchanges are XLA's, in order."""
    import jax
    stacks = np.stack([_stack(4096, 28), _stack(4096, 29)])
    before = tile_kernels._lu_panel_exported.cache_info()
    lu, piv = (np.asarray(x) for x in jax.vmap(_lu_panel)(stacks))
    after = tile_kernels._lu_panel_exported.cache_info()
    assert after.hits + after.misses > before.hits + before.misses
    assert lu.shape == stacks.shape and piv.shape == (2, 128)
    for i, stack in enumerate(stacks):
        want, want_piv = _xla_lu(stack)
        np.testing.assert_array_equal(piv[i], want_piv)
        np.testing.assert_allclose(lu[i], want, atol=1e-5)


def test_the_shape_says_which_factorization_a_block_is_traced_through(
        make_ctx):
    """ib = 16 (these tests', the dry runs'): XLA's LU; float32 with ib a
    multiple of 128: the VMEM panel, its tall kernel over 4096 rows, up
    to a 32768 x 128 stack's 16 MiB (the height it factors every tall
    stack at); a block a count, where the chip module's counters are."""
    import jax
    import jax.numpy as jnp
    traced = tile_kernels.LU_BLOCKS_TRACED

    def trace(nb, ib, dtype=jnp.float32):
        before = dict(traced)
        t = jax.ShapeDtypeStruct((nb, nb), dtype)
        jax.eval_shape(lambda u, a: tstrf_tile(u, a, ib), t, t)
        return {k: traced[k] - before[k] for k in traced}

    def counts(packed, tall, xla):
        return {"vmem_panel": packed, "vmem_panel_tall": tall,
                "xla_lu": xla}

    assert trace(64, 16) == counts(0, 0, 4)
    assert trace(256, 128) == counts(2, 0, 0)
    assert trace(256, 256) == counts(1, 0, 0)
    assert trace(128, 64) == counts(0, 0, 2)
    assert trace(4096, 128) == counts(0, 32, 0)      # 4224-row stacks
    takes = tile_kernels._lu_panel_takes
    assert takes(2176, 128, jnp.float32)
    assert not takes(2176, 128, jnp.bfloat16)
    assert takes(4096, 128, jnp.float32)
    assert not tile_kernels._lu_panel_tall(4096)
    assert takes(4096 + 128, 128, jnp.float32)
    assert tile_kernels._lu_panel_tall(4096 + 128)
    assert takes(32768, 128, jnp.float32)
    assert not takes(32768 + 128, 128, jnp.float32)
    assert not takes(4096, 2048, jnp.float32)
    # the packed kernel keeps PR 41's 8 MiB
    assert takes(2048, 1024, jnp.float32)
    assert not takes(4096, 1024, jnp.float32)
    # every tall stack is factored at the cap's height, a multiple of
    # 1024 rows (whole vregs of blocks) under 16 MiB
    assert tile_kernels._lu_panel_height(128) == 32768
    assert tile_kernels._lu_panel_height(256) == 16384
    assert tile_kernels._lu_panel_height(384) == 10240
    assert takes(10240, 384, jnp.float32)
    assert not takes(10240 + 128, 384, jnp.float32)
    assert tile_kernels._lu_panel_height(896) == 4096
    assert not takes(4096 + 128, 896, jnp.float32)
    ctx = make_ctx("tpu", nb_cores=1)
    stats = _chip(ctx).dump_statistics()
    assert stats["lu_blocks_vmem_panel"] == traced["vmem_panel"] >= 3
    assert stats["lu_blocks_vmem_panel_tall"] == \
        traced["vmem_panel_tall"] >= 32
    assert stats["lu_blocks_xla_lu"] == traced["xla_lu"] >= 6
    assert any("lu_blocks_vmem_panel_tall" in d
               for d in ctx.statusz()["devices"])


def test_a_vmapped_tstrf_traces_and_factors_each_pair():
    """The body's ``batch_hook`` is ``jax.vmap(tstrf)``: the panel under
    it, a stack a member."""
    import jax
    import jax.numpy as jnp
    nb = ib = 128
    us = jnp.asarray(np.stack([np.triu(_seeded((nb, nb), s))
                               for s in (31, 32)]))
    As = jnp.asarray(np.stack([_seeded((nb, nb), s) for s in (33, 34)]))

    def tstrf(u, a):
        return tstrf_tile(u, a, ib)

    assert "pallas_call" in str(jax.make_jaxpr(jax.vmap(tstrf))(us, As))
    got = jax.vmap(tstrf)(us, As)
    for i in range(2):
        for x, y in zip(got, tstrf(us[i], As[i])):
            np.testing.assert_allclose(np.asarray(x[i]), np.asarray(y),
                                       atol=1e-5)


def test_a_blocks_interchanges_as_moves_against_one_at_a_time():
    """A lower row that wins twice takes a U row down and brings it back:
    the moves give what the interchanges give one at a time."""
    import jax.numpy as jnp
    ib, nb = 4, 6
    top, bot = _seeded((ib, 5), 8), _seeded((nb, 5), 9)
    for piv in ([ib + 2, ib + 2, 2, ib + 0], [0, 1, 2, 3],
                [ib + 5, ib + 4, ib + 5, ib + 5], [ib, ib + 1, ib, ib + 1]):
        stack = np.concatenate([top, bot], axis=0)
        for j, p in enumerate(piv):
            stack[[j, p]] = stack[[p, j]]
        got_top, got_bot = _pair_swap(jnp.asarray(top), jnp.asarray(bot),
                                      jnp.asarray(piv, jnp.int32))
        np.testing.assert_array_equal(np.asarray(got_top), stack[:ib])
        np.testing.assert_array_equal(np.asarray(got_bot), stack[ib:])


# -- a region of a tile as a dependency carries it ------

def test_a_region_is_merged_where_the_tile_lies():
    import jax
    import jax.numpy as jnp
    tile, part = _seeded((8, 8), 1), _seeded((8, 8), 2)
    want = np.tril(tile, -1) + np.triu(part)
    host = tile.copy()
    assert UPPER_TILE.merge(host, part) is host
    np.testing.assert_array_equal(host, want)
    on_device = jnp.asarray(tile)
    merged = UPPER_TILE.merge(on_device, jnp.asarray(part))
    np.testing.assert_array_equal(np.asarray(merged), want)
    if jax.devices()[0].platform != "cpu" or on_device.is_deleted():
        assert on_device.is_deleted()       # its buffer was given
    A = TiledMatrix.from_array(tile.copy(), 8, 8)
    A.merge_tile((0, 0), part, UPPER_TILE)
    np.testing.assert_array_equal(A.data_of((0, 0)), want)
    # a region belongs to a write-back: a value between tasks is whole
    tp = ptg.Taskpool("t", A=A)
    with pytest.raises(ValueError, match="write-back"):
        tp.task_class(
            "X", params=("k",), space=lambda g: [(0,)],
            flows=[ptg.FlowSpec("T", ptg.RW, outs=[ptg.Out(
                dst=("X", lambda g, k: (k,), "T"), region=UPPER_TILE)])])


@pytest.mark.parametrize("nt", [1, 2, 5])
def test_both_sides_of_every_dependency_agree(nt):
    A = TiledMatrix(nt * NB, nt * NB, NB, NB)
    tp = build_getrf_incpiv(A, ib=4)
    ptg.check_taskpool(tp)
    counts = {tc.name: len(list(tc.enumerate_space()))
              for tc in tp.task_classes}
    pairs = nt * (nt - 1) // 2
    assert counts == {"GETRF": nt, "GESSM": pairs, "TSTRF": pairs,
                      "SSSSM": sum((nt - k - 1) ** 2 for k in range(nt))}
    assert ptg.taskpool_writes_regions(tp)
    assert not ptg.taskpool_writes_regions(build_getrf(A))
    # an executor scatters whole tiles, and says so
    from parsec_tpu.compiled.wavefront import plan_taskpool
    with pytest.raises(ValueError, match="region"):
        plan_taskpool(tp)


def test_the_two_counts_of_an_lus_operations_agree():
    from benchmark import ops_getrf
    for n in (512, 32768, 49152):
        assert getrf_flops(n) == ops_getrf.getrf_ops(n) == \
            2.0 * n ** 3 / 3.0 - n ** 2 / 2.0 - n / 6.0
    assert "no pivoting" in build_getrf.__doc__.lower().replace(
        "without pivoting", "no pivoting")
    assert "incremental pivoting" in build_getrf_incpiv.__doc__


# -- the whole taskpool on the host-scheduler path the cell uses ------

@pytest.mark.parametrize("on", ["cpu", "tpu"])
@pytest.mark.parametrize("ib", IBS)
@pytest.mark.parametrize("nt", [1, 2, 5])
def test_the_taskpool_against_the_plain_reference(make_ctx, ref, nt, ib, on):
    n = nt * NB
    a0 = _seeded((n, n), 100 * nt + ib)
    want_a, want_l, want_p = ref.factor_plain(a0, NB, ib)
    ctx = make_ctx(on)
    tp, A, L, P = _factor(ctx, a0, NB, ib)
    ran = {s["name"]: s["tasks"] for s in ctx.devices.dump_statistics()}
    chip = _chip(ctx).name
    assert ran[chip] == (sum(ran.values()) if on == "tpu" else 0)
    a, low, piv = _tiles(A, L, P)
    # U against the plain loops' U
    got_u = np.triu(A.to_array())
    want_u = np.triu(np.block([[want_a[i, j] for j in range(nt)]
                               for i in range(nt)]))
    np.testing.assert_allclose(got_u, want_u, rtol=2e-3, atol=2e-4)
    # the invariants: multipliers, valid pivots, unit lower L11
    for k in range(nt):
        assert bool(ref.permutation_valid(piv(k, k)))
        assert float(ref.multipliers_diagonal(a(k, k))) <= 1.0
        for m in range(k + 1, nt):
            assert bool(ref.interchanges_valid(piv(m, k), ib))
            assert float(ref.multipliers_pair(a(m, k), low(m, k))) <= 1.0
    # A0 = M U, and the solve dgetrs_incpiv goes on to
    x = np.random.default_rng(3).standard_normal((n, 8)).astype(np.float32)
    import jax.numpy as jnp
    mux = np.asarray(ref.apply_l_inverse(
        a, low, piv, jnp.asarray(got_u @ x), nt))
    assert np.linalg.norm(mux - a0 @ x) <= 1e-4 * np.linalg.norm(a0 @ x)
    got_x = _solve(ref, a, low, piv, a0 @ x, nt)
    plain = [lambda i, j, d=d: jnp.asarray(d[i, j])
             for d in (want_a, want_l, want_p)]
    want_x = _solve(ref, *plain, a0 @ x, nt)
    scale = np.linalg.cond(a0.astype(np.float64)) * 1e-6
    assert np.linalg.norm(got_x - x) <= scale * np.linalg.norm(x)
    assert np.linalg.norm(got_x - want_x) <= scale * np.linalg.norm(x)


def test_pivots_from_the_lower_tile_where_a_no_pivot_program_fails(
        make_ctx, ref):
    """A tiny diagonal block: every pivot of the first block column has
    to come from a tile under the diagonal. Solved to the reference's
    accuracy; ``build_getrf`` (no pivoting) on the same matrix is not."""
    nt, ib = 3, 8
    n = nt * NB
    a0 = _seeded((n, n), 11)
    a0[:NB, :NB] *= 1e-6
    x = np.random.default_rng(4).standard_normal((n, 8)).astype(np.float32)
    ctx = make_ctx("tpu")
    _tp, A, L, P = _factor(ctx, a0, NB, ib)
    a, low, piv = _tiles(A, L, P)
    # the first column's interchanges all reach into the lower tiles
    first = np.asarray(P.data_of((1, 0)))[0]
    assert (first >= ib).all()
    got = _solve(ref, a, low, piv, a0 @ x, nt)
    import jax.numpy as jnp
    plain = [lambda i, j, d=d: jnp.asarray(d[i, j])
             for d in ref.factor_plain(a0, NB, ib)]
    want = _solve(ref, *plain, a0 @ x, nt)
    err_ref = np.linalg.norm(want - x) / np.linalg.norm(x)
    err = np.linalg.norm(got - x) / np.linalg.norm(x)
    assert err_ref < 1e-2 and err <= max(4 * err_ref, 1e-4)
    # the no-pivot program on the same matrix
    B = TiledMatrix.from_array(a0.copy(), NB, NB)
    ctx.add_taskpool(build_getrf(B))
    ctx.wait()
    lu = B.to_array().astype(np.float64)
    with np.errstate(all="ignore"):
        y = np.linalg.solve(np.tril(lu, -1) + np.eye(n), a0 @ x)
        nopiv = np.linalg.solve(np.triu(lu), y)
        err_nopiv = np.linalg.norm(nopiv - x) / np.linalg.norm(x)
    assert not err_nopiv <= 100 * err       # NaN, or off by orders


def test_held_is_0_for_an_ssssm_and_a_tstrf_holds_only_the_w_it_makes(
        make_ctx):
    """The storage guarantee's count, read off the programs the module
    built: an SSSSM and a GESSM write where their tiles lie and hold
    nothing new, and so does a TSTRF for U, the multipliers, L and IPIV;
    what it holds is W alone (ib x nb, the blocks' L11^-1 it hands its
    SSSSMs: a value made beside the tiles, by design). A GETRF holds U,
    the other such value, and nothing else."""
    nt, ib = 5, 8
    ctx = make_ctx("tpu", nb_cores=1)
    dev = _chip(ctx)
    tp, A, _L, _P = _factor(ctx, _seeded((nt * NB, nt * NB), 12), NB, ib)
    by_class = {tc.name: _programs(dev, tc) for tc in tp.task_classes}
    assert all(by_class.values())
    for name in ("SSSSM", "GESSM"):
        assert {p.held for p in by_class[name]} == {0}, name
    assert {p.held for p in by_class["TSTRF"]} == {ib * NB * 4}
    assert {p.held for p in by_class["GETRF"]} == {NB * NB * 4}
    stats = dev.dump_statistics()
    launches = stats["tasks"] - stats["batched_tasks"] + stats["batches"]
    assert stats["lone_in_place"] + stats["groups_in_place"] == \
        launches - nt - nt * (nt - 1) // 2      # all but GETRFs and TSTRFs
    # U merged into A(k, k) once a column that has a chain, in place
    assert stats["region_merges"] == nt - 1


def test_an_int32_tile_is_staged_once_and_shared_by_a_group(make_ctx):
    """A row of GESSMs shares GETRF's permutation, a row of SSSSMs
    TSTRF's interchanges: one int32 operand a launch, counted once."""
    nt, ib = 6, 8
    ctx = make_ctx("tpu", nb_cores=1)
    ctx.set_stage_timers(True)
    dev = _chip(ctx)
    tp, _A, _L, P = _factor(ctx, _seeded((nt * NB, nt * NB), 13), NB, ib)
    stats = dev.dump_statistics()
    tasks = stats["tasks"]
    assert tasks == sum(len(list(tc.enumerate_space()))
                        for tc in tp.task_classes)
    assert stats["batches"] > 0
    by_class = stats["launches_by_class"]
    assert by_class["SSSSM"] < stats["tasks_by_class"]["SSSSM"]
    assert by_class["GESSM"] < stats["tasks_by_class"]["GESSM"]
    # every task of every class reads or writes one int32 tile; a group's
    # members share theirs
    want = tasks - (stats["batched_tasks"] - stats["batches"])
    assert stats["int_tiles_staged"] == want
    assert stats["int_bytes_staged"] == want * NB * 4
    import jax
    assert all(isinstance(P.data_of((m, k)), jax.Array) and
               P.data_of((m, k)).dtype == np.int32
               for k in range(nt) for m in range(k, nt))
    assert any("int_tiles_staged" in d for d in ctx.statusz()["devices"])
