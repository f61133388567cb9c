"""The panel program partitioned by the runtime (compiled/panels.py
``PanelExecutor.partitioned``, ``compile_with_plan``): left-looking POTRF
over a one-axis mesh as per-chip matmuls and, a column chunk, one send
from every chip that holds factored rows to the row panel's owner, under
``shard_map``, on the conftest's 8 forced CPU devices. The factor
against ``numpy.linalg.cholesky`` and the one-chip program, what the
compiled program holds of collectives, which branch a call takes and
says it took, and under which store key each lives."""

import hashlib
import os
import re
import sys

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import parsec_tpu.compiled.panels as panels
from parsec_tpu.algorithms.geqrf import build_geqrf_hh
from parsec_tpu.algorithms.potrf import (_potrf_left_wave_fuser,
                                         build_potrf_left)
from parsec_tpu.compiled.panels import PanelExecutor
from parsec_tpu.compiled.spmd import compile_with_plan
from parsec_tpu.compiled.wavefront import plan_taskpool
from parsec_tpu.data.matrix import TiledMatrix
from parsec_tpu.utils import compile_cache as cc
from parsec_tpu.utils import mca_param

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import generate  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402

REF = Manifest(ROOT).reference("dpotrf_panel_reference")
NB = 32
KEY = generate.step_key(7, 1)


@pytest.fixture(autouse=True)
def small_chunks(monkeypatch):
    """A last send of one tile's partial sums, and three tiles to a run
    nobody sends, so that these sizes go in several chunks a step as the
    real ones do."""
    monkeypatch.setattr(panels, "SEND_TAIL_BYTES", NB * NB * 4)
    monkeypatch.setattr(panels, "PANEL_CHUNK_BYTES", 3 * NB * NB * 4)


@pytest.fixture
def hook(request):
    mca_param.set("potrf.trsm_hook", request.param)
    yield request.param
    mca_param.unset("potrf.trsm_hook")


def _mesh(chips, axis="rows"):
    return Mesh(np.asarray(jax.devices()[:chips]), (axis,))


def _left(n, nb=NB):
    return PanelExecutor(plan_taskpool(build_potrf_left(
        TiledMatrix(n, n, nb, nb, name="A"))))


def _compile(ex, mesh, spec_in=None, spec_out=None, fn_key="partition"):
    axis = mesh.axis_names[0]
    sh_in = NamedSharding(mesh, spec_in if spec_in is not None else P(axis))
    sh_out = NamedSharding(mesh,
                           spec_out if spec_out is not None else P(axis))
    fn = compile_with_plan(
        ex.run_state, mesh=mesh,
        in_shardings=({name: sh_in for name in ex.geoms},),
        out_shardings={name: sh_out for name in ex.geoms},
        donate_argnums=0, example_args=(ex.state_shapes(),),
        fn_key=(fn_key, ex.monolith_cache_key()))
    return fn, sh_in


def _factor(ex, mesh, n, **spec):
    fn, sh = _compile(ex, mesh, **spec)
    out = fn({"A": jax.device_put(generate.spd_matrix(KEY, n, NB), sh)})
    return fn, np.triu(np.asarray(out["A"], np.float64)).T


def _collectives(fn):
    """Collective instructions of a compiled program by kind (a
    ``-start``/``-done`` pair is one)."""
    ops = re.findall(r"= \S+ (all-reduce|all-gather|all-to-all|"
                     r"collective-permute|reduce-scatter|"
                     r"collective-broadcast)(?:-start)?\(", fn.as_text())
    return {kind: ops.count(kind) for kind in set(ops)}


@pytest.mark.parametrize("hook", ["gemm", "solve"], indirect=True)
@pytest.mark.parametrize("panels_a_chip", [1, 2, 4])
@pytest.mark.parametrize("chips", [2, 4, 8])
def test_partitioned_factor_is_numpy_cholesky(chips, panels_a_chip, hook):
    n = NB * chips * panels_a_chip
    ex = _left(n)
    _fn, got = _factor(ex, _mesh(chips), n)
    rep = ex.partition_report()
    assert rep["branch"] == "runtime" and rep["shards"] == chips
    want = np.linalg.cholesky(REF.dense_a0(KEY, n, NB))
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


@pytest.mark.parametrize("hook", ["gemm", "solve"], indirect=True)
@pytest.mark.parametrize("chips,panels_a_chip", [(2, 1), (4, 2), (8, 2)])
def test_partitioned_factor_is_the_one_chip_factor(chips, panels_a_chip,
                                                   hook):
    n = NB * chips * panels_a_chip
    ex = _left(n)
    _fn, got = _factor(ex, _mesh(chips), n)
    one = ex.jitted({"A": generate.spd_matrix(KEY, n, NB)})
    one = np.triu(np.asarray(one["A"], np.float64)).T
    assert np.abs(got - one).max() <= 1e-5 * np.abs(one).max()


@pytest.mark.parametrize("hook", ["gemm"], indirect=True)
def test_four_chip_program_sends_to_the_owner_and_moves_nothing_else(hook):
    """No all-reduce, no all-gather, no all-to-all: collective-permutes
    alone, each of one pair (a chip before the owner, the owner), as many
    as the lowering says it wrote: a sender's row panel in halving runs
    down to one tile. The first chip's own row panels take nobody's
    products; the last chip's take everybody else's."""
    n, chips = NB * 16, 4
    ex = _left(n)
    fn, _got = _factor(ex, _mesh(chips), n)
    rep = ex.partition_report()
    nt = n // NB
    held = nt // chips
    # with a sender: runs of 2^i tiles from the last back take in the panel
    chunks = {k: (nt - k).bit_length() for k in range(held, nt)}
    found = _collectives(fn)
    assert set(found) == {"collective-permute"}, found
    assert found["collective-permute"] == rep["sends_per_step"] == \
        rep["collectives_per_step"] == \
        sum(k // held * c for k, c in chunks.items())
    text = fn.as_text()
    pairs = re.findall(r"source_target_pairs=\{(.*?)\}\}?[,\s]", text)
    for owner in range(1, chips):
        of_owner = sum(c for k, c in chunks.items() if k // held == owner)
        for sender in range(owner):
            assert pairs.count(f"{{{sender},{owner}") == of_owner, (
                sender, owner, pairs)
    assert len(pairs) == found["collective-permute"]     # and no other pair
    # chip 0 hands every such step its nb × (n − k·nb) f32 partial
    assert rep["sent_bytes_per_step_busiest_chip"] == \
        rep["reduced_bytes_per_step_and_chip"] == \
        sum(NB * (n - k * NB) * 4 for k in chunks)
    # the widest send: the first run of the widest sent panel, [6, 3, 2, 1]
    assert rep["send_chunk_bytes"] == 6 * NB * NB * 4
    # all of a sender's sends in a row panel but its last
    assert rep["sends_with_a_product_behind_them_share"] == pytest.approx(
        sum(k // held * (c - 1) for k, c in chunks.items())
        / rep["sends_per_step"])
    assert "parsec:panel_reduce" in text


@pytest.mark.parametrize("tiles,owner,want", [
    (48, 1, [27, 14, 7]), (33, 1, [19, 9, 5]), (21, 2, [14, 7]),
    (8, 3, [5, 3]), (7, 3, [7]), (1, 3, [1]),
    (64, 0, [21, 22, 21]), (49, 0, [17, 16, 16]),
    (24, 0, [24]), (1, 0, [1])])
def test_a_sent_panel_goes_in_halving_runs_down_to_the_tail(tiles, owner,
                                                            want):
    """At the cell's tile (4 MiB of partial sums) and the cell's
    constants: the last run of a sent panel, which its sender cannot
    hide, within 28 MiB; a panel nobody sends in equal runs within 96."""
    part = panels.PanelPartition("rows", 4)
    part.tail_bytes, part.chunk_bytes = 28 << 20, 96 << 20
    runs = part.chunks(5, 5 + tiles, 1024 * 1024 * 4, owner)
    assert [hi - lo for lo, hi in runs] == want
    assert runs[0][0] == 5 and runs[-1][1] == 5 + tiles
    assert all(a[1] == b[0] for a, b in zip(runs, runs[1:]))


@pytest.mark.parametrize("shards,owner,want", [
    (4, 0, []), (4, 1, [0]), (4, 2, [0, 1]), (4, 3, [0, 1, 2]),
    (2, 1, [0]), (8, 2, [0, 1]), (6, 2, [0, 1]), (6, 3, [0, 1, 2])])
def test_an_owner_is_sent_to_by_the_chips_that_hold_factored_rows(
        shards, owner, want):
    part = panels.PanelPartition("rows", shards)
    assert list(part.senders(owner)) == want
    for i, sender in enumerate(part.senders(owner)):
        part.count_send(sender, 8, last=bool(i))
    assert part.sends == len(want) and part.sent_bytes == \
        [8] * len(want) + [0] * (shards - len(want))
    assert part.hideable_sends == min(len(want), 1)


@pytest.mark.parametrize("hook", ["gemm", "solve"], indirect=True)
def test_the_owner_sums_in_one_order(hook):
    """Two runs of the 4-chip program on one input give the same bits:
    the owner takes its own product and then each sender's off the row
    panel in the order of the chips, whichever arrives first (an
    all-reduce promises no order)."""
    n, chips = NB * 8, 4
    ex = _left(n)
    fn, sh = _compile(ex, _mesh(chips))
    a0 = np.asarray(generate.spd_matrix(KEY, n, NB))
    runs = [np.asarray(fn({"A": jax.device_put(a0, sh)})["A"])
            for _ in range(2)]
    assert runs[0].tobytes() == runs[1].tobytes()
    assert np.isfinite(runs[0]).all()


def test_busiest_chip_share_is_what_contiguous_rows_give():
    """Chip 0 holds row panels 0..nt/4−1, which every later step
    contracts: (64³ − 48³) / 64³ = 58% of the update's operations at
    nt = 64 (the four-chip cell), with the owners' chains on top."""
    ex = PanelExecutor(plan_taskpool(build_potrf_left(
        TiledMatrix(64 * 8, 64 * 8, 8, 8, name="A"))))
    mesh = _mesh(4)
    sh = {"A": NamedSharding(mesh, P("rows"))}
    assert ex.partition_report()["branch"] is None      # nobody asked yet
    assert ex.partitioned(mesh, (sh,), sh) is not None
    share = ex.partition_report()["busiest_chip_ops_share"]
    assert (64 ** 3 - 48 ** 3) / 64 ** 3 - 0.03 < share < 0.60


@pytest.mark.parametrize("hook", ["gemm"], indirect=True)
def test_a_taskpool_without_a_mesh_lowering_goes_through_gspmd(hook):
    """GEQRF registers a wave_fuser and no mesh_wave_fuser: the old
    branch, said and not silent, and still a QR factorization."""
    n, nb = 128, 32
    rng = np.random.default_rng(5)
    a = rng.standard_normal((n, n)).astype(np.float32)
    A = TiledMatrix.from_array(a.copy(), nb, nb, name="A")
    ex = PanelExecutor(plan_taskpool(build_geqrf_hh(A)))
    assert ex.partition_report() == {
        "branch": "gspmd", "reason": "taskpool registers no mesh_wave_fuser"}
    mesh = _mesh(4)
    fn, sh = _compile(ex, mesh, fn_key="partition-geqrf")
    assert ex.partition_report()["branch"] == "gspmd"
    state = {name: jax.device_put(v, sh)
             for name, v in ex.make_state().items()}
    ex.write_back(fn(state))
    r = np.triu(A.to_array().astype(np.float64))
    assert np.allclose(r.T @ r, a.astype(np.float64).T @ a, atol=2e-3)


@pytest.mark.parametrize("hook", ["gemm"], indirect=True)
@pytest.mark.parametrize("case,chips,n,spec,why", [
    ("size_does_not_divide_nt", 4, NB * 6, {}, "do not divide nt"),
    ("specs_differ", 2, NB * 4, {"spec_out": P()}, "in/out shardings"),
    ("columns_split", 2, NB * 4, {"spec_in": P(None, "rows"),
                                  "spec_out": P(None, "rows")},
     "in/out shardings")])
def test_other_splits_take_the_old_branch_and_say_so(case, chips, n, spec,
                                                     why, hook):
    ex = _left(n)
    mesh = _mesh(chips)
    if case == "size_does_not_divide_nt":
        # GSPMD cannot split 6 row panels' rows evenly by 4 either: the
        # decision is all there is to see
        sh = {"A": NamedSharding(mesh, P("rows"))}
        assert ex.partitioned(mesh, (sh,), sh) is None
    else:
        _fn, got = _factor(ex, mesh, n, **spec)
        want = np.linalg.cholesky(REF.dense_a0(KEY, n, NB))
        assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    rep = ex.partition_report()
    assert rep["branch"] == "gspmd" and why in rep["reason"], rep


@pytest.mark.parametrize("hook", ["gemm"], indirect=True)
def test_a_two_axis_mesh_takes_the_old_branch(hook):
    ex = _left(NB * 4)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("rows", "x"))
    sh = {"A": NamedSharding(mesh, P("rows"))}
    assert ex.partitioned(mesh, (sh,), sh) is None
    assert ex.partition_report() == {"branch": "gspmd",
                                     "reason": "mesh has 2 axes"}
    # the report is of the last call, not of the executor
    sh = {"A": NamedSharding(_mesh(4), P("rows"))}
    assert ex.partitioned(_mesh(4), (sh,), sh) is not None
    assert ex.partition_report()["branch"] == "runtime"


# ---------------------------------------------------------------------------
# compiled for the chip (described, not attached): what the program holds
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def v5e_2x2():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _chip_program(topo, n, nb, tail_bytes, monkeypatch):
    """The TPU compiler's four-chip program of the cell's configuration
    at ``n`` (nothing runs: the chips are described), and the lowering's
    report of it."""
    monkeypatch.setattr(panels, "PANEL_CHUNK_BYTES", 96 << 20)
    monkeypatch.setattr(panels, "SEND_TAIL_BYTES", tail_bytes)
    mca_param.set("potrf.trsm_hook", "gemm")
    try:
        ex = _left(n, nb)
        mesh = Mesh(np.asarray(topo.devices), ("rows",))
        sh = {"A": NamedSharding(mesh, P("rows"))}
        fn, _key = ex.partitioned(mesh, (sh,), sh)
        compiled = jax.jit(
            fn, in_shardings=(sh,), out_shardings=sh, donate_argnums=0
        ).lower({"A": jax.ShapeDtypeStruct((n, n), np.float32,
                                           sharding=sh["A"])}).compile()
    finally:
        mca_param.unset("potrf.trsm_hook")
    return compiled, ex.partition_report()


def _schedule(text):
    """The entry computation of a scheduled program, in its order, as
    ``(what, name)``: a send's ``start`` and ``done`` (named by their
    start) and a ``product`` (the switch on a chip's role around a
    step's matmuls: the one conditional of three branches)."""
    entry = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", text, re.S | re.M)
    out = []
    for line in entry.group(1).splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (?:\(.*?\)|\S+) ([\w-]+)\(",
                     line)
        if not m:
            continue
        name, op = m.groups()
        if op == "collective-permute-start":
            out.append(("start", name))
        elif op == "collective-permute-done":
            out.append(("done", re.search(
                r"collective-permute-done\(%?([^)\s]+)\)", line).group(1)))
        elif op == "conditional" and len(re.search(
                r"branch_computations=\{([^}]*)\}", line).group(1)
                .split(",")) == 3:
            out.append(("product", name))
    return out


# the parent's text at N = 65536 and what the benchmark's 1% on
# peak_hbm_gib (4.3424 GiB there) lets a program add to it
CELL_TEXT_BYTES = 349.08 * 2 ** 20 + 0.01 * 4.3424 * 2 ** 30


@pytest.mark.parametrize("n,tail_tiles,hideable,text_bytes", [
    (8192, 7, 0, 5e6 * 8), (8192, 1, 13, None),
    pytest.param(65536, 7, 113, CELL_TEXT_BYTES, marks=pytest.mark.slow)])
def test_on_the_chip_the_shard_stays_put_and_a_send_runs_under_a_product(
        v5e_2x2, monkeypatch, n, tail_tiles, hideable, text_bytes):
    """The TPU compiler's program at the cell's tile size: nt = 8 with
    the cell's constants (a last send within 7 tiles: a chunk a step
    there) and with a last send of one tile (several chunks a step, as
    the cell has); and, marked slow (90 s), the cell's own. The owner's
    writes happen in place — no copy of a chip's shard, which a tile of
    Lᵀ written inside the owner's loop once cost twice, and a product
    that reads the state after one of its step's writes would cost
    again. Nothing but sends crosses chips. The schedule puts a product
    between the start and the done of every send the report calls
    hideable. The temporaries are a step's partial sums, once on a
    sender and once a sender on the owner, and the diagonal tile's
    workspace; the text, which a chip keeps in HBM beside the shard,
    stays under 5 MB a step at nt = 8 (the unrolled tile kernels alone
    are 8.3 MB) and within the benchmark's bound at the cell's size."""
    nb, chips = 1024, 4
    compiled, rep = _chip_program(v5e_2x2, n, nb, tail_tiles * nb * nb * 4,
                                  monkeypatch)
    text = compiled.as_text()
    assert not re.findall(rf"= f32\[{n // chips},{n}\]\S* copy\(", text)
    assert set(re.findall(r"= \S+ (all-\w+|collective-\w+?|reduce-scatter)"
                          r"(?:-start|-done)?\(", text)) == \
        {"collective-permute"}
    order = _schedule(text)
    at = {item: i for i, item in enumerate(order)}
    sends = [name for what, name in order if what == "start"]
    assert len(sends) == rep["sends_per_step"]
    assert hideable == round(rep["sends_with_a_product_behind_them_share"]
                             * rep["sends_per_step"])
    assert hideable <= sum(
        any(what == "product"
            for what, _ in order[at["start", s] + 1:at["done", s]])
        for s in sends)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == mem.argument_size_in_bytes
    assert mem.temp_size_in_bytes < chips * nb * n * 4 + 8 * nb * nb * 4
    if text_bytes:
        assert mem.generated_code_size_in_bytes < text_bytes


@pytest.mark.parametrize("rows,w", [(2176, 128), (2560, 512),
                                    (32768, 128)])
def test_on_the_chip_a_tstrf_blocks_stack_is_factored_by_one_mosaic_call(
        v5e_2x2, monkeypatch, rows, w):
    """``ops/tile_kernels.py _lu_panel`` at the LU cell's shape (a 2048
    tile under a block of IB = 128), at the widest block of
    ``chip_smoke.py``'s IB sweep and at a 16-tile panel's stack (the tall
    kernel), compiled by the TPU compiler (this file holds the described
    chip, so the kernel's compile is kept here): Mosaic takes the
    transposes, the reductions across lanes, the lane gather and the
    dynamic and strided slices as written, the stack, its output and the
    transposed copy fit the VMEM the call asks for, and the program is
    that one call: no ``LuDecompositionBlock``, no HBM temporary."""
    from jax.sharding import SingleDeviceSharding
    from parsec_tpu.ops import tile_kernels
    from parsec_tpu.utils import jax_platform
    # the run asked for the CPU platform, where the kernel is interpreted
    monkeypatch.setattr(jax_platform, "cpu_requested", lambda: False)
    assert tile_kernels._lu_panel_takes(rows, w, np.float32)
    compiled = jax.jit(tile_kernels._lu_panel).lower(jax.ShapeDtypeStruct(
        (rows, w), np.float32,
        sharding=SingleDeviceSharding(v5e_2x2.devices[0]))).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "LuDecomposition" not in text
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_on_the_chip_a_tall_stack_is_factored_at_the_caps_height(
        v5e_2x2, monkeypatch):
    """A tall stack under the cap is factored at 32768 rows with zero
    rows under it, by a kernel exported once a process: two stacks of
    different heights lower the kernel once between them, and each
    program is one Mosaic call and no ``LuDecomposition``."""
    from jax.sharding import SingleDeviceSharding
    from parsec_tpu.ops import tile_kernels
    from parsec_tpu.utils import jax_platform
    monkeypatch.setattr(jax_platform, "cpu_requested", lambda: False)
    one = SingleDeviceSharding(v5e_2x2.devices[0])
    exported = tile_kernels._lu_panel_exported
    before = exported.cache_info()
    for rows in (6144, 12288):
        text = jax.jit(tile_kernels._lu_panel).lower(jax.ShapeDtypeStruct(
            (rows, 128), np.float32, sharding=one)).compile().as_text()
        assert text.count("tpu_custom_call") == 1
        assert "LuDecomposition" not in text
    after = exported.cache_info()
    assert after.hits >= before.hits + 1
    assert after.misses <= before.misses + 1
    # TSTRF's stacked form vmaps the kernel: a stack at a time
    text = jax.jit(jax.vmap(tile_kernels._lu_panel)).lower(
        jax.ShapeDtypeStruct((2, 6144, 128), np.float32,
                             sharding=one)).compile().as_text()
    assert text.count("tpu_custom_call") == 2


# sha256 of the text ``jax.jit(_lu_panel)`` is lowered to at 2176 x 128
# for a described v5e, source locations left out, as the parent of PR 45
# (291a4c3, PR 41's kernel) lowers it: the incpiv cell's TSTRF blocks
_PACKED_PANEL_2176_SHA256 = (
    "bc94b5244b584a3eb645d180bfaa205479aee7ac66dcc272d6e0dc0035b48d56")


def test_on_the_chip_a_tstrf_blocks_kernel_is_lowered_as_pr_41_built_it(
        v5e_2x2, monkeypatch):
    """Stacks of 4096 rows and under keep the packed search of PR 41's
    kernel, byte for byte: the tall kernel is a kernel beside it, taken
    from the stack's shape alone. Locations are left out of the text
    (they are line numbers of this repo's files) and the lowering caches
    are cleared, so that an earlier lowering with them is not reused."""
    import hashlib

    from jax._src import config
    from jax.sharding import SingleDeviceSharding
    from parsec_tpu.ops import tile_kernels
    from parsec_tpu.utils import jax_platform
    monkeypatch.setattr(jax_platform, "cpu_requested", lambda: False)
    jax.clear_caches()
    with config.traceback_in_locations_limit(0):
        text = jax.jit(tile_kernels._lu_panel).lower(jax.ShapeDtypeStruct(
            (2176, 128), np.float32,
            sharding=SingleDeviceSharding(v5e_2x2.devices[0]))).as_text()
    jax.clear_caches()
    assert not tile_kernels._lu_panel_tall(2176)
    assert hashlib.sha256(text.encode()).hexdigest() == \
        _PACKED_PANEL_2176_SHA256


def test_on_the_chip_a_sixteen_tile_panel_is_factored_where_it_lies(
        v5e_2x2, monkeypatch):
    """``ops/tile_kernels.py getrf_panel_tiles`` at the panel-pivoted LU
    cell's tallest shape (sixteen 2048-tiles, IB = 128), compiled by the
    TPU compiler as the panel's chore has it compiled
    (``Chore.compiler_options`` = ``PANEL_COMPILER_OPTIONS``: the scoped
    VMEM XLA's ``LuDecompositionBlock`` needed for the 32768 x 128 block
    until PR 45): the block is factored by the tall VMEM panel
    (``parsec_lu_panel``), not by XLA's kernel; the program is one rolled
    loop (under 16 MiB of text, one such program a list length), holds
    one copy of the stack beside the tiles and writes every tile where it
    was given (all 256 MiB aliased)."""
    from jax.sharding import SingleDeviceSharding
    from parsec_tpu.ops import tile_kernels
    from parsec_tpu.utils import jax_platform
    monkeypatch.setattr(jax_platform, "cpu_requested", lambda: False)
    one = SingleDeviceSharding(v5e_2x2.devices[0])
    tiles = [jax.ShapeDtypeStruct((2048, 2048), np.float32, sharding=one)
             for _ in range(16)]
    compiled = jax.jit(
        lambda ts: tile_kernels.getrf_panel_tiles(ts, 128),
        donate_argnums=0).lower(tiles).compile(
            compiler_options=tile_kernels.PANEL_COMPILER_OPTIONS)
    text = compiled.as_text()
    assert "parsec_lu_panel" in text and "LuDecomposition" not in text
    mem = compiled.memory_analysis()
    stack = 16 * 2048 * 2048 * 4
    assert mem.alias_size_in_bytes == stack
    assert mem.temp_size_in_bytes < 1.1 * stack
    assert mem.generated_code_size_in_bytes < 16 << 20


# ---------------------------------------------------------------------------
# the store key
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hook", ["gemm"], indirect=True)
def test_the_two_partitions_live_under_different_keys(hook):
    """One executor, one ``fn_key``: the runtime's partition and GSPMD's
    are two entries of the jit store, and asking again for either is a
    hit — the same callable, nothing traced or compiled."""
    n = NB * 4
    ex = _left(n)
    mesh = _mesh(2)
    cc.reset_in_process_cache()
    own, _ = _compile(ex, mesh, fn_key="two-keys")
    assert ex.partition_report()["branch"] == "runtime"
    # GSPMD's program of the same executor under the same fn_key, as a
    # tree before the mesh lowering built it
    sh = NamedSharding(mesh, P("rows"))
    plain = PanelExecutor.run_state

    def gspmd():
        return compile_with_plan(
            lambda st: plain(ex, st), mesh=mesh, in_shardings=({"A": sh},),
            out_shardings={"A": sh}, donate_argnums=0,
            example_args=(ex.state_shapes(),),
            fn_key=("two-keys", ex.monolith_cache_key()))

    theirs = gspmd()
    assert theirs is not own and cc.jit_store_size() == 2
    assert _collectives(own) != _collectives(theirs)
    compiles = cc.backend_compile_count()
    hits = cc.cache_stats()["jit_store_hits"]
    assert _compile(ex, mesh, fn_key="two-keys")[0] is own
    assert gspmd() is theirs
    assert cc.backend_compile_count() == compiles
    assert cc.cache_stats()["jit_store_hits"] == hits + 2
    # a rebuilt executor of the same plan finds the same program
    assert _compile(_left(n), mesh, fn_key="two-keys")[0] is own


def test_the_stored_keys_of_the_left_looking_programs():
    """``_potrf_left_wave_fuser``'s text keys the flagship's stored
    program and every one-chip user's: pinned, so that a change to it is
    a decision (PR 40 made one: the update's products alone, in column
    runs; the key carries the run size too). The mesh lowering's text is
    pinned to what PR 32 left: the four-chip cell's program is tuned and
    measured as it is."""
    from parsec_tpu.algorithms.potrf import _potrf_left_mesh_wave_fuser
    ok, fp = cc.function_fingerprint(_potrf_left_wave_fuser)
    assert ok and fp == ("9617ef079e6ecfa11042f18ef67d5efd"
                         "9142959f00ead3ca4af6abbe8eea0eef")
    ok, fp = cc.function_fingerprint(_potrf_left_mesh_wave_fuser)
    assert ok and fp == ("0ea9bfa91342523f5b4b2cd73ea16137"
                         "4d5cc01352c2f97026bd468a08c6e64d")
    key = PanelExecutor(plan_taskpool(build_potrf_left(
        TiledMatrix(256, 256, 64, 64, name="A")))).monolith_cache_key()
    assert key[-1] == 3 * NB * NB * 4      # this file's run size
    assert hashlib.sha256(repr(key[:-1]).encode()).hexdigest() == (
        "69b9a35925faa508631c4cd6d518e65c24fb01380ef94880be7cb456d815173c")
