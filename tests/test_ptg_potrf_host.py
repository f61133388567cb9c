"""DPLASMA ``dpotrf`` on the dynamic path, on the CPU platform at N=512,
NB=64 (NT=8: 8 POTRF + 28 TRSM + 28 SYRK + 56 GEMM = 120 tasks):
``build_potrf`` through ``ctx.add_taskpool`` over the lower tiles of a
``SymTwoDimBlockCyclic`` matrix, every tile a ``jax.Array`` before the
pool starts, against the plain reference of the ``dpotrf_ptg_host``
configuration. What the benchmark's readers need of the program is
asserted here: the factor, the front end's two spans, the counters by
task class and why group takes ended, and that none of it is made while
the stage timers are off. One accelerator module, the inline CPU module
kept a last resort as beside a real chip (a test steers that; the program
has no knob for it)."""

import gc
import glob
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parsec_tpu as parsec
import parsec_tpu.device.tpu
from parsec_tpu.algorithms import build_potrf
from parsec_tpu.core import context as context_mod
from parsec_tpu.core.task import GROUP_SIZES, DeviceType
from parsec_tpu.data.matrix import SymTwoDimBlockCyclic, TiledMatrix
from parsec_tpu.utils import mca_param

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import generate  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402

MAN = Manifest(ROOT)
REF = MAN.reference("dpotrf_ptg_host_reference")
LIMIT = MAN.config("dpotrf_ptg_host")["correct"]["limit"]
N, NB = 512, 64
NT = N // NB
LOWER = [(i, j) for j in range(NT) for i in range(j, NT)]
CLASSES = {"POTRF": NT, "TRSM": NT * (NT - 1) // 2,
           "SYRK": NT * (NT - 1) // 2,
           "GEMM": NT * (NT - 1) * (NT - 2) // 6}
TASKS = sum(CLASSES.values())
assert TASKS == 120
GROUP_ENDS = ("limit", "empty", "class")


@pytest.fixture
def make_ctx():
    made = []
    knobs = {"device.tpu.max_devices": 1, "potrf.trsm_hook": "gemm"}

    def make(nb_cores=4, **params):
        knobs.update(params)
        for knob, value in knobs.items():
            mca_param.set(knob, value)
        ctx = parsec.init(nb_cores=nb_cores)
        ctx.start()
        # as the registry does where a real accelerator is registered
        ctx.devices.devices[0].weight = 0.01
        made.append(ctx)
        return ctx

    yield make
    for ctx in made:
        parsec.fini(ctx)
    for knob in knobs:
        mca_param.unset(knob)


def _module(ctx):
    (dev,) = ctx.devices.by_type(DeviceType.TPU)
    return dev


def _matrix(seed=7, step=1, dtype=jnp.float32):
    """The configuration's matrix at test size: lower tiles only, each a
    jax.Array; its key and A0 whole."""
    key = generate.step_key(seed, step)
    a0 = REF.dense_a0(key, N, NB)
    A = TiledMatrix(N, N, NB, NB, name="A",
                    dist=SymTwoDimBlockCyclic(1, 1, uplo="lower"))
    for i, j in LOWER:
        A.write_tile((i, j), jnp.asarray(
            a0[i * NB:(i + 1) * NB, j * NB:(j + 1) * NB], dtype))
    assert all(A.dist.stored(i, j) for i, j in LOWER)
    return A, key, a0


def _factor(ctx, A):
    tp = build_potrf(A)
    ctx.add_taskpool(tp)
    assert tp.wait_completed(300)
    assert set(A._tiles) == set(LOWER)      # no upper tile was touched
    return {k: A.data_of(k) for k in LOWER}


def _dense(tiles):
    out = np.zeros((N, N))
    for (i, j), t in tiles.items():
        t = np.asarray(t.astype(jnp.float32), np.float64)
        out[i * NB:(i + 1) * NB, j * NB:(j + 1) * NB] = \
            np.tril(t) if i == j else t
    return out


def _residual(key, tiles):
    """The reference's blocked probe over a dict of lower tiles."""
    with jax.default_matmul_precision("highest"):
        x = REF.probe_vectors(key, N)
        y, z, y2 = (jnp.zeros_like(x) for _ in range(3))
        for j in range(NT):
            y = REF.probe_input_row(j, key, x, y, n=N, nb=NB)
        for (i, j), t in tiles.items():
            z = REF.probe_factor_t(i, j, t, x, z)
        for (i, j), t in tiles.items():
            y2 = REF.probe_factor(i, j, t, z, y2)
        return REF.residual(y, y2)


# -- the factor ------

@pytest.mark.parametrize("nb_cores", [1, 4])
def test_the_factor_is_numpys_cholesky_of_the_references_matrix(
        make_ctx, nb_cores):
    ctx = make_ctx(nb_cores=nb_cores)
    A, key, a0 = _matrix()
    tiles = _factor(ctx, A)
    assert all(isinstance(t, jax.Array) for t in tiles.values())
    want = np.linalg.cholesky(a0)
    assert np.abs(_dense(tiles) - want).max() <= 1e-4 * np.abs(want).max()
    assert _residual(key, tiles) <= LIMIT
    # every task of the graph ran once, on the accelerator module
    assert sum(es.stats["executed"] for es in ctx.streams) == TASKS
    assert _module(ctx).stats["tasks"] == TASKS
    # the probe is of THIS matrix: another step's key fails it
    assert _residual(generate.step_key(7, 2), tiles) > LIMIT


def test_bodies_that_compute_in_bfloat16_fail_the_references_limit(
        make_ctx):
    ctx = make_ctx()
    A, key, a0 = _matrix(dtype=jnp.bfloat16)
    tiles = _factor(ctx, A)
    assert all(t.dtype == jnp.bfloat16 for t in tiles.values())
    want = np.linalg.cholesky(a0)
    assert np.abs(_dense(tiles) - want).max() > 1e-4 * np.abs(want).max()
    assert _residual(key, tiles) > 10 * LIMIT


def test_a_sound_factor_rounded_to_bfloat16_fails_the_references_limit(
        make_ctx):
    ctx = make_ctx()
    A, key, _a0 = _matrix()
    tiles = _factor(ctx, A)
    rounded = {k: t.astype(jnp.bfloat16).astype(jnp.float32)
               for k, t in tiles.items()}
    assert _residual(key, tiles) <= LIMIT < _residual(key, rounded)


# -- the cell's graph in groups: what `correct` holds the chip to ------

@pytest.mark.parametrize("nb_cores", [1, 4])
def test_in_groups_the_factor_every_task_and_the_storage_hold(
        make_ctx, nb_cores):
    """The benchmark's three conditions at test size, with the classes
    leaving in groups: the residual, every task on the module, and each
    tile held once when the pool has ended (the storage guarantee's
    shadow on the CPU: no launch keeps an output, no bin a task)."""
    ctx = make_ctx(nb_cores=nb_cores)
    A, key, _a0 = _matrix()
    _factor(ctx, A)                     # compiles; constants are made
    dev = _module(ctx)
    groups, tasks = dev.stats["batches"], dev.stats["tasks"]
    gc.collect()                        # what earlier tests left behind
    before = _tile_sized()              # the first matrix's 36 tiles
    A2, key, _a0 = _matrix(step=2)
    tiles = _factor(ctx, A2)
    assert _residual(key, tiles) <= LIMIT
    assert dev.stats["tasks"] - tasks == TASKS
    assert dev.stats["batches"] > groups
    assert sum(es.stats["executed"] for es in ctx.streams) == 2 * TASKS
    assert all(d.load == 0.0 for d in ctx.devices.devices)
    # what the second pool left is its matrix and nothing else (the
    # module's last launch's output is one of its tiles): no launch keeps
    # an output, no bin a task. A parked worker still holds the last task
    # it completed, and with it the version of a tile that task read:
    # after either pool, so the two readings differ by the workers that
    # did not run the last POTRF
    del tiles
    gc.collect()
    assert abs(_tile_sized() - before - len(LOWER)) <= nb_cores - 1


# -- in place: every update is the tile's one copy ------

def _tile_sized():
    return sum(1 for a in jax.live_arrays() if a.shape == (NB, NB))


@pytest.mark.parametrize("nb_cores", [1, 4])
def test_every_update_is_written_to_its_tile_and_frees_the_last_version(
        make_ctx, nb_cores):
    ctx = make_ctx(nb_cores=nb_cores)
    A, _key, _a0 = _matrix()
    _factor(ctx, A)                     # compiles; constants are made
    A, key, _a0 = _matrix()
    tp = build_potrf(A)
    held, stale = [], []

    def updated(task):
        """At a SYRK's or GEMM's completion, before what it released
        is scheduled: the collection holds what the task made."""
        m, n = task.locals[0], task.locals[-2]
        if A.data_of((m, n if task.task_class.name == "GEMM" else m)) \
                is not task.output["C"]:
            stale.append(task.locals)
        held.append(_tile_sized())

    for name in ("SYRK", "GEMM"):
        tp.task_class_by_name(name).on_complete = updated
    before = _tile_sized()
    ctx.add_taskpool(tp)
    assert tp.wait_completed(300)
    assert len(held) == CLASSES["SYRK"] + CLASSES["GEMM"] and not stale
    # beside the matrix: nothing. Every body writes where its tile lies
    # (``Chore.donates``), so a launch, alone or a group, one queued or
    # two, makes no array the matrix did not hold (until PR 36 a launch's
    # outputs stood beside the versions they replaced until its members'
    # release: GROUP_SIZES[0] + 4 * (nb_cores - 1) tiles at the most)
    assert max(held) <= before, (before, max(held))
    dev = _module(ctx)
    assert dev.stats["groups_in_place"] == dev.stats["batches"] > 0
    assert dev.stats["lone_in_place"] == \
        dev.stats["tasks"] - dev.stats["batched_tasks"]
    assert _residual(key, {k: A.data_of(k) for k in LOWER}) <= LIMIT


def test_a_callers_tile_is_gone_once_its_update_is_launched(make_ctx):
    """Every tile of the triangle is updated by some task where it lies:
    the arrays the caller wrote into the collection are deleted, the
    collection holds the factor, and nothing deleted can be reached from
    it."""
    ctx = make_ctx()
    A, key, _a0 = _matrix()
    first = {k: A.data_of(k) for k in LOWER}
    tiles = _factor(ctx, A)
    assert all(t.is_deleted() for t in first.values())
    assert not any(t.is_deleted() for t in tiles.values())
    assert _residual(key, tiles) <= LIMIT


@pytest.mark.parametrize("name", list(CLASSES))
def test_every_program_of_the_cells_bodies_holds_nothing_new(make_ctx, name):
    """What the module read off each program it built for the cell's
    bodies (``TPUDevice._build``: the outputs of a program's first run
    that lie in no buffer it was given): nothing, for the lone program
    and for every group size, the stacked forms of TRSM (one product for
    a column) among them. That figure, not the declaration, is what ends
    a group's turn at its call and queues it behind the last group."""
    ctx = make_ctx(nb_cores=1)          # one worker: whole bins, so groups
    A, _key, _a0 = _matrix()
    tp = build_potrf(A)
    (chore,) = tp.task_class_by_name(name).incarnations
    ctx.add_taskpool(tp)
    assert tp.wait_completed(300)
    built = {(slot[2], size): program.held
             for slot, programs in _module(ctx)._table[id(chore)].items()
             if isinstance(slot, tuple)
             for size, program in programs.items()}
    assert (False, 1) in built and set(built.values()) == {0}, built
    # a POTRF is ready alone and never stacked; a TRSM's column is
    if name != "POTRF":
        assert any(size > 1 for _stacked, size in built)
    assert any(stacked for stacked, _size in built) == (name == "TRSM")


# -- the front end's spans, the counters by class, why takes ended ------

def _parsec_spans(trace_dir):
    """``{thread: {stage: [(start_ns, end_ns), ...]}}``."""
    (path,) = glob.glob(trace_dir + "/plugins/profile/*/*.xplane.pb")
    spans = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("parsec:"):
                    spans.setdefault((plane.name, i), {}).setdefault(
                        e.name[len("parsec:"):], []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    return spans


def test_a_traced_pool_has_one_startup_span_and_an_unfold_span_a_task(
        make_ctx, tmp_path):
    ctx = make_ctx()
    A, _key, _a0 = _matrix()
    _factor(ctx, A)                     # compiles; no session, no span
    assert not ctx.stage_timers
    A, _key, _a0 = _matrix()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _factor(ctx, A)
        assert ctx.stage_timers
    finally:
        jax.profiler.stop_trace()
    spans = _parsec_spans(str(tmp_path))

    def count(stage):
        return sum(len(t.get(stage, ())) for t in spans.values())

    # the pool's enumeration, on the thread that added it
    assert count("ptg_startup") == 1
    (adder,) = [t for t in spans.values() if "ptg_startup" in t]
    assert "select" not in adder
    # a release a task, the successor list nested in it
    assert count("release") == count("ptg_unfold") == TASKS
    for thread in spans.values():
        for lo, hi in thread.get("ptg_unfold", ()):
            assert any(r0 <= lo and hi <= r1 for r0, r1 in thread["release"])
    unfold = 1e-9 * sum(hi - lo for t in spans.values()
                        for lo, hi in t.get("ptg_unfold", ()))
    summed = sum(es.stats["unfold_s"] for es in ctx.streams)
    assert 0 < summed <= unfold <= sum(es.stats["release_s"]
                                       for es in ctx.streams)


def test_the_overhead_module_reports_the_unfolding_beside_release(make_ctx):
    from parsec_tpu.profiling.pins_modules import new_module
    ctx = make_ctx()
    mod = new_module("overhead").install(ctx)
    A, _key, _a0 = _matrix()
    _factor(ctx, A)
    rep = mod.report()
    mod.uninstall()
    assert rep["executed"] == TASKS
    assert 0 < rep["unfold_s"] <= rep["release_s"]


@pytest.mark.parametrize("nb_cores", [1, 4])
def test_with_the_timers_on_tasks_and_launches_are_counted_by_class(
        make_ctx, monkeypatch, nb_cores):
    ctx = make_ctx(nb_cores=nb_cores)
    dev = _module(ctx)
    takes = []
    take = ctx._take_group

    def counted(es, task, chore, module, limit):
        bins = take(es, task, chore, module, limit)
        takes.append(len(bins))
        return bins

    monkeypatch.setattr(ctx, "_take_group", counted)
    handed = {}                         # the smallest bin a class handed
    launch = ctx._group_launch

    def watched(es, tasks, chore, module, later):
        name = tasks[0].task_class.name
        handed[name] = min(handed.get(name, len(tasks)), len(tasks))
        launch(es, tasks, chore, module, later)

    monkeypatch.setattr(ctx, "_group_launch", watched)
    A, _key, _a0 = _matrix()
    _factor(ctx, A)                     # the timers are off: no count
    # only bins of a group's size are handed to the module as groups; a
    # POTRF, one ready at a time, never forms one (its stacked programs
    # are never built: TPUDevice._programs)
    assert "GEMM" in handed and "POTRF" not in handed
    # SYRKs become ready one by one: a bin of four forms for certain
    # under one worker; four racing workers take them apart in about
    # half the runs
    assert nb_cores > 1 or "SYRK" in handed
    assert handed.get("TRSM", GROUP_SIZES[-1]) >= GROUP_SIZES[-1]
    assert takes and dev.dump_statistics()["tasks_by_class"] == {}
    assert not any(es.stats["group_end_" + why] for es in ctx.streams
                   for why in GROUP_ENDS)

    del takes[:]
    before = dev.dump_statistics()
    ctx.set_stage_timers(True)
    A, _key, _a0 = _matrix()
    _factor(ctx, A)
    ctx.set_stage_timers(False)
    stats = dev.dump_statistics()
    assert stats["tasks_by_class"] == CLASSES
    launches = stats["launches_by_class"]
    # a launch is a lone task or a group; every body can leave in a
    # group, but one POTRF is ready at a time
    assert launches["POTRF"] == CLASSES["POTRF"]
    assert all(1 <= launches[c] <= CLASSES[c] for c in CLASSES)
    assert launches["GEMM"] < CLASSES["GEMM"]
    batches, batched = (stats[k] - before[k]
                        for k in ("batches", "batched_tasks"))
    assert sum(launches.values()) == TASKS - batched + batches
    # one reason a take, whatever became of the tasks taken; within one
    # pool every task can be grouped, so none ends on another class
    ends = {why: sum(es.stats["group_end_" + why] for es in ctx.streams)
            for why in GROUP_ENDS}
    assert sum(ends.values()) == len(takes) > 0
    assert ends["class"] == 0
    assert sum(es.stats["group_bins"] for es in ctx.streams) == sum(takes)
    # the statistics are a copy: a reader's delta is of two readings
    stats["tasks_by_class"]["GEMM"] = 0
    assert dev.dump_statistics()["tasks_by_class"]["GEMM"] == CLASSES["GEMM"]


def test_with_the_timers_off_no_span_object_is_made(make_ctx, monkeypatch):
    made = []

    class Counting(context_mod.StageSpan):
        def __init__(self, name):
            made.append(name)
            super().__init__(name)

    for site in (context_mod, parsec_tpu.device.tpu):
        monkeypatch.setattr(site, "StageSpan", Counting)
    ctx = make_ctx()
    A, _key, _a0 = _matrix()
    _factor(ctx, A)
    assert made == [] and not ctx.stage_timers
    assert all(es.stats["unfold_s"] == 0.0 for es in ctx.streams)
    # and with them on, the two new names are among those made
    ctx.set_stage_timers(True)
    A, _key, _a0 = _matrix()
    _factor(ctx, A)
    ctx.set_stage_timers(False)
    assert made.count(context_mod.SPAN_PTG_STARTUP) == 1
    assert made.count(context_mod.SPAN_PTG_UNFOLD) == TASKS
    assert made.count(context_mod.SPAN_RELEASE) == TASKS
