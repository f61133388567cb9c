"""The runtime's stage spans (``parsec:insert/select/park/dispatch/exec/
release``, and since PR 35 ``parsec:turn`` and a launch taken apart:
``parsec:exec_wait``, ``parsec:exec_call``): opened at the stage-timer
sites behind ``context.stage_timers``,
which is on while a profiler session is live; written into the profiler's
own trace and, as before, summed into ``es.stats`` / ``Taskpool.insert_s``.
A small DTD GEMM with accelerator-typed bodies on the CPU platform, read
back with ``jax.profiler.ProfileData``."""

import glob
import threading

import jax
import jax.numpy as jnp
import pytest

import parsec_tpu as parsec
import parsec_tpu.device.tpu     # a StageSpan site patched below
from parsec_tpu import dtd, serving
from parsec_tpu.core import context as context_mod
from parsec_tpu.core.task import DeviceType
from parsec_tpu.data.matrix import TiledMatrix
from parsec_tpu.profiling.pins_modules import new_module
from parsec_tpu.utils import mca_param

NB, MT, NT, KT = 16, 3, 2, 2
TASKS, INSERT_CALLS = MT * NT * KT, MT
STAGES = ("insert", "select", "park", "dispatch", "exec", "release",
          "turn", "exec_wait", "exec_call")
# a GEMM whose rows of C hold four ready tasks each: groups form
GROUPS = (4, 4, 2)


def _gemm_body(a, b, c):
    return c + a @ b


def _matrix(name, mt, nt):
    m = TiledMatrix(mt * NB, nt * NB, NB, NB, name=name)
    for key in m.keys():
        m.write_tile(key, jnp.ones((NB, NB), jnp.float32))
    return m


def _look(c):
    return None


def _run_pool(ctx, name, timeout=60.0, pure=True, shape=(MT, NT, KT),
              kept=False):
    """One GEMM through a new pool, every task on an accelerator module:
    one insert_tasks call per row of C tiles, as insert_gemm_dtd makes.
    ``pure`` bodies may share a launch (a worker that selects four of
    them issues one group program); impure ones run one by one.
    ``kept``: a reader of every C tile (a CPU body) goes in before each
    of its writers, a block of k at a time, so that no writer is given
    its tile and every launch holds new outputs (the front end gives an
    INOUT tile nobody else read to the program, which then holds
    none)."""
    mt, nt, kt = shape
    a, b, c = _matrix("A", mt, kt), _matrix("B", kt, nt), _matrix("C", mt, nt)
    tp = dtd.Taskpool(name)
    ctx.add_taskpool(tp)
    for m in range(mt):
        for ks in ([[k] for k in range(kt)] if kept else [range(kt)]):
            if kept:
                tp.insert_tasks(_look, [(dtd.TileArg(c, (m, n), dtd.INPUT),)
                                        for n in range(nt)],
                                device=DeviceType.CPU)
            tp.insert_tasks(
                _gemm_body,
                [(dtd.TileArg(a, (m, k), dtd.INPUT),
                  dtd.TileArg(b, (k, n), dtd.INPUT),
                  dtd.TileArg(c, (m, n), dtd.INOUT, affinity=True))
                 for n in range(nt) for k in ks],
                device=DeviceType.TPU, pure=pure)
    waiter = threading.Thread(target=tp.wait, daemon=True)
    waiter.start()
    waiter.join(timeout)
    assert not waiter.is_alive(), f"pool {name} did not drain"
    assert tp._native is None
    assert float(c.data_of((0, 0))[0, 0]) == 1.0 + kt * NB
    return tp


def _groups(ctx):
    """(group launches, tasks in them) on the accelerator modules so far."""
    stats = [d.stats for d in ctx.devices.by_type(DeviceType.TPU)]
    return (sum(s["batches"] for s in stats),
            sum(s["batched_tasks"] for s in stats))


def _launches(ctx, before, tasks=TASKS):
    """Launches a pool's ``tasks`` tasks took since ``before =
    _groups(ctx)``: the tasks that shared none, and the group launches.
    An exec span each."""
    groups, grouped = (now - then
                       for now, then in zip(_groups(ctx), before))
    return tasks - grouped + groups


def _module(ctx):
    (dev,) = ctx.devices.by_type(DeviceType.TPU)
    return dev


@pytest.fixture
def make_ctx():
    """Contexts on the Python engine (the one a chip gets: engine_for
    declines a real accelerator), with the knobs a case sets undone."""
    made, knobs = [], []

    def make(scheduler="lfq", nb_cores=3, **params):
        # one accelerator module (the suite runs on 8 virtual devices)
        params = {"runtime.native_dtd": 0, "device.tpu.max_devices": 1,
                  **params}
        for knob, value in params.items():
            mca_param.set(knob, value)
            knobs.append(knob)
        ctx = parsec.init(nb_cores=nb_cores, scheduler=scheduler)
        ctx.start()
        made.append(ctx)
        return ctx

    yield make
    for ctx in made:
        parsec.fini(ctx)
    for knob in knobs:
        mca_param.unset(knob)


class _Session:
    """``jax.profiler`` session around the body; ``spans`` afterwards:
    ``{thread: {stage: [(start_ns, end_ns), ...]}}`` of the ``parsec:``
    events."""

    def __init__(self, tmp_path):
        self.dir, self.spans = str(tmp_path / "trace"), {}

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        (path,) = glob.glob(self.dir + "/plugins/profile/*/*.xplane.pb")
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith("parsec:"):
                        self.spans.setdefault((plane.name, i), {}) \
                            .setdefault(e.name[len("parsec:"):], []).append(
                                (e.start_ns, e.start_ns + e.duration_ns))

    def count(self, stage):
        return sum(len(t.get(stage, ())) for t in self.spans.values())

    def seconds(self, stage):
        return 1e-9 * sum(hi - lo for t in self.spans.values()
                          for lo, hi in t.get(stage, ()))


@pytest.mark.parametrize("scheduler,batch", [
    ("lfq", 0), ("gd", 0), ("wfq", 0), ("lfq", 1), ("gd", 1), ("wfq", 1)])
def test_a_traced_pool_has_its_stages_in_the_profile(
        make_ctx, tmp_path, scheduler, batch):
    ctx = make_ctx(scheduler)
    pure = bool(batch)      # impure bodies never share a launch
    _run_pool(ctx, "warm", pure=pure)       # compiles; no session, no span
    assert not ctx.stage_timers
    before = _groups(ctx)
    with _Session(tmp_path) as prof:
        tp = _run_pool(ctx, "traced", pure=pure)
        assert ctx.stage_timers
    launches = _launches(ctx, before)
    assert {s for t in prof.spans.values() for s in t} <= set(STAGES)
    assert prof.count("insert") == INSERT_CALLS
    assert prof.count("release") == TASKS
    assert prof.count("select") >= 1
    workers = [t for t in prof.spans.values() if "select" in t]
    assert 1 <= len(workers) <= ctx.nb_cores
    # a dispatch span a task; an exec span a launch, however many tasks
    assert prof.count("dispatch") == TASKS
    assert prof.count("exec") == launches
    assert launches == TASKS if not batch else 1 <= launches <= TASKS
    for thread in prof.spans.values():
        for lo, hi in thread.get("exec", ()):
            # a lone task's launch is nested in its dispatch span; a group
            # is launched after its members' spans, on the same thread
            assert any(d0 <= lo and (hi <= d1 or (batch and d1 <= lo))
                       for d0, d1 in thread["dispatch"])
    # the sums the `overhead` module reports are of the same passes: held
    # to their counts. The clocks are not compared: a grouped task's
    # dispatch span is its prepare_input alone, a microsecond or two, and
    # the span's own cost is of that order (nor is a select's)
    stats = {k: sum(es.stats[k] for es in ctx.streams)
             for k in ("select_s", "select_calls", "dispatch_s", "release_s")}
    assert tp.insert_calls == TASKS and tp.insert_s > 0
    assert all(stats[k] > 0 for k in ("select_s", "dispatch_s", "release_s"))
    assert all(prof.seconds(stage) > 0
               for stage in ("insert", "select", "dispatch", "release"))
    # an idle worker goes on selecting once the session has ended
    assert prof.count("select") <= stats["select_calls"]


def test_no_session_no_span_and_the_flag_follows_the_session(
        make_ctx, tmp_path, monkeypatch):
    made = []

    class Counting(context_mod.StageSpan):
        def __init__(self, name):
            made.append(name)
            super().__init__(name)

    for site in (context_mod, dtd, parsec_tpu.device.tpu):
        monkeypatch.setattr(site, "StageSpan", Counting)
    ctx = make_ctx()
    _run_pool(ctx, "untraced")
    assert not ctx.stage_timers and made == []
    assert all(es.stats["dispatch_s"] == 0.0 for es in ctx.streams)
    with _Session(tmp_path) as prof:
        _run_pool(ctx, "traced")
        assert ctx.stage_timers and not ctx.stage_timers_asked
    assert prof.count("dispatch") == TASKS == made.count("parsec:dispatch")
    # still on until a pool is added: the profiler is read once per pool,
    # and without a session a span is next to nothing
    assert ctx.stage_timers
    _run_pool(ctx, "after")
    assert not ctx.stage_timers
    del made[:]
    _run_pool(ctx, "after2")
    assert made == [] or set(made) == {"parsec:park"}   # a worker mid-wait


@pytest.mark.parametrize("how", ["parameter", "overhead_module"])
def test_what_was_asked_for_is_not_switched_off_by_the_profiler(
        make_ctx, tmp_path, how):
    if how == "parameter":
        ctx = make_ctx(**{"runtime.stage_timers": 1})
    else:
        ctx = make_ctx()
        mod = new_module("overhead").install(ctx)
    assert ctx.stage_timers and ctx.stage_timers_asked
    _run_pool(ctx, "asked")
    with _Session(tmp_path) as prof:
        _run_pool(ctx, "traced")
    _run_pool(ctx, "after")
    assert ctx.stage_timers and prof.count("dispatch") == TASKS
    assert sum(es.stats["dispatch_s"] for es in ctx.streams) > 0
    if how == "overhead_module":
        rep = mod.report()
        assert rep["executed"] == 3 * TASKS == rep["insert_calls"]
        assert rep["per_task_us"]["dispatch"] > 0
        mod.uninstall()
        assert not ctx.stage_timers and not ctx.stage_timers_asked


def test_overhead_module_installed_under_a_session_outlives_it(
        make_ctx, tmp_path):
    ctx = make_ctx()
    with _Session(tmp_path):
        _run_pool(ctx, "traced")
        mod = new_module("overhead").install(ctx)
    _run_pool(ctx, "after")
    assert ctx.stage_timers
    mod.uninstall()
    assert not ctx.stage_timers


def test_a_live_profiler_does_not_start_the_overhead_shedder(
        make_ctx, tmp_path):
    """serving's second shedding trigger reads the per-stage sums: only
    where they were asked for. A profiler changes records, no behaviour."""
    ctx = make_ctx("wfq", **{"serving.shed_overhead_us": 0.001})
    rt = serving.enable(ctx)
    with _Session(tmp_path):
        _run_pool(ctx, "traced")
        assert ctx.stage_timers
        assert sum(es.stats["dispatch_s"] for es in ctx.streams) > 0
        assert rt._overload_reason() is None
    ctx.set_stage_timers(True)
    assert "serving.shed_overhead_us" in rt._overload_reason()


# -- a launch taken apart, and the module's turn (PR 35) ----------------------

def _inside(span, others):
    return any(lo <= span[0] and span[1] <= hi for lo, hi in others)


@pytest.mark.parametrize("nb_cores", [1, 3])
def test_a_traced_pool_with_groups_shows_the_turn_and_the_launch_apart(
        make_ctx, tmp_path, nb_cores):
    ctx = make_ctx(nb_cores=nb_cores)
    dev = _module(ctx)
    # compiles; leaves a last group (kept: a group that holds new
    # outputs waits for the one before it)
    _run_pool(ctx, "warm", shape=GROUPS, kept=True)
    tasks = GROUPS[0] * GROUPS[1] * GROUPS[2]
    before, stats0 = _groups(ctx), dict(dev.stats)
    turn0 = sum(es.stats["turn_s"] for es in ctx.streams)
    assert before[0] >= 1 and turn0 == 0.0
    assert stats0["call_s"] == stats0["chip_wait_s"] == 0.0
    with _Session(tmp_path) as prof:
        _run_pool(ctx, "traced", shape=GROUPS, kept=True)
    launches = _launches(ctx, before, tasks)
    groups = _groups(ctx)[0] - before[0]
    assert groups >= 1
    # a call a launch; a wait a group (the module's last group is there
    # to be waited for: the warm pool's, then this pool's own); a turn
    # taken by every worker that held a task of a body with groups
    assert prof.count("exec") == prof.count("exec_call") == launches
    assert prof.count("exec_wait") == groups \
        == dev.stats["chip_waits"] - stats0["chip_waits"]
    assert 1 <= prof.count("turn") <= tasks
    for thread in prof.spans.values():
        execs = thread.get("exec", ())
        inner = thread.get("exec_wait", []) + thread.get("exec_call", [])
        # both nested in a parsec:exec of the same thread, and what they
        # take is a part of it
        assert all(_inside(span, execs) for span in inner)
        assert sum(hi - lo for lo, hi in inner) <= \
            sum(hi - lo for lo, hi in execs)
        # a turn is taken outside every other span of its thread
        others = [span for stage, spans in thread.items()
                  if stage != "turn" for span in spans]
        assert not any(lo < hi0 and lo0 < hi
                       for lo, hi in thread.get("turn", ())
                       for lo0, hi0 in others)
    # the sums are of the same passes
    assert dev.stats["call_s"] > 0 and dev.stats["chip_wait_s"] > 0
    assert dev.stats["call_s"] == pytest.approx(
        prof.seconds("exec_call"), rel=0.5)
    assert sum(es.stats["turn_s"] for es in ctx.streams) > 0
    assert dev.stats["call_max_s"] >= stats0["call_max_s"] > 0


def test_no_session_no_turn_wait_or_call_and_no_growth_of_their_sums(
        make_ctx, monkeypatch):
    made = []

    class Counting(context_mod.StageSpan):
        def __init__(self, name):
            made.append(name)
            super().__init__(name)

    for site in (context_mod, dtd, parsec_tpu.device.tpu):
        monkeypatch.setattr(site, "StageSpan", Counting)
    ctx = make_ctx(nb_cores=1)
    dev = _module(ctx)
    _run_pool(ctx, "warm", shape=GROUPS, kept=True)
    _run_pool(ctx, "untraced", shape=GROUPS, kept=True)
    assert not ctx.stage_timers and made == []
    assert _groups(ctx)[0] >= 2             # waits were made, and calls
    assert all(es.stats["turn_s"] == 0.0 for es in ctx.streams)
    assert dev.stats["chip_wait_s"] == dev.stats["call_s"] == 0.0
    assert dev.stats["chip_waits"] == 0
    # the stall's signature is kept all the same: the longest of each
    assert dev.stats["call_max_s"] > 0 and dev.stats["chip_wait_max_s"] > 0
    ctx.set_stage_timers(True)
    _run_pool(ctx, "timed", shape=GROUPS, kept=True)
    assert {"parsec:turn", "parsec:exec_wait", "parsec:exec_call"} \
        <= set(made)
    assert made.count("parsec:exec_wait") == dev.stats["chip_waits"] >= 1
    assert made.count("parsec:exec_call") == made.count("parsec:exec")


@pytest.mark.parametrize("launches,waits", [(3, 0), (5, 2)])
def test_a_lone_launch_over_the_queue_bound_waits_under_its_span(
        make_ctx, monkeypatch, launches, waits):
    """``TPUDevice._queued``: the lone launches still queued hold
    ``GROUP_BYTES`` of new outputs at most (three tiles here); the thread
    that enqueues more waits for the oldest, under ``parsec:exec_wait``.
    Under the bound no wait is made and no span opened."""
    import numpy as np
    from parsec_tpu.core.task import Chore, Flow, FlowAccess, Task
    from parsec_tpu.core.taskpool import Taskpool
    made = []

    class Counting(context_mod.StageSpan):
        def __init__(self, name):
            made.append(name)
            super().__init__(name)

    monkeypatch.setattr(parsec_tpu.device.tpu, "StageSpan", Counting)
    monkeypatch.setattr(parsec_tpu.device.tpu, "GROUP_BYTES", 3 * 8 * 8 * 4)
    ctx = make_ctx(nb_cores=1)
    ctx.set_stage_timers(True)
    dev = _module(ctx)
    tp = Taskpool("lone")
    tc = tp.new_task_class("H", params=("i",),
                           flows=[Flow("x", FlowAccess.RW)])
    chore = Chore(DeviceType.TPU, lambda task, x: {"x": 2.0 * x})
    tc.add_chore(chore)
    tp.context = ctx
    held = []                   # an output somebody holds is waited for
    for i in range(launches):
        t = Task(tp, tc, (i,))
        t.data["x"] = np.full((8, 8), float(i), np.float32)
        dev.execute(None, t, chore)
        held.append(t)
    assert made.count("parsec:exec") == launches \
        == made.count("parsec:exec_call")
    assert made.count("parsec:exec_wait") == waits == dev.stats["chip_waits"]
    assert (dev.stats["chip_wait_s"] > 0) == bool(waits)
    assert dev.stats["batches"] == 0


def test_statusz_carries_each_modules_longest_wait_and_call(make_ctx):
    ctx = make_ctx(nb_cores=1)
    _run_pool(ctx, "untraced", shape=GROUPS, kept=True)     # waits made
    _run_pool(ctx, "again", shape=GROUPS, kept=True)
    devices = {d["name"]: d for d in ctx.statusz()["devices"]}
    assert devices == {d["name"]: d
                       for d in ctx.devices.dump_statistics()}
    chip = devices[_module(ctx).name]
    assert chip["call_max_s"] > 0 and chip["chip_wait_max_s"] > 0
    assert chip["call_s"] == chip["chip_wait_s"] == 0.0     # untraced
    # a sum nobody read made way for them
    assert not any("exec_s" in d for d in devices.values())
    import json
    json.dumps(ctx.statusz()["devices"])
