"""The chip's idle time by host stage, a launch taken apart and the
module's turn, as the benchmark reads them (``benchmark/idle_by_stage.py``):
the partition and the clock bound on hand-made intervals, where the answer
is known; the device lines of a trace recorded on a v5e; and the whole
route on the CPU, where a trace has host planes and no TPU plane, so the
three span figures are printed and the five ``idle_*`` shares are not."""

import gzip
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import idle_by_stage as ibs  # noqa: E402
from benchmark import program_spans as ps  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.run import DRY_SUFFIX, main  # noqa: E402

MAN = Manifest(ROOT)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SPAN_METRICS = ["launch_call_us", "chip_wait_us_per_task", "turn_wait_share"]
IDLE_METRICS = [f"idle_{c}_share" for c in ibs.CLASSES]
DYNAMIC_CELLS = ["gemm_dtd_nb1024", "gemm_dtd_nb4096",
                 "potrf_ptg_host_n49152_nb2048", "potrf_dtd_n49152_nb2048",
                 "geqrf_ptg_host_n32768_nb2048"]
STEP = (10.0, 12.0)


def _spans(threads, steps=(STEP,), traced=(0.0, 100.0)):
    bench = [("traced", *traced)] + [("step", *s) for s in steps]
    return ps.Spans(threads={f"t{i}": t for i, t in enumerate(threads)},
                    bench=sorted(bench, key=lambda s: s[1]))


def _ops(*busy):
    return {0: [(f"%fusion.{i}", lo, hi) for i, (lo, hi) in enumerate(busy)]}


def _shares(out):
    """The five as the metrics give them: of the traced step time."""
    return {c: out[f"idle_{c}_share"] for c in ibs.CLASSES}


def _of_idle(out):
    """The five as a partition of the idle time: they sum to 100."""
    return {c: 100.0 * out["idle_s"][c] / out["idle_s"]["all"]
            for c in ibs.CLASSES}


# a launch that carries its call: without one a program has no such spans
CALL = {"exec": [(10.0, 10.1)], "exec_call": [(10.0, 10.1)]}


# -- the manifest -------------------------------------------------------------

@pytest.mark.parametrize("name", SPAN_METRICS + IDLE_METRICS)
def test_a_new_name_resolves_and_lists_the_five_dynamic_cells(name):
    (entry,) = [m for m in MAN.bench["per_layer"] if m["name"] == name]
    assert entry["workloads"] == DYNAMIC_CELLS
    assert entry["moves"] == "step_s_p50" and entry["better"] == "lower"
    assert entry["source"] == ("program_span" if name in SPAN_METRICS
                               else "device_trace")
    spec = MAN.metric(name)
    assert spec["reader"] == "idle_by_stage"
    assert spec["params"] == {"key": name}
    assert callable(MAN.reader(spec["reader"]).read)
    for cell in DYNAMIC_CELLS:
        assert entry in MAN.metrics_for("per_layer", cell)
    for cell in ("potrf_panel_n40960", "potrf_panel_n65536_x4"):
        assert entry not in MAN.metrics_for("per_layer", cell)


def test_the_eight_are_there_once_each():
    names = [m["name"] for m in MAN.bench["per_layer"]]
    assert all(names.count(n) == 1 for n in SPAN_METRICS + IDLE_METRICS)


# -- the three span figures ---------------------------------------------------

def test_call_wait_and_turn_are_read_over_the_traced_steps():
    w0 = {"select": [(10.0, 10.0)], "turn": [(10.0, 10.5)],
          "exec": [(10.5, 11.5)], "exec_wait": [(10.6, 10.9)],
          "exec_call": [(10.9, 11.3)]}
    w1 = {"park": [(10.0, 10.2)], "turn": [(11.0, 13.0)],     # clipped
          "exec": [(9.5, 10.4)], "exec_call": [(9.8, 10.2)]}  # starts before
    out = ibs.reduce(_spans([w0, w1, {"insert": [(10.0, 11.0)]}]), {}, {}, 4)
    # the call that started before the step is clipped and not counted
    assert out["launch_call_us"] == pytest.approx(1e6 * (0.4 + 0.2) / 1)
    assert out["chip_wait_us_per_task"] == pytest.approx(1e6 * 0.3 / 4)
    assert out["turn_wait_share"] == pytest.approx(100 * 1.5 / (2 * 2.0))
    assert out["spans"] == {"turn": 2, "exec_wait": 1, "exec_call": 1}
    assert out["steps"] == 1 and out["window_s"] == 2.0
    # no device plane: nothing to say of the chip's idle time
    assert _shares(out) == dict.fromkeys(ibs.CLASSES) and out["pairs"] == 0


@pytest.mark.parametrize("spans,tasks", [
    (_spans([{"exec": [(10.0, 11.0)], "dispatch": [(10.0, 11.0)]}]), 4),
    (_spans([CALL], steps=()), 4),
    (_spans([CALL]), 0)])
def test_a_program_without_the_spans_a_trace_without_a_step_read_none(
        spans, tasks):
    assert ibs.reduce(spans, _ops((10.0, 11.0)), {}, tasks) is None


# -- the partition ------------------------------------------------------------

@pytest.mark.parametrize("stage,cls", [
    ("exec", "launching"), ("release", "releasing"),
    ("insert", "front_end"), ("dtd_flush", "front_end"),
    ("ptg_startup", "front_end"), ("select", "front_end"),
    ("dispatch", "front_end"), ("exec_wait", "completion"),
    ("turn", "unaccounted"), ("park", "unaccounted")])
def test_each_rule_of_the_partition(stage, cls):
    """The chip is idle in (11, 12); a thread is inside ``stage`` in
    (11.0, 11.5), nobody in anything after."""
    thread = {stage: [(11.0, 11.5)]}
    if stage == "exec_wait":        # a wait lies inside its launch
        thread["exec"] = [(11.0, 11.5)]
    out = ibs.reduce(_spans([CALL, thread]), _ops((10.0, 11.0)), {}, 1)
    want = dict.fromkeys(ibs.CLASSES, 0.0)
    want["unaccounted"] = 50.0
    want[cls] += 50.0
    assert _of_idle(out) == pytest.approx(want)
    assert out["idle_s"]["all"] == pytest.approx(1.0)
    # the metric is the share of the step's two seconds, so that one
    # class's figure does not rise because another's fell
    assert _shares(out) == pytest.approx({c: v / 2 for c, v in want.items()})


def test_the_first_rule_that_applies_takes_the_instant():
    """Idle in (10.0, 11.0); five threads, each one rule further down."""
    threads = [
        CALL,                                               # to 10.1
        {"exec": [(10.0, 10.4)], "exec_wait": [(10.0, 10.2)],
         "dispatch": [(10.0, 10.45)]},      # launching 10.2-10.4 too
        {"release": [(10.0, 10.5)]},
        {"select": [(10.0, 10.7)]},
        {"exec": [(10.0, 10.8)], "exec_wait": [(10.0, 10.8)]},
        {"turn": [(10.0, 11.0)], "park": [(10.0, 11.0)]}]
    out = ibs.reduce(_spans(threads), _ops((11.0, 12.0)), {}, 1)
    assert _of_idle(out) == pytest.approx({
        "launching": 30.0,      # CALL's 0.1 and the second thread's 0.2
        "releasing": 20.0,      # what release covers beyond them
        "front_end": 20.0,      # select to 10.7; dispatch less its exec
        "completion": 10.0,     # to 10.8: only a wait for the chip
        "unaccounted": 20.0})   # a turn and a park are in no class
    assert sum(_of_idle(out).values()) == pytest.approx(100.0)
    assert out["idle_s"]["all"] == pytest.approx(1.0)
    # of the step they sum to the chip's idle share of it
    assert sum(_shares(out).values()) == pytest.approx(50.0)


def test_a_wait_inside_a_launch_is_not_a_launch_on_its_way():
    """The thread's own ``exec_wait`` is taken out of its ``exec``;
    another thread's is not."""
    w0 = {"exec": [(10.0, 11.0)], "exec_wait": [(10.2, 10.8)],
          "exec_call": [(10.8, 11.0)]}
    out = ibs.reduce(_spans([w0]), _ops((11.0, 12.0)), {}, 1)
    assert _of_idle(out) == pytest.approx({
        "launching": 40.0, "releasing": 0.0, "front_end": 0.0,
        "completion": 60.0, "unaccounted": 0.0})
    w1 = {"exec": [(10.2, 10.8)]}
    out = ibs.reduce(_spans([w0, w1]), _ops((11.0, 12.0)), {}, 1)
    assert _of_idle(out)["launching"] == pytest.approx(100.0)
    assert out["idle_launching_share"] == pytest.approx(50.0)


def test_idle_time_is_of_the_steps_alone_and_of_leaf_operations():
    """Between steps the harness makes the next input; a ``while`` spans
    the operations of its body, and only those were work."""
    ops = {0: [("%while.1", 10.0, 12.0), ("%fusion.1", 10.0, 10.5),
               ("%fusion.2", 11.5, 12.0), ("%fusion.3", 20.5, 21.0)]}
    threads = [CALL, {"release": [(10.5, 11.0), (12.0, 20.2)]}]
    out = ibs.reduce(_spans(threads, steps=[STEP, (20.0, 21.0)]), ops, {}, 1)
    assert out["idle_s"] == pytest.approx({
        "launching": 0.0, "releasing": 0.7, "front_end": 0.0,
        "completion": 0.0, "unaccounted": 0.8, "all": 1.5})
    assert out["steps"] == 2


def test_nothing_idle_reads_none():
    out = ibs.reduce(_spans([CALL]), _ops((9.0, 13.0)), {}, 1)
    assert _shares(out) == dict.fromkeys(ibs.CLASSES)
    assert out["idle_s"]["all"] == 0.0
    assert out["launch_call_us"] == pytest.approx(1e5)


def test_a_stage_repaired_moves_its_own_share_and_no_other():
    """The release that left the chip idle for 0.4 s goes: releasing
    falls; launching and unaccounted read what they read (as shares of
    the idle time both would have risen)."""
    launch = {"exec": [(10.0, 10.2)], "exec_call": [(10.0, 10.2)]}
    before = ibs.reduce(_spans([launch, {"release": [(10.2, 10.6)]}]),
                        _ops((10.8, 12.0)), {}, 1)
    after = ibs.reduce(_spans([launch]), _ops((10.4, 12.0)), {}, 1)
    assert _shares(before) == pytest.approx({
        "launching": 10.0, "releasing": 20.0, "front_end": 0.0,
        "completion": 0.0, "unaccounted": 10.0})
    assert _shares(after) == pytest.approx({
        "launching": 10.0, "releasing": 0.0, "front_end": 0.0,
        "completion": 0.0, "unaccounted": 10.0})
    assert _of_idle(after)["launching"] > _of_idle(before)["launching"]


# -- one clock, checked -------------------------------------------------------

def _launches(calls, programs, wait=()):
    """A worker whose calls start at ``calls`` (0.2 ms each), and the
    chip's line of the programs they started, 1 ms each."""
    worker = {"exec": [(c - 1e-4, c + 3e-4) for c in calls],
              "exec_call": [(c, c + 2e-4) for c in calls]}
    ops = {0: [(f"%fusion.{i}", p, p + 1e-3)
               for i, p in enumerate(programs)]}
    modules = {0: [(f"jit_parsec_GEMM_x4({i})", p, p + 1e-3)
                   for i, p in enumerate(programs)]
               + [("jit_generate(7)", 9.0, 9.5)]}
    return _spans([worker]), ops, modules


def test_a_device_line_half_a_millisecond_early_is_shifted():
    calls = [10.001, 10.004, 10.007]
    # the device stamps each start 0.5 ms before the host stamps its call
    spans, ops, modules = _launches(calls, [c - 5e-4 for c in calls])
    out = ibs.reduce(spans, ops, modules, 1)
    assert out["clock_us"] == pytest.approx(-500.0)
    assert (out["shifted"], out["pairs"]) == (1, 3)
    # after the shift a program starts with its call: the chip is busy
    # from there for 1 ms, idle while the next launch is on its way only
    # where a thread is inside exec (0.1 ms before the call)
    busy = [(c, c + 1e-3) for c in calls]
    idle = ibs.subtract([STEP], busy)
    assert out["idle_s"]["all"] == pytest.approx(ibs.total(idle))
    assert out["idle_s"]["launching"] == pytest.approx(3 * 1e-4)


def test_a_late_device_line_is_left_where_it_is():
    calls = [10.001, 10.004, 10.007]
    spans, ops, modules = _launches(calls, [c + 3e-4 for c in calls])
    out = ibs.reduce(spans, ops, modules, 1)
    assert out["clock_us"] == pytest.approx(300.0)
    assert (out["shifted"], out["pairs"]) == (0, 3)
    # the chip is idle while the call runs and 0.1 ms after it returns
    assert out["idle_s"]["launching"] == pytest.approx(3 * 4e-4)


def test_the_kth_program_is_held_against_the_kth_call_of_its_step():
    """Whichever thread made which call; a program behind a busy chip
    starts long after its call and bounds nothing; counting starts anew
    with every step, and a program that is not the module's is no pair."""
    steps = [(10.0, 11.0), (20.0, 21.0)]
    calls = [10.1, 10.2, 10.3, 20.1]
    programs = [10.1002, 10.5, 10.6,        # queued behind the first
                20.0999]                    # 0.1 ms early: the bound
    assert ibs.clock_bound(programs, calls, steps) == (
        pytest.approx(-1e-4), 4)
    assert ibs.clock_bound(programs[:1], calls, steps)[1] == 1
    assert ibs.clock_bound([], calls, steps) == (None, 0)
    # a start a little before its step's first instant is that step's
    assert ibs.clock_bound([19.9995], [20.0], steps) == (
        pytest.approx(-5e-4), 1)
    w0 = {"exec_call": [(10.1, 10.15), (10.3, 10.35)]}
    w1 = {"exec_call": [(10.2, 10.25), (20.1, 20.15)]}
    modules = {0: [(f"jit_parsec_T_x1({i})", p, p + 0.01)
                   for i, p in enumerate(programs)]
               + [("jit_other(1)", 10.0, 10.05)]}
    out = ibs.reduce(_spans([w0, w1], steps=steps), _ops((10.1, 10.7)),
                     modules, 1)
    assert out["clock_us"] == pytest.approx(-100.0) and out["pairs"] == 4


def test_the_reduction_prints_its_clock_and_its_seconds_a_step(
        capsys, tmp_path, monkeypatch):
    calls = [10.001, 10.004, 10.007]
    spans, ops, modules = _launches(calls, [c - 5e-4 for c in calls])
    out = ibs.reduce(spans, ops, modules, 1)
    monkeypatch.setattr(ibs, "load", lambda path: (spans, ops, modules))
    assert ibs._reduced(str(tmp_path / "made_up"), 0.0, 1) == out
    clock, idle = capsys.readouterr().out.splitlines()
    assert clock == "[clock] device_minus_host_us=-500.000 shifted=1 pairs=3"
    said = dict(kv.split("=") for kv in idle.split()[1:])
    assert idle.startswith("[idle_by_stage] launching_s=0.000300 ")
    assert [k for k in said if k.endswith("_s")] == [
        f"{c}_s" for c in ibs.CLASSES] + ["all_s", "step_s"]
    assert sum(float(said[f"{c}_s"]) for c in ibs.CLASSES) == pytest.approx(
        float(said["all_s"]), abs=1e-5)
    assert float(said["step_s"]) == 2.0 and said["steps"] == "1"
    assert (said["turn_spans"], said["exec_wait_spans"],
            said["exec_call_spans"]) == ("0", "0", "3")


# -- a trace recorded on the chip ---------------------------------------------

def test_the_device_lines_of_a_recorded_trace(tmp_path):
    """Two panel factorizations and two DTD GEMMs on one v5e chip (PR 22:
    before the program had its spans, or names for its programs)."""
    path = tmp_path / "v5e_1chip.xplane.pb"
    with gzip.open(os.path.join(DATA, "v5e_1chip.xplane.pb.gz")) as fh:
        path.write_bytes(fh.read())
    spans, ops, modules = ibs.load(str(path))
    assert sorted(ops) == sorted(modules) == [0]
    assert len(ops[0]) == 1208 and len(modules[0]) == 120
    assert not any(ibs.PROGRAM.match(n) for n, _lo, _hi in modules[0])
    # the host planes in the same pass, as program_spans reads them
    again = ps.load(str(path))
    assert (spans.threads, spans.bench) == (again.threads, again.bench)
    assert len(ibs.steps_of(spans.bench)) == 4
    assert ibs.reduce(spans, ops, modules, 32) is None


# -- the route: run.py leaves the trace, the reader opens it ------------------

@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout of this module's own: other test files rehearse the same
    cells at the same time, and a cell's trace has one place per tree."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return str(root)


def _dry_run(capsys, root, cell, trace, seconds="1.0"):
    from parsec_tpu.utils import mca_param
    # the engine a chip gets (engine_for declines a real accelerator)
    mca_param.set("runtime.native_dtd", 0)
    try:
        rc = main(["--workload", cell, "--seed", "3000000019", "--seconds",
                   seconds, "--trace", str(trace), "--dry-run-cpu=1"],
                  root=root)
    finally:
        mca_param.unset("runtime.native_dtd")
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(lines[-1]), lines


@pytest.mark.parametrize("cell", ["gemm_dtd_nb1024",
                                  "potrf_ptg_host_n49152_nb2048"])
def test_a_traced_rehearsal_prints_the_three_and_leaves_the_five_out(
        capsys, checkout, cell):
    last, lines = _dry_run(capsys, checkout, cell, 1)
    assert last["correct"] is True and last["failed"] == 0
    got = {n[:-len(DRY_SUFFIX)]: m for n, m in last["metrics"].items()}
    units = {m["name"]: m["unit"] for m in MAN.bench["per_layer"]}
    for name in SPAN_METRICS:
        assert got[name]["unit"] == units[name]
        assert got[name]["value"] >= 0
    assert got["launch_call_us"]["value"] > 0
    assert 0 <= got["turn_wait_share"]["value"] <= 100
    assert not set(IDLE_METRICS) & set(got)     # no TPU plane on a CPU
    # parsed once; no pair to bound a clock with
    assert sum(ln.startswith("[idle_by_stage]") for ln in lines) == 1
    assert not any(ln.startswith("[clock]") for ln in lines)
    (line,) = [ln for ln in lines if ln.startswith("[idle_by_stage]")]
    counts = dict(kv.split("=") for kv in line.split()[1:])
    assert int(counts["exec_call_spans"]) >= 1
    assert int(counts["turn_spans"]) >= 1
    # the nesting changed no accepted figure's source: a launch is an
    # exec span, and each has its call
    out = ibs.reduced(checkout, cell, 1)
    spans = ps.load(ps.find(checkout, cell))
    steps = ibs.steps_of(spans.bench)
    launches = sum(1 for stages in spans.threads.values()
                   for lo, _hi in stages.get("exec", ())
                   if any(s0 <= lo < s1 for s0, s1 in steps))
    assert out["spans"]["exec_call"] == launches


def test_an_untraced_rehearsal_prints_none_of_the_eight(capsys, checkout):
    last, lines = _dry_run(capsys, checkout, "gemm_dtd_nb1024", 0, "0.3")
    assert not {n + DRY_SUFFIX for n in SPAN_METRICS + IDLE_METRICS} \
        & set(last["metrics"])
    assert not any(ln.startswith("[idle_by_stage]") for ln in lines)
