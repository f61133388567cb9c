"""The runtime's stage spans as the benchmark reads them: the reduction on
hand-made intervals, where the answer is known, and the whole route on the
CPU (``--dry-run-cpu --trace 1`` leaves a trace with host planes; the
reader finds it where ``run.py`` left it)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import program_spans as ps  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.run import DRY_SUFFIX, main  # noqa: E402

MAN = Manifest(ROOT)
STAGE_METRICS = [m["name"] for m in MAN.bench["per_layer"]
                 if MAN.metric(m["name"])["reader"] == "program_stage"]
DTD_CELLS = ["gemm_dtd_nb1024", "gemm_dtd_nb4096"]


def _spans(threads, steps=((10.0, 12.0),), traced=(0.0, 100.0), insert=()):
    bench = [("traced", *traced)] + [("step", *s) for s in steps] + \
        [("insert", *i) for i in insert]
    return ps.Spans(threads={f"t{i}": t for i, t in enumerate(threads)},
                    bench=sorted(bench, key=lambda s: s[1]))


# -- the reduction ------------------------------------------------------------

def test_eight_metrics_are_declared_for_the_dtd_cells_alone():
    assert len(STAGE_METRICS) == 8
    for m in MAN.bench["per_layer"]:
        if m["name"] in STAGE_METRICS:
            assert m["workloads"] == DTD_CELLS
            assert m["source"] == "program_span"
            assert m["moves"] == "step_s_p50"
            assert MAN.metric(m["name"])["params"] == {"key": m["name"]}


def test_seconds_are_divided_by_the_tasks_of_the_traced_steps():
    worker = {"select": [(10.0, 10.1), (11.0, 11.1)],
              "dispatch": [(10.1, 10.5)], "release": [(10.5, 10.6)]}
    inserter = {"insert": [(10.0, 10.8)]}
    out = ps.reduce(_spans([worker, inserter]), tasks_per_step=4)
    assert out["steps"] == 1 and out["window_s"] == 2.0
    assert out["select_us_per_task"] == pytest.approx(0.2e6 / 4)
    assert out["dispatch_us_per_task"] == pytest.approx(0.4e6 / 4)
    assert out["release_us_per_task"] == pytest.approx(0.1e6 / 4)
    assert out["insert_us_per_task"] == pytest.approx(0.8e6 / 4)
    assert out["exec_enqueue_us_per_task"] == 0.0
    assert out["host_threads_busy_mean"] == pytest.approx(1.5 / 2.0)
    assert out["dispatch_spans"] == 1 and out["workers"] == 1
    assert set(STAGE_METRICS) <= set(out)


def test_two_steps_double_the_tasks():
    worker = {"dispatch": [(10.0, 11.0), (20.0, 21.0)], "select": []}
    out = ps.reduce(_spans([worker], steps=[(10.0, 12.0), (20.0, 22.0)]), 5)
    assert out["steps"] == 2 and out["window_s"] == 4.0
    assert out["dispatch_us_per_task"] == pytest.approx(2.0e6 / 10)
    assert out["dispatch_spans"] == 2


def test_spans_are_clipped_to_the_traced_steps():
    """Between steps the harness makes the next input; a step outside
    ``bench:traced`` was not traced whole."""
    worker = {"select": [(9.0, 10.5)],              # starts before the step
              "dispatch": [(11.5, 13.0)],           # ends after it
              "release": [(14.0, 15.0)],            # between steps
              "park": [(0.0, 100.0)]}
    out = ps.reduce(_spans([worker], steps=[(10.0, 12.0), (200.0, 201.0)]), 1)
    assert out["steps"] == 1
    assert out["select_us_per_task"] == pytest.approx(0.5e6)
    assert out["dispatch_us_per_task"] == pytest.approx(0.5e6)
    assert out["release_us_per_task"] == 0.0
    assert out["workers_parked_share"] == pytest.approx(100.0)
    assert out["dispatch_spans"] == 1


def test_nested_exec_is_taken_out_of_its_threads_dispatch():
    w0 = {"dispatch": [(10.0, 11.0)], "exec": [(10.2, 10.9)]}
    w1 = {"dispatch": [(10.0, 11.0)]}      # another thread's exec is not
    out = ps.reduce(_spans([w0, w1]), 2)
    assert out["exec_enqueue_us_per_task"] == pytest.approx(0.7e6 / 2)
    assert out["dispatch_us_per_task"] == pytest.approx(1.3e6 / 2)
    assert out["host_threads_busy_mean"] == pytest.approx(1.0)


@pytest.mark.parametrize("workers,parked,want", [
    (2, [[(10.0, 11.0)], [(10.0, 12.0)]], 75.0),
    (4, [[(10.0, 11.0)], [(10.0, 12.0)], [], [(11.5, 12.0)]], 43.75)])
def test_parked_share_is_of_the_worker_threads_seen(workers, parked, want):
    threads = [{"select": [(10.0, 10.0)], "park": p} for p in parked]
    threads.append({"insert": [(10.0, 10.5)]})       # not a worker
    threads.append({"exec": [(10.0, 10.5)], "release": []})   # a manager
    out = ps.reduce(_spans(threads), 1)
    assert out["workers"] == workers
    assert out["workers_parked_share"] == pytest.approx(want)


@pytest.mark.parametrize("running,want", [
    ({"select": [(10.0, 11.0)]}, 0.0),
    ({"dispatch": [(10.5, 10.7)], "release": [(10.7, 11.5)]}, 50.0),
    ({"dispatch": [(9.0, 10.5)], "release": [(10.5, 12.0)]}, 100.0)])
def test_insert_overlap_share(running, want):
    out = ps.reduce(_spans([running, {"insert": [(10.0, 11.0)]}],
                           insert=[(10.0, 11.0)]), 1)
    assert out["insert_overlap_share"] == pytest.approx(want)


@pytest.mark.parametrize("spans,tasks", [
    (_spans([]), 4),                                          # no parsec span
    (_spans([{"dispatch": [(10.0, 11.0)]}], steps=()), 4),    # no step
    (ps.Spans(threads={"t": {"dispatch": [(1.0, 2.0)]}},
              bench=[("step", 0.0, 3.0)]), 4),                # not traced
    (_spans([{"dispatch": [(10.0, 11.0)]}]), 0)])             # no tasks
def test_nothing_to_read_is_none(spans, tasks):
    assert ps.reduce(spans, tasks) is None


def test_no_trace_and_an_old_trace_are_none(tmp_path):
    assert ps.find(str(tmp_path), "gemm_dtd_nb1024") is None
    d = tmp_path / ".benchmark_trace" / "cell" / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"")
    assert ps.find(str(tmp_path), "cell") == str(d / "host.xplane.pb")
    os.utime(d / "host.xplane.pb", (1e9, 1e9))     # the last run's file
    assert ps.find(str(tmp_path), "cell") is None
    assert ps.stages(str(tmp_path), "cell", 4) is None


# -- the route: run.py leaves the trace, the reader opens it ------------------

@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout of this module's own: other test files rehearse the same
    cells at the same time, and a cell's trace has one place per tree."""
    import shutil
    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return str(root)


def _dry_run(capsys, root, cell, trace, chips=1):
    from parsec_tpu.utils import mca_param
    # the engine a chip gets (engine_for declines a real accelerator); the
    # native one, which this CPU would get, has no Python stages to span
    mca_param.set("runtime.native_dtd", 0)
    try:
        rc = main(["--workload", cell, "--seed", "3000000019", "--seconds",
                   "0.3", "--trace", str(trace), f"--dry-run-cpu={chips}"],
                  root=root)
    finally:
        mca_param.unset("runtime.native_dtd")
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(lines[-1]), lines


@pytest.mark.parametrize("cell", DTD_CELLS)
def test_traced_dry_run_of_a_dtd_cell_prints_the_eight(
        capsys, checkout, cell):
    last, lines = _dry_run(capsys, checkout, cell, 1)
    assert last["correct"] is True and last["failed"] == 0
    got = {n[:-len(DRY_SUFFIX)]: m for n, m in last["metrics"].items()}
    assert set(STAGE_METRICS) <= set(got)
    units = {m["name"]: m["unit"] for m in MAN.bench["per_layer"]}
    for name in STAGE_METRICS:
        assert got[name]["unit"] == units[name]
        assert got[name]["value"] >= 0
    for name in ("insert_us_per_task", "dispatch_us_per_task",
                 "exec_enqueue_us_per_task", "release_us_per_task",
                 "host_threads_busy_mean"):
        assert got[name]["value"] > 0
    assert 0 <= got["workers_parked_share"]["value"] <= 100
    assert 0 <= got["insert_overlap_share"]["value"] <= 100
    # one dispatch span per task of every traced step; parsed once
    tasks = MAN.driver("dtd_gemm").ops.tiled_gemm_tasks(
        *(MAN.workload(cell)["dry"][k] for k in ("m", "n", "k", "nb")))
    stages = ps.stages(checkout, cell, tasks)
    assert stages["steps"] >= 1
    assert stages["dispatch_spans"] == tasks * stages["steps"]
    assert stages["workers"] == MAN.config("dgemm_dtd")["nb_cores"]
    assert sum(line.startswith("[program_spans]") for line in lines) == 1


def test_untraced_dry_run_prints_none_of_the_eight(capsys, checkout):
    last, lines = _dry_run(capsys, checkout, "gemm_dtd_nb1024", 0)
    assert not {n + DRY_SUFFIX for n in STAGE_METRICS} & set(last["metrics"])
    assert not any(line.startswith("[program_spans]") for line in lines)


def test_a_cell_that_bypasses_the_host_runtime_has_no_stage(
        capsys, checkout):
    last, _lines = _dry_run(capsys, checkout, "potrf_panel_n40960", 1)
    assert not {n + DRY_SUFFIX for n in STAGE_METRICS} & set(last["metrics"])
    # not for want of a trace: the trace is there and holds no parsec: span
    path = ps.find(checkout, "potrf_panel_n40960")
    assert path is not None
    spans = ps.load(path)
    assert spans.threads == {} and any(n == "step" for n, *_ in spans.bench)
    assert ps.stages(checkout, "potrf_panel_n40960", 100) is None
