"""``tasks_per_launch``: the traced steps' tasks over their ``parsec:exec``
spans. The reduction on hand-made spans (made the way
``test_benchmark_program_spans.py`` makes them), and the route on the CPU:
``--dry-run-cpu --trace 1`` of a DTD cell prints it."""

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
NAME = "tasks_per_launch"
DTD_CELLS = ["gemm_dtd_nb1024", "gemm_dtd_nb4096"]
reduce = MAN.reader(NAME).reduce


def _spans(threads, steps=((10.0, 12.0),), traced=(0.0, 100.0)):
    bench = [("traced", *traced)] + [("step", *s) for s in steps]
    return ps.Spans(threads={f"t{i}": t for i, t in enumerate(threads)},
                    bench=sorted(bench, key=lambda s: s[1]))


def _execs(n, lo=10.0, hi=12.0):
    width = (hi - lo) / n
    return [(lo + i * width, lo + (i + 0.5) * width) for i in range(n)]


def test_it_is_declared_for_the_dtd_cells_alone():
    (entry,) = [m for m in MAN.bench["per_layer"] if m["name"] == NAME]
    assert entry == MAN.bench["per_layer"][-1]
    assert entry["workloads"] == DTD_CELLS
    assert (entry["layer"], entry["source"], entry["better"],
            entry["moves"]) == ("device_layer", "program_span", "higher",
                                "step_s_p50")
    assert MAN.metric(NAME)["reader"] == NAME


@pytest.mark.parametrize("per_launch", [1, 16])
def test_an_exec_span_per_task_reads_one_and_one_per_16_reads_16(per_launch):
    tasks = 64
    launches = _execs(tasks // per_launch)
    workers = [{"dispatch": [(10.0, 12.0)], "exec": launches[0::2]},
               {"dispatch": [(10.0, 12.0)], "exec": launches[1::2]},
               {"insert": [(10.0, 11.0)]}]
    assert reduce(_spans(workers), tasks) == pytest.approx(per_launch)


def test_launches_are_counted_inside_the_traced_steps_only():
    worker = {"exec": [(9.0, 9.5),              # before the step
                       (10.0, 10.1), (11.9, 12.5),    # start inside it
                       (14.0, 15.0),            # between steps
                       (20.5, 20.6),
                       (200.5, 200.6)]}         # a step that was not traced
    spans = _spans([worker], steps=[(10.0, 12.0), (20.0, 22.0),
                                    (200.0, 201.0)])
    assert reduce(spans, 6) == pytest.approx(2 * 6 / 3)


@pytest.mark.parametrize("spans,tasks", [
    (_spans([]), 4),                                          # no parsec span
    (_spans([{"dispatch": [(10.0, 11.0)]}]), 4),              # no exec span
    (_spans([{"exec": [(10.0, 11.0)]}], steps=()), 4),        # no step
    (ps.Spans(threads={"t": {"exec": [(1.0, 2.0)]}},
              bench=[("step", 0.0, 3.0)]), 4),                # not traced
    (_spans([{"exec": [(10.0, 11.0)]}]), 0)])                 # no tasks
def test_nothing_to_read_is_none(spans, tasks):
    assert reduce(spans, tasks) is None


def test_no_trace_is_none(tmp_path, monkeypatch):
    reader = MAN.reader(NAME)
    monkeypatch.setattr(reader, "_CHECKOUT", str(tmp_path))
    record = {"cell": "gemm_dtd_nb1024", "driver": {"tasks_per_step": 4}}
    assert reader.read(record, {}) is None


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


@pytest.mark.parametrize("cell,trace", [
    ("gemm_dtd_nb1024", 1), ("gemm_dtd_nb4096", 1), ("gemm_dtd_nb1024", 0),
    ("potrf_panel_n40960", 1)])
def test_dry_run_prints_it_where_there_are_launches_to_count(
        capsys, checkout, cell, trace):
    from parsec_tpu.utils import mca_param
    # the engine a chip gets (engine_for declines a real accelerator)
    mca_param.set("runtime.native_dtd", 0)
    try:
        rc = main(["--workload", cell, "--seed", "3000000019", "--seconds",
                   "0.3", "--trace", str(trace), "--dry-run-cpu=1"],
                  root=checkout)
    finally:
        mca_param.unset("runtime.native_dtd")
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and last["correct"] is True and last["failed"] == 0
    got = last["metrics"].get(NAME + DRY_SUFFIX)
    if not trace or cell not in DTD_CELLS:
        assert got is None
        return
    tasks = MAN.driver("dtd_gemm").ops.tiled_gemm_tasks(
        *(MAN.workload(cell)["dry"][k] for k in ("m", "n", "k", "nb")))
    assert got["unit"] == "ratio" and 1.0 <= got["value"] <= tasks
    # every launch carries a whole number of tasks of one step
    stages = ps.stages(checkout, cell, tasks)
    launches = tasks * stages["steps"] / got["value"]
    assert launches == pytest.approx(round(launches))
    assert launches <= stages["dispatch_spans"] == tasks * stages["steps"]
