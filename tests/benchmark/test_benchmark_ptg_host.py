"""The ``dpotrf_ptg_host`` configuration and its cell: what was added to
``BENCHMARK.json`` for it, its plain reference against numpy at test
size, its driver's check (a wrong tile, a tile rounded to bfloat16 and
another step's matrix all fail it), the new reader on hand-made spans and
counters, and the traced rehearsal, which prints every ``ptg_`` metric.
The cell's other CPU dry runs come through ``test_benchmark_dryrun.py``'s
parametrisation."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import generate, ops  # noqa: E402
from benchmark import program_spans as ps  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.run import DRY_SUFFIX, Spans, main  # noqa: E402

MAN = Manifest(ROOT)
CELL, CONFIG = "potrf_ptg_host_n49152_nb2048", "dpotrf_ptg_host"
REF = MAN.reference("dpotrf_ptg_host_reference")
READER = MAN.reader("ptg_stage")
STAGE_TWINS = ["release_us_per_task", "select_us_per_task",
               "dispatch_us_per_task", "exec_enqueue_us_per_task",
               "host_threads_busy_mean", "workers_parked_share"]
NEW = ["ptg_host_us_per_task", "ptg_startup_share",
       "ptg_unfold_us_per_task"] + ["ptg_" + k for k in STAGE_TWINS] + [
       "ptg_tasks_per_launch", "ptg_group_cut_by_class_share",
       "ptg_tasks_on_chip_share", "ptg_device_step_s",
       "potrf_tile_roofline"]
DEVICE_TRACE = {"ptg_device_step_s", "potrf_tile_roofline"}


# -- what BENCHMARK.json gained ------

def test_the_configuration_the_cell_and_its_metrics_are_declared():
    bench = MAN.bench
    assert bench["configs"][-1]["name"] == CONFIG
    assert bench["configs"][-1]["reduced"] == []
    for word in ("zpotrf_L.jdf", "testing_dpotrf.c"):
        assert word in bench["configs"][-1]["source"]
    assert bench["workloads"][-1] == {
        "name": CELL, "config": CONFIG, "traffic": "n49152_nb2048",
        "chips": 1, "why": bench["workloads"][-1]["why"]}
    assert [m["name"] for m in bench["per_layer"][-len(NEW):]] == NEW
    twins = {m["name"]: m for m in bench["per_layer"]}
    for m in bench["per_layer"][-len(NEW):]:
        assert m["workloads"] == [CELL]
        twin = twins.get(m["name"][len("ptg_"):])
        if twin is not None and "gemm_dtd_nb1024" in twin["workloads"]:
            # layer, source and moves as its gemm_dtd_* twin
            assert {k: m[k] for k in m if k not in ("name", "workloads")} \
                == {k: twin[k] for k in twin
                    if k not in ("name", "workloads")}
    # no accepted metric's list was touched: the cell reports the metrics
    # that have no list, and its own
    mine = {m["name"] for m in MAN.metrics_for("per_layer", CELL)}
    assert mine == set(NEW) | {"plan_compile_s", "compiles_in_window",
                               "device_idle_share"}
    assert {m["name"] for m in MAN.metrics_for("end_to_end", CELL)} == {
        "tflops_per_chip", "step_s_p50", "peak_hbm_gib", "setup_s"}


def test_the_configuration_file_states_the_deployment():
    config = MAN.config(CONFIG)
    workload = MAN.workload(CELL)
    assert workload["traffic"] == {"n": 49152, "nb": 2048}
    assert workload["dry"] == {"n": 512, "nb": 64}
    assert config["sizes"] == {"nb": 2048, "dtype": "float32"}
    assert config["knobs"] == {"potrf.trsm_hook": "gemm"}
    assert config["nb_cores"] == 4
    assert config["taskpool"].endswith(":build_potrf")
    assert (config["ops"], config["min_bytes"]) == ("potrf_ops",
                                                    "potrf_min_bytes")
    assert "bfloat16" in config["correct"]["reason"]
    for word in ("chip", "once", "fresh", "own storage"):
        assert word in config["guarantees"]
    # the traffic as ISSUE 27 names it
    nt = 49152 // 2048
    d = _driver({"n": 49152, "nb": 2048})
    assert d.tasks_per_step == 2600 == nt + nt * (nt - 1) + \
        nt * (nt - 1) * (nt - 2) // 6
    assert len(d.lower) == 300 and len(d.lower) * 2048 * 2048 * 4 > 4.6 * 2**30
    assert d.ops_per_step == pytest.approx(39.6e12, rel=2e-3)


# -- the plain reference ------

def _residual(key, n, nb, tiles):
    """The reference's blocked probe over a dict of lower tiles."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        x = REF.probe_vectors(key, n)
        y, z, y2 = (jnp.zeros_like(x) for _ in range(3))
        for j in range(n // nb):
            y = REF.probe_input_row(j, key, x, y, n=n, nb=nb)
        for (i, j), t in tiles.items():
            z = REF.probe_factor_t(i, j, t, x, z)
        for (i, j), t in tiles.items():
            y2 = REF.probe_factor(i, j, t, z, y2)
        return REF.residual(y, y2), np.asarray(y), np.asarray(x)


def test_the_reference_agrees_with_numpy_and_the_panel_cells_matrix():
    import jax.numpy as jnp
    n, nb = 128, 32
    key = generate.step_key(5, 2)
    a0 = REF.dense_a0(key, n, nb)
    # dpotrf_panel's matrix for the same seed
    panel = MAN.reference("dpotrf_panel_reference").dense_a0(key, n, nb)
    assert a0.dtype == np.float64 and np.array_equal(a0, panel)
    assert np.array_equal(a0, a0.T)
    want = np.linalg.cholesky(a0)
    # the blocked probe: A0 x as numpy gives it, a small residual for the
    # factor, a large one for a factor rounded to bfloat16, and whatever a
    # diagonal tile holds above its diagonal is ignored
    lower = [(i, j) for j in range(n // nb) for i in range(j, n // nb)]
    tiles = {(i, j): jnp.asarray(want[i * nb:(i + 1) * nb,
                                      j * nb:(j + 1) * nb], jnp.float32)
             for i, j in lower}
    err, y, x = _residual(key, n, nb, tiles)
    np.testing.assert_allclose(y, a0 @ x, rtol=1e-5, atol=1e-4)
    assert err < 1e-6
    junk = dict(tiles)
    junk[1, 1] = tiles[1, 1] + jnp.triu(jnp.ones((nb, nb)), 1)
    assert _residual(key, n, nb, junk)[0] == err
    rounded = {k: t.astype(jnp.bfloat16).astype(jnp.float32)
               for k, t in tiles.items()}
    limit = MAN.config(CONFIG)["correct"]["limit"]
    assert _residual(key, n, nb, rounded)[0] > 10 * limit


# -- the driver ------

def _driver(sizes, seed=5):
    import jax
    config = MAN.config(CONFIG)
    return MAN.driver(config["driver"]).build(
        config, {**config["sizes"], **sizes}, seed, jax.devices()[:1],
        Spans(), REF)


def test_the_factor_agrees_with_numpy_cholesky_and_a_wrong_tile_fails():
    import jax.numpy as jnp
    n, nb = 128, 32
    d = _driver({"n": n, "nb": nb})
    try:
        d.setup()
        a = d.generate(2)
        a0 = REF.dense_a0(generate.step_key(5, 2), n, nb)
        # the lower triangle alone is stored; it is the reference's A0
        assert sorted(a._tiles) == sorted(d.lower)
        for i, j in d.lower:
            t = a.data_of((i, j))
            assert t.committed
            assert np.array_equal(
                np.asarray(t), a0[i * nb:(i + 1) * nb,
                                  j * nb:(j + 1) * nb].astype(np.float32))
        before = d.counters()["tasks_by_module"]
        a = d.step(a)
        after = d.counters()["tasks_by_module"]
        assert d.tasks_per_step == 4 + 6 + 6 + 4
        assert after["tpu0"] - before["tpu0"] == 20 == sum(after.values())
        assert d.ops_per_step == ops.potrf_ops(n)
        assert d.bytes_per_step == ops.potrf_min_bytes(n, 4)
        ok, detail = d.check(a, 2)
        assert ok and detail["residual"] < 1e-6 and detail["factor_on_chip"]
        assert sorted(a._tiles) == sorted(d.lower)
        want = np.linalg.cholesky(a0)
        for i, j in d.lower:
            got = np.asarray(a.data_of((i, j)))
            ref = want[i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]
            assert np.abs((np.tril(got) if i == j else got) - ref).max() \
                <= 1e-4 * np.abs(want).max()
        # the check is of THIS step's input: another step's key fails it
        assert not d.check(a, 3)[0]
        good = a.data_of((2, 1))
        # one tile of 10 rounded to bfloat16 fails the limit
        a.write_tile((2, 1), good.astype(jnp.bfloat16).astype(jnp.float32))
        ok, detail = d.check(a, 2)
        assert not ok and detail["residual"] > detail["limit"]
        a.write_tile((2, 1), good + 0.5)
        assert not d.check(a, 2)[0]
        a.write_tile((2, 1), np.asarray(good))      # right, but on the host
        ok, detail = d.check(a, 2)
        assert not ok and not detail["factor_on_chip"]
        a.write_tile((2, 1), jnp.full((nb, nb), jnp.nan))
        assert not d.finite(a)
        # a step whose tasks were not all counted on the chip's module
        a.write_tile((2, 1), good)
        assert d.check(a, 2)[0]
        d.steps_run += 1
        ok, detail = d.check(a, 2)
        assert not ok and detail["tasks_on_chip"] == 20 and \
            detail["tasks_of_the_steps"] == 40
    finally:
        d.close()


def test_the_storage_guarantee_stops_a_program_that_holds_tiles_twice(
        monkeypatch):
    n, nb = 128, 32
    d = _driver({"n": n, "nb": nb})
    stored = len(d.lower) * nb * nb * 4
    limit = MAN.config(CONFIG)["storage"]["peak_over_stored_limit"]
    # under what a second copy of the trailing tiles reads, over what a
    # block column and the workers' tasks in flight do
    assert 1.2 <= limit <= 1.5
    assert d.storage_limit_bytes == pytest.approx(limit * stored)
    peak = [int(1.1 * stored)]
    try:
        d.setup()
        assert d._peak_bytes() == 0         # the CPU keeps no such count
        monkeypatch.setattr(d, "_peak_bytes", lambda: peak[0])
        a = d.step(d.generate(0))           # the warm step, within it
        ok, detail = d.check(a, 0)
        assert ok and detail["peak_bytes"] == peak[0] < \
            detail["storage_limit_bytes"]
        # the updated tiles held beside the matrix: the window's check
        # fails, and at the warm step the run ends
        peak[0] = int(1.7 * stored)
        ok, detail = d.check(a, 0)
        assert not ok and detail["residual"] <= detail["limit"]
        d.steps_run = 0
        with pytest.raises(RuntimeError, match="own storage"):
            d.step(d.generate(0))
    finally:
        d.close()


# -- the new reader ------

def _spans(threads, steps=((10.0, 12.0),), traced=(0.0, 100.0)):
    bench = [("traced", *traced)] + [("step", *s) for s in steps]
    return ps.Spans(threads={f"t{i}": t for i, t in enumerate(threads)},
                    bench=sorted(bench, key=lambda s: s[1]))


def test_span_seconds_sums_a_span_inside_the_traced_steps():
    reduce = READER.span_seconds
    spans = _spans(
        [{"ptg_startup": [(10.0, 10.2), (20.0, 20.1), (30.0, 30.5)]},
         {"release": [(10.0, 12.0)],
          "ptg_unfold": [(9.9, 10.1), (11.0, 11.2), (11.9, 12.3),
                         (21.0, 21.1)]}],
        steps=[(10.0, 12.0), (20.0, 22.0), (200.0, 201.0)])
    # two traced steps of 2 s; the span at 30 s is in no step
    assert reduce(spans, "ptg_startup", "step_time", 4) == pytest.approx(
        100.0 * 0.3 / 4.0)
    # clipped to the steps: 0.1 + 0.2 + 0.1 + 0.1 s over 2 x 4 tasks
    assert reduce(spans, "ptg_unfold", "task", 4) == pytest.approx(
        1e6 * 0.5 / 8)
    with pytest.raises(ValueError):
        reduce(spans, "ptg_unfold", "hour", 4)
    for name, span, per in (
            ("ptg_startup_share", "ptg_startup", "step_time"),
            ("ptg_unfold_us_per_task", "ptg_unfold", "task")):
        spec = MAN.metric(name)
        assert spec["reader"] == "ptg_stage"
        assert spec["params"] == {"span": span, "per": per}


@pytest.mark.parametrize("spans,tasks", [
    # a program without the two spans (the parent): None, not 0
    (_spans([{"release": [(10.0, 11.0)], "dispatch": [(10.0, 10.5)]}]), 4),
    (_spans([{"ptg_unfold": [(1.0, 2.0)]}], steps=()), 4),      # no step
    (ps.Spans(threads={"t": {"ptg_unfold": [(1.0, 2.0)]}},
              bench=[("step", 0.0, 3.0)]), 4),                  # not traced
    (_spans([{"ptg_unfold": [(10.0, 11.0)]}]), 0)])             # no tasks
def test_a_trace_without_the_span_reads_none(spans, tasks):
    assert READER.span_seconds(spans, "ptg_unfold", "task", tasks) is None


def test_without_a_trace_every_span_figure_is_none(tmp_path, monkeypatch):
    monkeypatch.setattr(READER, "_CHECKOUT", str(tmp_path))
    record = {"cell": CELL, "driver": {"tasks_per_step": 20}}
    assert READER.read(record, {"span": "ptg_unfold", "per": "task"}) is None
    assert READER.read(record, {"stage": "release_us_per_task"}) is None


def test_counter_share_reads_what_the_driver_left_in_the_record():
    cut = MAN.metric("ptg_group_cut_by_class_share")["params"]
    assert cut == {"over": ["group_end_class"], "under": ["group_end_*"]}
    window = {"tasks.GEMM": 1000, "launches.GEMM": 200,
              "group_end_limit": 50, "group_end_empty": 20,
              "group_end_class": 120, "group_end_sig": 0}

    def record(counters):
        return {"setup": {"context_s": 0.1, "program_counters": counters}}

    assert READER.read(record(window), cut) == pytest.approx(
        100.0 * 120 / (50 + 20 + 120))
    assert READER.counter_share(window, ["tasks.GEMM"], ["launches.*"]) == \
        pytest.approx(100.0 * 1000 / 200)
    # a program without the counters (the parent) leaves the dict empty;
    # counters that never moved (an untraced window) give no divisor; a
    # driver that leaves none has no such key
    assert READER.read(record({}), cut) is None
    assert READER.read(record(dict.fromkeys(window, 0)), cut) is None
    assert READER.read({"setup": {}}, cut) is None


def test_the_driver_leaves_the_windows_counters_in_what_setup_returned():
    d = _driver({"n": 128, "nb": 32})
    try:
        facts = d.setup()
        assert facts["program_counters"] == {}
        d.step(d.generate(0))               # the warm step: not counted
        d.ctx.set_stage_timers(True)        # as a profiler session does
        first = d.counters()
        assert facts["program_counters"] == {}
        d.step(d.generate(1))
        d.step(d.generate(2))
        second = d.counters()
        assert second["tasks_by_module"]["tpu0"] - \
            first["tasks_by_module"]["tpu0"] == 2 * d.tasks_per_step
        window = facts["program_counters"]
        assert {k: n for k, n in window.items() if k.startswith("tasks.")} \
            == {"tasks.POTRF": 8, "tasks.TRSM": 12, "tasks.SYRK": 12,
                "tasks.GEMM": 8}
        assert sum(n for k, n in window.items()
                   if k.startswith("group_end_")) > 0
        share = READER.read({"setup": facts}, MAN.metric(
            "ptg_group_cut_by_class_share")["params"])
        assert 0 <= share <= 100
    finally:
        d.close()


def test_the_twins_read_what_the_entries_they_copy_read():
    for twin, original in (
            ("ptg_host_us_per_task", "host_us_per_task"),
            ("ptg_tasks_on_chip_share", "tasks_on_chip_share"),
            ("ptg_tasks_per_launch", "tasks_per_launch"),
            ("ptg_device_step_s", "device_step_s"),
            ("potrf_tile_roofline", "potrf_panel_roofline")):
        a, b = MAN.metric(twin), MAN.metric(original)
        assert (a["reader"], a.get("params")) == (b["reader"],
                                                  b.get("params"))
    # the stage figures go through ptg_stage (the accepted benchmark's
    # tests count the metrics that name `program_stage`) and are
    # program_spans.reduce's own
    for key in STAGE_TWINS:
        assert MAN.metric("ptg_" + key)["reader"] == "ptg_stage"
        assert MAN.metric("ptg_" + key)["params"] == {"stage": key}
        assert MAN.metric(key)["params"] == {"key": key}


def test_the_roofline_counts_are_the_algorithms():
    """``potrf_tile_roofline`` divides the least time for the algorithm's
    operations and bytes by the device time of a step: 0.201 s at
    N=49152 on the v5e's peaks, compute-bound."""
    n = MAN.workload(CELL)["traffic"]["n"]
    peaks = MAN.peaks("TPU v5 lite")
    least, bound = ops.roofline_seconds(
        ops.potrf_ops(n), ops.potrf_min_bytes(n, 4),
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
    assert bound == "compute" and least == pytest.approx(0.201, rel=2e-3)
    record = {"trace": {"device_step_s": 0.40}, "peaks": peaks, "chips": 1,
              "driver": {"ops_per_step": ops.potrf_ops(n),
                         "bytes_per_step": ops.potrf_min_bytes(n, 4)}}
    share = MAN.reader("roofline").read(record, {})
    assert share == pytest.approx(100.0 * least / 0.40) and share < 100


# -- the route: run.py leaves the trace, the readers open it ------

@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout of this module's own: other test files rehearse the same
    cell at the same time, and a cell's trace has one place per tree."""
    import shutil
    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return str(root)


def _dry_run(capsys, root, trace):
    rc = main(["--workload", CELL, "--seed", "3000000019", "--seconds",
               "0.5", "--trace", str(trace), "--dry-run-cpu=1"], root=root)
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(lines[-1]), lines


def test_the_traced_rehearsal_prints_every_ptg_metric(capsys, checkout):
    last, lines = _dry_run(capsys, checkout, 1)
    assert last["correct"] is True and last["failed"] == 0
    got = {n[:-len(DRY_SUFFIX)]: m for n, m in last["metrics"].items()}
    # all but the two that read the device's plane of the trace
    assert set(NEW) - DEVICE_TRACE <= set(got)
    assert not DEVICE_TRACE & set(got)
    units = {m["name"]: m["unit"] for m in MAN.bench["per_layer"]}
    for name in set(NEW) - DEVICE_TRACE:
        assert got[name]["unit"] == units[name] and got[name]["value"] >= 0
    assert got["compiles_in_window"]["value"] == 0
    # the rehearsal takes the chip's path: every task on the device module
    assert got["ptg_tasks_on_chip_share"]["value"] == 100.0
    assert 0 < got["ptg_unfold_us_per_task"]["value"] <= \
        got["ptg_release_us_per_task"]["value"]
    assert 0 < got["ptg_startup_share"]["value"] < 100
    assert 0 <= got["ptg_group_cut_by_class_share"]["value"] <= 100
    tasks = 8 + 28 + 28 + 56
    assert 1.0 <= got["ptg_tasks_per_launch"]["value"] <= tasks
    # a span a task: one dispatch, one release with its unfold inside
    stages = ps.stages(checkout, CELL, tasks)
    assert stages["dispatch_spans"] == tasks * stages["steps"]
    spans = ps.load(ps.find(checkout, CELL))
    assert sum(len(t.get("ptg_startup", ())) for t in
               spans.threads.values()) >= stages["steps"]
    # the program's counters reached the [window] line by class
    window = [line for line in lines if line.startswith("[window]")][0]
    for cls in ("POTRF", "TRSM", "SYRK", "GEMM"):
        assert f"'tasks.{cls}'" in window and f"'launches.{cls}'" in window


def test_the_untraced_rehearsal_prints_no_ptg_metric(capsys, checkout):
    last, _lines = _dry_run(capsys, checkout, 0)
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {
        n + DRY_SUFFIX for n in ("tflops_per_chip", "step_s_p50", "setup_s")}
