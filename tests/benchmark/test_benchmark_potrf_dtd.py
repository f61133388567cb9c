"""The ``dpotrf_dtd`` configuration and its cell: what was appended to
``BENCHMARK.json`` for them, that every new name resolves (no reader was
added: each metric names one that is there), the plain reference against
numpy at test size, the driver's check (a wrong tile, a tile rounded to
bfloat16, a broken storage guarantee and another step's matrix all fail
it), the front end's counters in the dict ``setup()`` returned, and the
rehearsals, which print every new metric under its ``_cpu_dryrun`` name.
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
CELL, CONFIG = "potrf_dtd_n49152_nb2048", "dpotrf_dtd"
TWIN_CELL, TWIN_CONFIG = "potrf_ptg_host_n49152_nb2048", "dpotrf_ptg_host"
REF = MAN.reference("dpotrf_dtd_reference")
# name -> (unit, better, source, layer, moves, reader, params)
NEW = {
    "pdtd_host_us_per_task": ("us", "lower", "host_clock", "host_runtime",
                              "step_s_p50", "host_us_per_task", None),
    "pdtd_insert_us_per_task": (
        "us", "lower", "program_span", "host_runtime", "step_s_p50",
        "ptg_stage", {"stage": "insert_us_per_task"}),
    "pdtd_insert_overlap_share": (
        "%", "higher", "program_span", "host_runtime", "step_s_p50",
        "ptg_stage", {"stage": "insert_overlap_share"}),
    "pdtd_flush_us_per_task": (
        "us", "lower", "program_span", "host_runtime", "step_s_p50",
        "ptg_stage", {"span": "dtd_flush", "per": "task"}),
    "pdtd_args_linked_share": (
        "%", "higher", "program_counter", "host_runtime", "step_s_p50",
        "ptg_stage", {"over": ["dtd_args_linked"],
                      "under": ["dtd_args_linked", "dtd_args_snapshot"]}),
    "pdtd_tasks_per_launch": ("ratio", "higher", "program_span",
                              "device_layer", "step_s_p50",
                              "tasks_per_launch", None),
    "pdtd_device_step_s": ("s", "lower", "device_trace", "kernels",
                           "step_s_p50", "device_step_s", None),
    "pdtd_tile_roofline": ("%", "higher", "device_trace", "kernels",
                           "tflops_per_chip", "roofline", None),
    "pdtd_tasks_on_chip_share": ("%", "higher", "program_counter",
                                 "device_layer", "tflops_per_chip",
                                 "tasks_on_chip_share", None),
}
DEVICE_TRACE = {"pdtd_device_step_s", "pdtd_tile_roofline"}


# -- what BENCHMARK.json gained, and that it resolves ------

def test_one_configuration_one_cell_and_nine_metrics_are_appended():
    bench = MAN.bench
    assert bench["configs"][-1] == {
        "name": CONFIG, "source": bench["configs"][-1]["source"],
        "file": "benchmark/configs/dpotrf_dtd.json", "reduced": [],
        "why": bench["configs"][-1]["why"]}
    source = bench["configs"][-1]["source"]
    assert "dplasma/blob/master/tests/testing_zpotrf_dtd.c" in source
    assert "-N <n> -t <NB>" in source and len(source) <= 200
    assert source == MAN.config(CONFIG)["source"]
    assert bench["workloads"][-1] == {
        "name": CELL, "config": CONFIG, "traffic": "n49152_nb2048",
        "chips": 1, "why": bench["workloads"][-1]["why"]}
    assert [m["name"] for m in bench["per_layer"][-len(NEW):]] == list(NEW)
    for m in bench["per_layer"][-len(NEW):]:
        unit, better, source, layer, moves, _, _ = NEW[m["name"]]
        assert m == {"name": m["name"], "unit": unit, "better": better,
                     "source": source, "layer": layer, "moves": moves,
                     "workloads": [CELL]}
    # no accepted entry's list was touched: the cell reports the metrics
    # that have no list, and its own
    assert {m["name"] for m in MAN.metrics_for("per_layer", CELL)} == \
        set(NEW) | {"plan_compile_s", "compiles_in_window",
                    "device_idle_share"}
    assert {m["name"] for m in MAN.metrics_for("end_to_end", CELL)} == {
        "tflops_per_chip", "step_s_p50", "peak_hbm_gib", "setup_s"}
    assert not any(CELL in m.get("workloads", ())
                   for m in bench["per_layer"][:-len(NEW)])


@pytest.mark.parametrize("name", list(NEW))
def test_every_new_metric_resolves_to_a_reader_that_was_there(name):
    spec = MAN.metric(name)
    *_, reader, params = NEW[name]
    assert spec["name"] == name and spec["what"]
    assert (spec["reader"], spec.get("params")) == (reader, params)
    assert callable(MAN.reader(reader).read)
    # layer, source and moves as the entry of the accepted cell it copies
    twins = {"pdtd_host_us_per_task": "ptg_host_us_per_task",
             "pdtd_insert_us_per_task": "insert_us_per_task",
             "pdtd_insert_overlap_share": "insert_overlap_share",
             "pdtd_tasks_per_launch": "ptg_tasks_per_launch",
             "pdtd_device_step_s": "ptg_device_step_s",
             "pdtd_tile_roofline": "potrf_tile_roofline",
             "pdtd_tasks_on_chip_share": "ptg_tasks_on_chip_share"}
    if name in twins:
        mine, its = (MAN._entry("per_layer", n) for n in (name, twins[name]))
        assert {k: v for k, v in mine.items()
                if k not in ("name", "workloads")} == \
            {k: v for k, v in its.items() if k not in ("name", "workloads")}


def test_the_configuration_file_states_the_deployment():
    config, twin = MAN.config(CONFIG), MAN.config(TWIN_CONFIG)
    workload = MAN.workload(CELL)
    assert workload["traffic"] == MAN.workload(TWIN_CELL)["traffic"] == \
        {"n": 49152, "nb": 2048}
    assert workload["dry"] == {"n": 512, "nb": 64}
    # the PTG deployment's sizes, knobs, counts and storage limit: the two
    # cells differ by the front end alone
    for key in ("sizes", "knobs", "nb_cores", "ops", "min_bytes", "reduced"):
        assert config[key] == twin[key], key
    assert config["storage"]["peak_over_stored_limit"] == \
        twin["storage"]["peak_over_stored_limit"] == 1.35
    assert config["knobs"] == {"potrf.trsm_hook": "gemm"}
    assert config["taskpool"].endswith("potrf:insert_potrf_dtd")
    assert (config["driver"], config["reference"]) == (
        "dtd_factorization", "dpotrf_dtd_reference")
    assert "bfloat16" in config["correct"]["reason"]
    for word in ("chip", "once", "fresh", "own storage", "own tiles"):
        assert word in config["guarantees"]
    for word in ("data_flush", "insert_tasks", "stacked", "AFFINITY",
                 "Priorities are the tester", "non-blocking"):
        assert word in config["deployment"], word
    assert set(config["assumed"]) >= {"nb", "nb_cores", "precision",
                                      "window", "insertion", "tester"}
    nt = 49152 // 2048
    d = _driver({"n": 49152, "nb": 2048})
    assert d.tasks_per_step == 2600 == nt + nt * (nt - 1) + \
        nt * (nt - 1) * (nt - 2) // 6
    assert len(d.lower) == 300 and \
        len(d.lower) * 2048 * 2048 * 4 > 0.25 * 16e9
    assert d.ops_per_step == pytest.approx(39.6e12, rel=2e-3)
    assert d.storage_limit_bytes == pytest.approx(1.35 * 4.6875 * 2 ** 30)


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


def test_the_reference_agrees_with_numpy_and_is_the_ptg_cells_matrix():
    import jax.numpy as jnp
    n, nb = 128, 32
    key = generate.step_key(5, 2)
    a0 = REF.dense_a0(key, n, nb)
    twin = MAN.reference("dpotrf_ptg_host_reference").dense_a0(key, n, nb)
    assert a0.dtype == np.float64 and np.array_equal(a0, twin)
    assert np.array_equal(a0, a0.T)
    want = np.linalg.cholesky(a0)
    lower = [(i, j) for j in range(n // nb) for i in range(j, n // nb)]
    tiles = {(i, j): jnp.asarray(want[i * nb:(i + 1) * nb,
                                      j * nb:(j + 1) * nb], jnp.float32)
             for i, j in lower}
    err, y, x = _residual(key, n, nb, tiles)
    np.testing.assert_allclose(y, a0 @ x, rtol=1e-5, atol=1e-4)
    assert err < 1e-6
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
    from parsec_tpu.utils import mca_param
    n, nb = 128, 32
    d = _driver({"n": n, "nb": nb})
    asked = mca_param.override_of("runtime.native_dtd")
    try:
        d.setup()
        a = d.generate(2)
        a0 = REF.dense_a0(generate.step_key(5, 2), n, nb)
        assert sorted(a._tiles) == sorted(d.lower)
        before = d.counters()["tasks_by_module"]
        a = d.step(a)
        after = d.counters()
        # a rehearsal takes the chip's path: the engine a chip gets, every
        # task on the device module
        assert after["engine"] == d.engine == "python"
        assert d.tasks_per_step == 4 + 6 + 6 + 4
        assert after["tasks_by_module"]["tpu0"] - before["tpu0"] == 20 == \
            sum(after["tasks_by_module"].values())
        assert d.ops_per_step == ops.potrf_ops(n)
        assert d.bytes_per_step == ops.potrf_min_bytes(n, 4)
        ok, detail = d.check(a, 2)
        assert ok and detail["residual"] < 1e-6 and detail["factor_on_chip"]
        assert detail["engine"] == "python"
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
    assert mca_param.override_of("runtime.native_dtd") == asked


def test_the_storage_guarantee_stops_a_program_that_holds_tiles_twice(
        monkeypatch):
    n, nb = 128, 32
    d = _driver({"n": n, "nb": nb})
    stored = len(d.lower) * nb * nb * 4
    peak = [int(1.1 * stored)]
    try:
        d.setup()
        monkeypatch.setattr(d, "_peak_bytes", lambda: peak[0])
        a = d.step(d.generate(0))           # the warm step, within it
        ok, detail = d.check(a, 0)
        assert ok and detail["peak_bytes"] == peak[0] < \
            detail["storage_limit_bytes"]
        peak[0] = int(1.7 * stored)
        ok, detail = d.check(a, 0)
        assert not ok and detail["residual"] <= detail["limit"]
        d.steps_run = 0
        with pytest.raises(RuntimeError, match="own storage"):
            d.step(d.generate(0))
    finally:
        d.close()


def test_a_program_without_the_loop_stops_before_it_starts_a_context(
        monkeypatch):
    """The parent commit under this PR's benchmark files: the cell exits
    at once, with nothing started that could hang."""
    import parsec_tpu
    from parsec_tpu.algorithms import potrf
    monkeypatch.delattr(potrf, "insert_potrf_dtd")
    monkeypatch.setattr(parsec_tpu, "init", lambda **kw: pytest.fail(
        "a Context was started"))
    d = _driver({"n": 128, "nb": 32})
    try:
        with pytest.raises(AttributeError, match="insert_potrf_dtd"):
            d.setup()
    finally:
        d.close()


def test_the_driver_leaves_the_front_ends_counters_in_what_setup_returned():
    d = _driver({"n": 128, "nb": 32})
    reader = MAN.reader("ptg_stage")
    try:
        facts = d.setup()
        assert facts["program_counters"] == {}
        d.step(d.generate(0))               # the warm step: not counted
        d.ctx.set_stage_timers(True)        # as a profiler session does
        first = d.counters()
        assert facts["program_counters"] == {}
        assert not any(k.startswith("dtd_")
                       for k in first["program_counters"])
        d.step(d.generate(1))
        d.step(d.generate(2))
        second = d.counters()
        assert second["tasks_by_module"]["tpu0"] - \
            first["tasks_by_module"]["tpu0"] == 2 * d.tasks_per_step
        window = facts["program_counters"]
        assert {k: n for k, n in window.items() if k.startswith("tasks.")} \
            == {"tasks._potrf_dtd_potrf": 8, "tasks._potrf_dtd_trsm": 12,
                "tasks._potrf_dtd_syrk": 12, "tasks._potrf_dtd_gemm": 8}
        # 4 + 2 x 6 + 2 x 6 + 3 x 4 tile arguments a step
        assert window["dtd_args_linked"] + window["dtd_args_snapshot"] == \
            2 * 40
        assert window["dtd_tiles_flushed"] == 2 * 10
        assert 1 <= window["dtd_tiles_tracked_peak"] <= 10
        assert second["program_counters"]["dtd_args_linked"] == \
            window["dtd_args_linked"]
        share = reader.read({"setup": facts},
                            MAN.metric("pdtd_args_linked_share")["params"])
        assert share == pytest.approx(
            100.0 * window["dtd_args_linked"] / 80) and 0 < share < 100
    finally:
        d.close()


def test_the_roofline_counts_are_the_algorithms():
    n = MAN.workload(CELL)["traffic"]["n"]
    peaks = MAN.peaks("TPU v5 lite")
    least, bound = ops.roofline_seconds(
        ops.potrf_ops(n), ops.potrf_min_bytes(n, 4),
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
    assert bound == "compute" and least == pytest.approx(0.201, rel=2e-3)
    record = {"trace": {"device_step_s": 0.43}, "peaks": peaks, "chips": 1,
              "driver": {"ops_per_step": ops.potrf_ops(n),
                         "bytes_per_step": ops.potrf_min_bytes(n, 4)}}
    spec = MAN.metric("pdtd_tile_roofline")
    share = MAN.reader(spec["reader"]).read(record, spec.get("params", {}))
    assert share == pytest.approx(100.0 * least / 0.43) and share < 100


# -- the rehearsals ------

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


def _dry_run(capsys, root, trace, seconds="1.0"):
    rc = main(["--workload", CELL, "--seed", "3000000019", "--seconds",
               seconds, "--trace", str(trace), "--dry-run-cpu=1"], root=root)
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(lines[-1]), lines


def test_the_traced_rehearsal_prints_every_new_metric(capsys, checkout):
    last, lines = _dry_run(capsys, checkout, 1)
    if not any(line.startswith("[program_spans]") for line in lines):
        # a loaded machine: no two steps inside the traced part of a
        # 1 s window (the accepted cells' rehearsals know the same)
        last, lines = _dry_run(capsys, checkout, 1, seconds="4.0")
    assert last["correct"] is True and last["failed"] == 0
    got = {n[:-len(DRY_SUFFIX)]: m for n, m in last["metrics"].items()}
    assert all(n.endswith(DRY_SUFFIX) for n in last["metrics"])
    # all but the two that read the device's plane of the trace
    assert set(NEW) - DEVICE_TRACE <= set(got)
    assert not DEVICE_TRACE & set(got)
    for name in set(NEW) - DEVICE_TRACE:
        assert got[name]["unit"] == NEW[name][0]
        assert got[name]["value"] is not None and got[name]["value"] >= 0
    assert got["compiles_in_window"]["value"] == 0
    assert got["pdtd_tasks_on_chip_share"]["value"] == 100.0
    assert got["pdtd_insert_us_per_task"]["value"] > 0
    assert 0 < got["pdtd_flush_us_per_task"]["value"] < \
        got["pdtd_insert_us_per_task"]["value"]
    assert 0 < got["pdtd_args_linked_share"]["value"] < 100
    assert 0 <= got["pdtd_insert_overlap_share"]["value"] <= 100
    tasks = 8 + 28 + 28 + 56
    assert 1.0 <= got["pdtd_tasks_per_launch"]["value"] <= tasks
    # one flush span a call (36 tiles and the flush_all), one insert span
    # a call of the loop, a step
    stages = ps.stages(checkout, CELL, tasks)
    spans = ps.load(ps.find(checkout, CELL))

    def count(name):
        return sum(len(t.get(name, ())) for t in spans.threads.values())

    assert count("dtd_flush") >= 36 * stages["steps"]
    assert count("insert") >= (8 + 7 + 28 + 21) * stages["steps"]
    # the front end's counters reached the [window] line, and the engine
    window = [line for line in lines if line.startswith("[window]")][0]
    for name in ("dtd_args_linked", "dtd_args_snapshot",
                 "dtd_tiles_tracked_peak", "dtd_tiles_flushed",
                 "tasks._potrf_dtd_trsm", "launches._potrf_dtd_trsm"):
        assert f"'{name}'" in window
    check = [line for line in lines if line.startswith("[check]")][0]
    assert "engine=python" in check and "tasks_on_chip=" in check


def test_the_untraced_rehearsal_prints_the_end_to_end_metrics(
        capsys, checkout):
    last, _lines = _dry_run(capsys, checkout, 0, seconds="0.5")
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {
        n + DRY_SUFFIX for n in ("tflops_per_chip", "step_s_p50", "setup_s")}
