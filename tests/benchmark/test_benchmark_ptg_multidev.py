"""The ``dpotrf_ptg_multidev`` configuration and its four-chip cell: what
was added to ``BENCHMARK.json`` for them (found by name, never by
position, so that what a later PR appends turns nothing here red), that
every new name resolves, the counts of the graph over a 2 x 2 grid, the
new reader on hand-made records, the driver's check (a wrong tile, a tile
on another chip than it is advised to and a broken storage guarantee all
fail it) and the rehearsal on four virtual devices, which prints every
new metric a CPU can read under its ``_cpu_dryrun`` name."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import ops, ops_multidev  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.run import DRY_SUFFIX, Spans, main  # noqa: E402

MAN = Manifest(ROOT)
CELL, CONFIG = "potrf_ptg_multidev_n98304_nb4096_x4", "dpotrf_ptg_multidev"
READER = MAN.reader("multidev")
# name -> (unit, better, source, layer, moves, reader, params)
NEW = {
    "md_host_us_per_task": ("us", "lower", "host_clock", "host_runtime",
                            "step_s_p50", "host_us_per_task", None),
    "md_tasks_on_chip_share": ("%", "higher", "program_counter",
                               "device_layer", "tflops_per_chip",
                               "tasks_on_chip_share", None),
    "md_device_step_s": ("s", "lower", "device_trace", "kernels",
                         "step_s_p50", "multidev_trace",
                         {"key": "device_step_s"}),
    "md_tile_roofline": ("%", "higher", "device_trace", "kernels",
                         "tflops_per_chip", "multidev_trace",
                         {"key": "tile_roofline"}),
    "md_busy_max_over_min": ("ratio", "lower", "device_trace", "data_plane",
                             "tflops_per_chip", "multidev_trace",
                             {"key": "busy_max_over_min"}),
    "md_owner_hit_share": ("%", "higher", "program_counter", "device_layer",
                           "tflops_per_chip", "ptg_stage",
                           {"over": ["tasks_on_advised"],
                            "under": ["tasks_advised"]}),
    "md_remote_gib_per_step": ("GiB", "lower", "program_counter",
                               "data_plane", "step_s_p50", "multidev",
                               {"key": "remote_gib_per_step"}),
    "md_remote_bytes_over_min": ("ratio", "lower", "program_counter",
                                 "data_plane", "step_s_p50", "multidev",
                                 {"key": "remote_bytes_over_min"}),
    "md_remote_reuse_share": ("%", "higher", "program_counter",
                              "data_plane", "step_s_p50", "multidev",
                              {"key": "remote_reuse_share"}),
    "md_stage_in_us_per_task": ("us", "lower", "program_span",
                                "device_layer", "step_s_p50", "ptg_stage",
                                {"span": "stage_in", "per": "task"}),
    "md_ici_peak_share": ("%", "lower", "device_trace", "data_plane",
                          "step_s_p50", "multidev",
                          {"key": "ici_peak_share"}),
}
assert len(NEW) == 11
DEVICE_TRACE = {n for n, spec in NEW.items() if spec[2] == "device_trace"}


def _named(section, name):
    (entry,) = [e for e in MAN.bench[section] if e["name"] == name]
    return entry


# -- what BENCHMARK.json gained, and that it resolves -----------------------

def test_the_configuration_and_the_cell_are_found_by_name():
    config = _named("configs", CONFIG)
    assert config == {
        "name": CONFIG, "source": config["source"],
        "file": "benchmark/configs/dpotrf_ptg_multidev.json",
        "reduced": [], "why": config["why"]}
    source = config["source"]
    assert "dplasma/blob/master/tests/testing_zpotrf.c" in source
    assert "-g 4" in source and "zpotrf_L.jdf" in source
    assert "dplasma_advise_data_on_device" in source and "2D" in source
    assert len(source) <= 200 and len(config["why"]) <= 200
    assert source == MAN.config(CONFIG)["source"]
    cell = _named("workloads", CELL)
    assert cell == {"name": CELL, "config": CONFIG,
                    "traffic": "n98304_nb4096", "chips": 4,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200
    assert [w["name"] for w in MAN.bench["workloads"]
            if w["config"] == CONFIG] == [CELL]
    # a quarter of the cells, rounded down, may take four chips
    cells = MAN.bench["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metric_is_declared_for_the_new_cell_alone(name):
    unit, better, source, layer, moves, _r, _p = NEW[name]
    assert _named("per_layer", name) == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": moves, "workloads": [CELL]}
    layers = {m["layer"] for m in MAN.bench["per_layer"]
              if m["name"] not in NEW}
    assert layer in layers                  # no layer of its own


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metrics_file_names_its_reader(name):
    reader, params = NEW[name][5:]
    spec = MAN.metric(name)
    assert spec["name"] == name and spec["what"]
    assert (spec["reader"], spec.get("params")) == (reader, params)
    assert callable(MAN.reader(reader).read)


def test_the_cell_reports_its_own_metrics_and_those_without_a_list():
    # no accepted metric's list was extended
    mine = {m["name"] for m in MAN.metrics_for("per_layer", CELL)}
    assert set(NEW) <= mine
    assert {m["name"] for m in MAN.bench["per_layer"]
            if CELL in m.get("workloads", ())} == set(NEW)
    assert mine - set(NEW) == {
        m["name"] for m in MAN.bench["per_layer"] if "workloads" not in m}
    assert {"plan_compile_s", "compiles_in_window",
            "device_idle_share"} <= mine
    assert {m["name"] for m in MAN.metrics_for("end_to_end", CELL)} == {
        "tflops_per_chip", "step_s_p50", "peak_hbm_gib", "setup_s"}


def test_the_configuration_file_states_the_deployment():
    config = MAN.config(CONFIG)
    workload = MAN.workload(CELL)
    assert workload["traffic"] == {"n": 98304, "nb": 4096}
    assert workload["dry"] == {"n": 512, "nb": 64}
    assert workload["chips"] == 4 and workload["who"]
    assert config["sizes"] == {"nb": 4096, "dtype": "float32"}
    assert config["knobs"] == {"potrf.trsm_hook": "gemm"}
    assert config["device_grid"] == [2, 2]
    assert config["reduced"] == [] and config["nb_cores"] == 4
    assert config["taskpool"].endswith(":build_potrf")
    assert config["driver"] == "ptg_multidev_factorization"
    assert config["reference"] == "dpotrf_ptg_multidev_reference"
    assert callable(MAN.driver(config["driver"]).build)
    assert callable(MAN.reference(config["reference"]).on_probe_chip)
    for key in ("nb", "device_grid", "nb_cores", "precision", "tester"):
        assert config["assumed"][key]
    assert "memory" in config["assumed"]["tester"]
    for word in ("L L^T = A0", "advised", "every task", "fresh",
                 "EVERY chip", "ITS stored share"):
        assert word in config["guarantees"], word
    for word in ("advise_on_devices", "parsec.init(nb_cores=4)", "-g 4",
                 "once per version"):
        assert word in config["deployment"], word
    assert 0 < config["correct"]["limit"] < 1e-4
    for word in ("bfloat16", "4 times"):
        assert word in config["correct"]["reason"], word
    # both readings of the storage limit, and the limit between them
    storage = config["storage"]
    assert 1.0 < storage["peak_over_stored_limit"] < 4.0
    for word in ("keeps every", "ITS stored share", "REMOTE_BYTES"):
        assert word in storage["reason"], word


# -- the benchmark's own arithmetic of the layout ---------------------------

def test_the_cells_counts_are_the_ones_its_files_state():
    grid, nt = (2, 2), 24
    d = _driver({"n": 98304, "nb": 4096})
    assert d.tasks_per_step == 2600 == sum(d.tasks_by_chip.values())
    assert d.tasks_by_chip == {0: 650, 1: 572, 2: 650, 3: 728}
    assert abs(d.ops_per_step - ops.potrf_ops(98304)) < 1
    assert round(d.ops_per_step / 1e12, 1) == 316.7
    tile = 4096 * 4096 * 4
    assert {c: b // tile for c, b in d.stored_by_chip.items()} == {
        0: 78, 1: 66, 2: 78, 3: 78}
    assert sum(d.stored_by_chip.values()) == 300 * tile     # 18.75 GiB
    assert max(d.stored_by_chip.values()) / 2 ** 30 == 4.875
    assert d.min_remote_bytes == 552 * tile                 # 34.5 GiB
    into = ops_multidev.potrf_min_remote_bytes_into(nt, 4096, 4, grid)
    assert [round(into[c] / 2 ** 30, 3) for c in range(4)] == [
        4.125, 12.375, 13.125, 4.875]
    # every tile with a reader on another chip: the panel tiles and the
    # diagonal ones but the last
    readers = ops_multidev.potrf_remote_readers(nt, grid)
    assert len(readers) == 276 + 23
    # (m, k) is read along row m and along column m: on the chips of
    # one row of the grid and of one column, its own among them
    assert max(len(chips) for chips in readers.values()) == 2
    # one chip: nothing crosses
    assert ops_multidev.potrf_min_remote_bytes(nt, 4096, 4, (1, 1)) == 0
    assert ops_multidev.potrf_tasks_by_chip(nt, (1, 1)) == {0: 2600}


# -- the new reader, on hand-made records -----------------------------------

def _record(counts, steps=10, trace=None, least=100, peaks=None):
    return {"setup": {"program_counters": counts,
                      "multidev": {"min_remote_bytes_per_step": least}},
            "window": {"attempted": steps, "failed": 0},
            "trace": trace or {}, "peaks": peaks}


def test_the_reader_reads_the_copies_off_the_window_counters():
    counts = {"remote_bytes_in": 1100, "remote_copies": 11,
              "remote_hits": 33, "remote_bytes_in.tpu0": 100,
              "remote_bytes_in.tpu1": 600, "remote_bytes_in.tpu2": 400}
    rec = _record(counts, trace={"window_s": 4.0, "steps": 2},
                  peaks={"ici_bits_per_s": 8 * 1000.0})
    read = READER.read
    assert read(rec, {"key": "remote_gib_per_step"}) == 110 / 2 ** 30
    assert read(rec, {"key": "remote_bytes_over_min"}) == 1.1
    assert read(rec, {"key": "remote_reuse_share"}) == 75.0
    # 60 bytes a step into the chip that received most, 2 s a step,
    # 1000 bytes a second the link
    assert read(rec, {"key": "ici_peak_share"}) == 100.0 * 60 / 2 / 1000
    with pytest.raises(ValueError):
        read(rec, {"key": "no_such"})


def test_the_reader_reads_nothing_where_there_is_nothing():
    for key in ("remote_gib_per_step", "remote_bytes_over_min",
                "remote_reuse_share", "ici_peak_share"):
        # a program without the counters (the parent), a driver that
        # leaves none, a window without a step
        assert READER.read(_record({}), {"key": key}) is None
        assert READER.read({"setup": {}, "window": {
            "attempted": 3, "failed": 0}, "trace": {}, "peaks": None},
            {"key": key}) is None
        assert READER.read(_record({"remote_bytes_in": 5}, steps=0),
                           {"key": key}) is None
    counts = {"remote_bytes_in": 5, "remote_bytes_in.tpu0": 5}
    # no device trace (a CPU rehearsal), no published link
    assert READER.read(_record(counts), {"key": "ici_peak_share"}) is None
    assert READER.read(_record(counts, trace={"window_s": 1.0, "steps": 1},
                               peaks={}), {"key": "ici_peak_share"}) is None


def test_device_seconds_are_put_together_from_the_programs_durations():
    """The reader of a device trace the profiler has cut: a class's mean
    program duration x the graph's count of its tasks on a chip."""
    trace = MAN.reader("multidev_trace")
    durations = {"GEMM": [1e-3, 3e-3], "TRSM": [5e-3], "POTRF": [2e-3],
                 "SYRK": [1e-3]}
    counts = {0: {"POTRF": 1, "TRSM": 2, "SYRK": 3, "GEMM": 10},
              1: {"POTRF": 0, "TRSM": 2, "SYRK": 0, "GEMM": 20}}
    by_chip = trace.seconds_by_chip(durations, counts)
    assert by_chip == pytest.approx({0: 0.002 + 0.010 + 0.003 + 0.020,
                                     1: 0.010 + 0.040})
    # a class of the graph that no event shows: nothing is made up
    assert trace.seconds_by_chip({"GEMM": [1e-3]}, counts) is None
    # a class without tasks on a chip needs no event
    assert trace.seconds_by_chip(
        {"GEMM": [1e-3], "TRSM": [1e-3]},
        {1: counts[1]}) == pytest.approx({1: 0.022})
    assert trace.PROGRAM.match("jit_parsec_GEMM_x1(8772201488519004805)")
    assert not trace.PROGRAM.match("jit_parsec_GEMM_x4(1)")
    assert not trace.PROGRAM.match("jit__lambda(3)")
    # nothing to read: no counts from the driver, no trace of this run
    record = {"cell": CELL, "setup": {}, "peaks": None, "chips": 4}
    for key in ("device_step_s", "tile_roofline", "busy_max_over_min"):
        assert trace.read(record, {"key": key}) is None
    record["setup"] = {"multidev": {"tasks_by_chip_class": counts}}
    assert trace.read(record, {"key": "device_step_s"}) is None
    by_class = ops_multidev.potrf_tasks_by_chip_class(24, (2, 2))
    assert {c: sum(n.values()) for c, n in by_class.items()} == \
        ops_multidev.potrf_tasks_by_chip(24, (2, 2))
    assert [by_class[c]["TRSM"] for c in range(4)] == [66, 66, 78, 66]
    assert sum(n["GEMM"] for n in by_class.values()) == 2024


def test_the_links_peak_is_published():
    peaks = MAN.peaks("TPU v5 lite")
    assert peaks["ici_bits_per_s"] == 1600e9


# -- the driver's check, on four virtual devices ----------------------------

def _driver(sizes, seed=11):
    config = MAN.config(CONFIG)
    sizes = {**config["sizes"], **sizes}
    return MAN.driver(config["driver"]).build(
        config, sizes, seed, jax.devices()[:4], Spans(),
        MAN.reference(config["reference"]))


needs_four = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs four (virtual) devices")


@needs_four
def test_the_check_holds_every_guarantee():
    d = _driver({"n": 256, "nb": 32})
    try:
        facts = d.setup()
        assert facts["multidev"]["min_remote_bytes_per_step"] == \
            ops_multidev.potrf_min_remote_bytes(8, 32, 4, (2, 2))
        A = d.step(d.generate(1))
        assert d.finite(A)
        ok, detail = d.check(A, 1)
        assert ok, detail
        assert detail["tiles_off_their_chip"] == 0
        assert detail["tasks_by_module"] == detail["tasks_wanted"]
        # every tile was made on the chip it is advised to
        for (i, j) in d.lower:
            assert A.data_of((i, j)).devices() == {
                d.devices[d.chip_of(i, j)]}
        # another step's matrix: the residual fails
        ok, detail = d.check(A, 2)
        assert not ok and detail["residual"] > detail["limit"]
        # a wrong tile
        good = A.data_of((5, 2))
        A.write_tile((5, 2), good + 0.5)
        ok, detail = d.check(A, 1)
        assert not ok and detail["residual"] > detail["limit"]
        # the right values on another chip than the tile is advised to
        elsewhere = [dev for dev in d.devices if {dev} != good.devices()][0]
        A.write_tile((5, 2), jax.device_put(good, elsewhere))
        ok, detail = d.check(A, 1)
        assert not ok and detail["tiles_off_their_chip"] == 1
        assert detail["residual"] <= detail["limit"]
        A.write_tile((5, 2), good)
        assert d.check(A, 1)[0]
        # a chip over its share of the storage guarantee
        d._peak_by_chip = lambda: {c: int(1.01 * b) for c, b in
                                   d.storage_limit_by_chip.items()}
        ok, detail = d.check(A, 1)
        assert not ok and detail["residual"] <= detail["limit"]
        del d._peak_by_chip
        # a task counted on another module than its tile's
        d.tasks_by_chip = {**d.tasks_by_chip, 0: d.tasks_by_chip[0] + 1,
                           1: d.tasks_by_chip[1] - 1}
        assert not d.check(A, 1)[0]
    finally:
        d.close()


@needs_four
def test_a_tile_rounded_to_bfloat16_fails_the_check():
    d = _driver({"n": 512, "nb": 64})
    try:
        d.setup()
        A = d.step(d.generate(1))
        assert d.check(A, 1)[0]
        good = A.data_of((3, 3))
        A.write_tile((3, 3), good.astype(jnp.bfloat16).astype(jnp.float32))
        ok, detail = d.check(A, 1)
        assert not ok and detail["residual"] > detail["limit"]
        assert detail["tiles_off_their_chip"] == 0
    finally:
        d.close()


# -- the rehearsal -----------------------------------------------------------

@needs_four
def test_the_rehearsal_on_four_devices_prints_the_new_metrics(
        capsys, monkeypatch):
    monkeypatch.setenv("PARSEC_MCA_runtime_native_dtd", "0")
    assert main(["--workload", CELL, "--seed", "3000000019", "--seconds",
                 "1.0", "--trace", "1", "--dry-run-cpu=4"], ROOT) == 0
    out = capsys.readouterr().out
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2
    assert result["device"]["platform"] == "cpu"
    metrics = result["metrics"]
    assert all(name.endswith(DRY_SUFFIX) for name in metrics)
    printed = {name[:-len(DRY_SUFFIX)] for name in metrics}
    # a CPU trace has no device plane: the device-trace four are absent
    assert set(NEW) - DEVICE_TRACE <= printed
    assert not printed & DEVICE_TRACE
    value = {n[:-len(DRY_SUFFIX)]: m["value"] for n, m in metrics.items()}
    assert value["md_tasks_on_chip_share"] == 100.0
    assert value["md_owner_hit_share"] == 100.0
    assert value["md_remote_bytes_over_min"] == 1.0
    assert 0 < value["md_remote_reuse_share"] < 100
    assert value["md_stage_in_us_per_task"] > 0
    assert value["md_remote_gib_per_step"] == \
        ops_multidev.potrf_min_remote_bytes(8, 64, 4, (2, 2)) / 2 ** 30
    assert value["compiles_in_window"] == 0
    assert "[check] correct=True" in out


@needs_four
def test_the_rehearsal_without_a_trace_prints_the_end_to_end_metrics(capsys):
    assert main(["--workload", CELL, "--seed", "5", "--seconds", "0.5",
                 "--trace", "0", "--dry-run-cpu=4"], ROOT) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True
    printed = {n[:-len(DRY_SUFFIX)] for n in result["metrics"]}
    assert printed == {"tflops_per_chip", "step_s_p50", "setup_s"}
