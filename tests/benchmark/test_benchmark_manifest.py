"""BENCHMARK.json against the contract, and every name it holds against
the files under benchmark/: a cell, a configuration, a driver, a metric
and a reader each resolve by name, and each can be added as new files
plus new entries without editing a file that is there."""

import hashlib
import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.manifest import Manifest, ManifestError  # noqa: E402

MAN = Manifest(ROOT)
BENCH = MAN.bench
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]
E2E = [m["name"] for m in BENCH["end_to_end"]]
LAYER = [m["name"] for m in BENCH["per_layer"]]
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_contract_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert 1 <= len(BENCH["paths"]) <= 16
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 2 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128
    assert len(BENCH["command"]) <= 32
    # the command names no file of the repo outside paths
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p) for p in BENCH["paths"])


def test_run_seconds_fits_the_check_with_24_cells():
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end",
                                     "per_layer"])
def test_names_are_plain_and_used_once(section):
    names = [e["name"] for e in BENCH[section]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)


def test_names_are_unique_across_sections():
    names = CELLS + CONFIGS + E2E + LAYER
    assert len(set(names)) == len(names)


def test_files_under_paths_have_plain_names():
    plain = re.compile(r"^[A-Za-z0-9_./-]+$")
    for p in BENCH["paths"]:
        assert plain.match(p) and len(p) <= 200
        for d, _dirs, files in os.walk(os.path.join(ROOT, p)):
            if "__pycache__" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert plain.match(rel), rel


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    assert len(four) <= max(1, len(CELLS) // 4)


def test_pairs_of_config_and_traffic_appear_once():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert {w["config"] for w in BENCH["workloads"]} == set(CONFIGS)
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"],
                         ids=lambda e: e["name"])
def test_why_fits(entry):
    assert 0 < len(entry["why"]) <= 200


@pytest.mark.parametrize("metric", BENCH["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_entry(metric):
    assert set(metric) <= {"name", "unit", "better", "bound", "source",
                           "workloads"}
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1
    if metric["name"] == "setup_s":
        assert metric["bound"] == 0.1


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_entry(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    assert metric["moves"] in E2E
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"
    # a layer named here is a layer PERF.md describes
    with open(os.path.join(ROOT, "PERF.md")) as fh:
        assert metric["layer"] in fh.read()


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    entry = MAN.cell(cell)
    workload = MAN.workload(cell)
    assert workload["name"] == cell and workload["why"]
    assert set(workload["dry"]) <= set(workload["traffic"]) | {"nb"}
    config = MAN.config(entry["config"])
    assert config["name"] == entry["config"]
    assert MAN.driver(config["driver"]).build
    assert MAN.reference(config["reference"])
    # every cell reports setup_s, another end-to-end metric and a layer
    e2e = [m["name"] for m in MAN.metrics_for("end_to_end", cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert MAN.metrics_for("per_layer", cell)


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file_states_its_source_and_limit(name):
    entry = MAN._entry("configs", name)
    config = MAN.config(name)
    assert entry["file"].startswith("benchmark/configs/")
    assert config["source"] == entry["source"]
    assert config["source"].startswith("https://")
    assert config["reduced"] == entry["reduced"]
    assert config["correct"]["limit"] > 0 and config["correct"]["reason"]
    assert config["guarantees"] and config["assumed"]


@pytest.mark.parametrize("name", E2E + LAYER)
def test_metric_resolves_to_a_reader(name):
    spec = MAN.metric(name)
    assert spec["name"] == name and spec["what"]
    assert callable(MAN.reader(spec["reader"]).read)


def test_every_metric_and_workload_file_is_listed():
    """No orphan data file: what sits in the directories is in use."""
    listed = set(E2E + LAYER)
    on_disk = {f[:-5] for f in os.listdir(os.path.join(MAN.dir, "metrics"))}
    assert on_disk == listed
    assert {f[:-5] for f in os.listdir(
        os.path.join(MAN.dir, "workloads"))} == set(CELLS)


@pytest.mark.parametrize("bad", ["nope", "../x", "a b", ""])
def test_unknown_or_unclean_names_are_refused(bad):
    with pytest.raises(ManifestError):
        MAN.cell(bad)
    with pytest.raises(ManifestError):
        MAN.driver(bad)
    with pytest.raises(ManifestError):
        MAN.reader(bad)


def test_peaks_refuse_an_unknown_device_kind():
    v5e = MAN.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
    for kind in ("cpu", "TPU v4", "TPU v5e", ""):
        with pytest.raises(ManifestError, match="no peaks"):
            MAN.peaks(kind)


# ---------------------------------------------------------------------------
# adding a cell, a configuration with its driver, a metric with its reader:
# new files and new entries only
# ---------------------------------------------------------------------------

def _copy_tree(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return {p: hashlib.sha256(open(p, "rb").read()).hexdigest()
            for d, _s, fs in os.walk(tmp_path / "benchmark")
            for p in (os.path.join(d, f) for f in fs)}


DRIVER = '''
import jax, jax.numpy as jnp
class Doubler:
    ops_per_step, bytes_per_step, tasks_per_step = 1.0, 8.0, 1
    def __init__(self, sizes, seed): self.n, self.seed = sizes["n"], seed
    def setup(self): return {}
    def generate(self, step, recycle): return jnp.full((self.n,), float(step))
    def step(self, x): return jax.block_until_ready(2 * x)
    def finite(self, out): return bool(jnp.isfinite(out).all())
    def counters(self): return {}
    def check(self, out, step):
        return self.ref.expect(out, step), {}
    def close(self): pass
def build(config, sizes, seed, devices, spans, reference):
    d = Doubler(sizes, seed); d.ref = reference; return d
'''


def test_a_cell_a_configuration_and_a_metric_are_new_files_only(
        tmp_path, capsys):
    from benchmark.run import main
    before = _copy_tree(tmp_path)
    bdir = tmp_path / "benchmark"
    # a cell of a configuration that is there: data only
    (bdir / "workloads" / "gemm_dtd_tiny.json").write_text(json.dumps({
        "name": "gemm_dtd_tiny", "config": "dgemm_dtd", "chips": 1,
        "traffic": {"m": 128, "n": 128, "k": 64, "nb": 32},
        "dry": {}, "why": "throw-away"}))
    # a configuration with its reference and its driver
    (bdir / "configs" / "doubling.json").write_text(json.dumps({
        "name": "doubling", "driver": "doubler", "reference": "doubling_ref",
        "sizes": {}, "knobs": {}}))
    (bdir / "configs" / "doubling_ref.py").write_text(
        "def expect(out, step):\n"
        "    return bool((out == 2.0 * step).all())\n")
    (bdir / "drivers" / "doubler.py").write_text(DRIVER)
    (bdir / "workloads" / "doubling_n8.json").write_text(json.dumps({
        "name": "doubling_n8", "config": "doubling", "chips": 1,
        "traffic": {"n": 8}, "dry": {}, "why": "throw-away"}))
    # a per-layer metric with its reader
    (bdir / "metrics" / "steps_done.json").write_text(json.dumps({
        "name": "steps_done", "reader": "steps_done", "what": "count"}))
    (bdir / "readers" / "steps_done.py").write_text(
        "def read(record, params):\n"
        "    return len(record['window']['step_s'])\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "doubling", "source": "https://example.org/doubling",
        "file": "benchmark/configs/doubling.json", "reduced": [],
        "why": "throw-away"})
    bench["workloads"] += [
        {"name": "gemm_dtd_tiny", "config": "dgemm_dtd", "traffic": "tiny",
         "chips": 1, "why": "throw-away"},
        {"name": "doubling_n8", "config": "doubling", "traffic": "n8",
         "chips": 1, "why": "throw-away"}]
    bench["per_layer"].append({
        "name": "steps_done", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "harness",
        "moves": "tflops_per_chip"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    for cell in ("gemm_dtd_tiny", "doubling_n8"):
        assert main(["--workload", cell, "--seed", "3", "--seconds", "0.2",
                     "--trace", "1", "--dry-run-cpu"],
                    root=str(tmp_path)) == 0
        last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert last["correct"] is True and last["failed"] == 0
        assert last["metrics"]["steps_done_cpu_dryrun"]["value"] == \
            last["attempted"] > 0
    # nothing that was there changed
    for path, digest in before.items():
        assert hashlib.sha256(open(path, "rb").read()).hexdigest() == digest


def test_a_workload_file_that_disagrees_with_the_manifest_is_refused(
        tmp_path):
    _copy_tree(tmp_path)
    path = tmp_path / "benchmark" / "workloads" / "gemm_dtd_nb1024.json"
    wl = json.loads(path.read_text())
    wl["chips"] = 4
    path.write_text(json.dumps(wl))
    with pytest.raises(ManifestError, match="chips"):
        Manifest(str(tmp_path)).workload("gemm_dtd_nb1024")
