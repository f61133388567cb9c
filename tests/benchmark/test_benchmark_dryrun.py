"""Every cell rehearsed on the CPU through the same harness and drivers
the chip run uses: the last line's shape, ``correct`` against numpy at
tiny size, and the refusals. Nothing here loads libtpu or starts a child
that does."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import generate  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.run import DRY_SUFFIX, Spans, main  # noqa: E402

MAN = Manifest(ROOT)
CELLS = [(w["name"], w["chips"]) for w in MAN.bench["workloads"]]


def _dry_run(capsys, cell, chips, trace):
    rc = main(["--workload", cell, "--seed", "11", "--seconds", "0.3",
               "--trace", str(trace), f"--dry-run-cpu={chips}"])
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(lines[-1]), lines


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell,chips", CELLS)
def test_dry_run_prints_the_contracts_last_line(capsys, cell, chips, trace):
    last, lines = _dry_run(capsys, cell, chips, trace)
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is True
    assert last["attempted"] >= 1 and last["failed"] == 0
    assert last["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": last["device"]["count"],
                              "memory_peak_bytes": 0}
    assert last["device"]["count"] >= chips
    # a CPU run prints nothing under a device metric's name
    section = "per_layer" if trace else "end_to_end"
    allowed = {m["name"] + DRY_SUFFIX: m["unit"]
               for m in MAN.metrics_for(section, cell)}
    assert last["metrics"], lines
    for name, m in last["metrics"].items():
        assert name in allowed and m["unit"] == allowed[name]
        assert isinstance(m["value"], (int, float))
    if trace:
        assert last["metrics"]["compiles_in_window" + DRY_SUFFIX][
            "value"] == 0
        # no TPU plane in a CPU trace: the device-trace readers found
        # nothing to read and their metrics are left out
        assert "device_idle_share" + DRY_SUFFIX not in last["metrics"]
    else:
        assert set(last["metrics"]) == set(allowed) - {
            "peak_hbm_gib" + DRY_SUFFIX}
        assert all(m["value"] > 0 for m in last["metrics"].values())
    assert any(line.startswith("[window] steps=") for line in lines)


def test_sharded_dry_run_checks_four_distinct_shards(capsys):
    _last, lines = _dry_run(capsys, "potrf_panel_n65536_x4", 4, 0)
    check = [line for line in lines if line.startswith("[check]")][0]
    assert "shard_devices=[0, 1, 2, 3]" in check


def _driver(cell, sizes):
    import jax
    entry = MAN.cell(cell)
    config = MAN.config(entry["config"])
    return MAN.driver(config["driver"]).build(
        config, {**config["sizes"], **sizes}, 5,
        jax.devices()[:entry["chips"]], Spans(),
        MAN.reference(config["reference"]))


@pytest.mark.parametrize("cell,sizes", [
    ("potrf_panel_n40960", {"n": 128, "nb": 32, "sharded": False}),
    ("potrf_panel_n65536_x4", {"n": 128, "nb": 32, "sharded": True})])
def test_panel_factor_agrees_with_numpy_cholesky(cell, sizes):
    d = _driver(cell, sizes)
    ref = MAN.reference("dpotrf_panel_reference")
    try:
        d.setup()
        out = d.step(d.generate(2))
        ok, detail = d.check(out, 2)
        assert ok and detail["residual"] < 1e-5
        n, nb = sizes["n"], sizes["nb"]
        want = np.linalg.cholesky(ref.dense_a0(generate.step_key(5, 2),
                                               n, nb))
        got = np.triu(np.asarray(out["A"], np.float64)).T
        assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()
        # the check is of THIS step's input: another step's key fails it
        assert not d.check(out, 3)[0]
        assert d.tasks_per_step > 0 and d.finite(out)
    finally:
        d.close()


def test_dtd_gemm_agrees_with_numpy_matmul_and_a_wrong_tile_fails():
    import jax.numpy as jnp
    d = _driver("gemm_dtd_nb1024", {"m": 96, "n": 64, "k": 64, "nb": 32})
    try:
        d.setup()
        c = d.generate(1)
        c0 = c.to_array().astype(np.float64)
        c = d.step(c)
        ok, detail = d.check(c, 1)
        assert ok and detail["rel_frobenius"] < 1e-5
        want = d.A.to_array().astype(np.float64) @ d.B.to_array() + c0
        assert np.abs(c.to_array() - want).max() <= 1e-4
        assert d.tasks_per_step == 3 * 2 * 2 and d.finite(c)
        assert d.counters()["engine"] in ("python", "native")
        c.write_tile((0, 0), jnp.asarray(c.data_of((0, 0))) + 0.5)
        assert not d.check(c, 1)[0]
        c.write_tile((0, 0), jnp.full((32, 32), jnp.nan))
        assert not d.finite(c)
    finally:
        d.close()


def _child(args, cwd=ROOT, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"          # the child loads no libtpu
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "-m", "benchmark.run", *args],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=cwd)


def test_no_tpu_is_an_error_and_prints_no_result():
    proc = _child(["--workload", "gemm_dtd_nb4096", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"metrics"' not in proc.stdout and "{" not in proc.stdout


def test_fewer_chips_than_the_cell_asks_is_an_error():
    proc = _child(["--workload", "potrf_panel_n65536_x4", "--seed", "1",
                   "--seconds", "1", "--trace", "0", "--dry-run-cpu=4"],
                  env_extra={"XLA_FLAGS":
                             "--xla_force_host_platform_device_count=2"})
    assert proc.returncode != 0
    assert "asks for 4 chips, JAX found 2" in proc.stderr
    assert "{" not in proc.stdout


def test_an_unknown_workload_is_an_error_and_prints_no_result():
    proc = _child(["--workload", "nope", "--seed", "1", "--seconds", "1",
                   "--trace", "0", "--dry-run-cpu"])
    assert proc.returncode != 0 and "nope" in proc.stderr
    assert "{" not in proc.stdout


def test_the_benchmark_alone_in_a_directory_fails(tmp_path):
    """BENCHMARK.json and the files under paths are not enough: without
    the system under test there is no result."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _child(["--workload", "gemm_dtd_nb4096", "--seed", "1",
                   "--seconds", "1", "--trace", "0", "--dry-run-cpu"],
                  cwd=str(tmp_path), env_extra={"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert "{" not in proc.stdout
